"""chip_smoke.py's phases at a tiny size on the CPU.

The chip run itself needs a TPU; these tests drive the same functions at
16^3 with interpret-mode Pallas kernels for the kernel and write phases
(the reads decode through the jnp reference: interpret-mode decode compiles
for seconds per plane-group shape), check that every failure the smoke
guards against does fail it, and pin the exact last-line format.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO

sys.path.insert(0, REPO)
import chip_smoke as cs  # noqa: E402

from repro.store import DatasetStore, RetrievalService  # noqa: E402
from repro.tune import RefactorConfig  # noqa: E402

SIDE = 16
CHUNK = 2048


@pytest.fixture(scope="module")
def tiny_store(tmp_path_factory):
    """A tiny store of all four smoke fields (jnp backend: fast)."""
    root = str(tmp_path_factory.mktemp("smoke_store"))
    fields = cs.make_fields(SIDE, SIDE, seed=3)
    cs.phase_write(root, fields, RefactorConfig(backend="jnp"), CHUNK)
    return root, fields


def test_smoke_phases_tiny_interpret(tmp_path, capsys):
    ok = cs.run_single(str(tmp_path / "store"), side=SIDE,
                       velocity_side=SIDE, chunk_elems=CHUNK,
                       backend="pallas_interpret", read_backend="jnp",
                       kernel_elems=4096)
    out = capsys.readouterr().out
    assert ok, out
    assert "FAIL" not in out
    for tol in cs.LADDER:
        assert f"check OK   ladder density tol {tol:.0e}" in out
    for tau in cs.QOI_TAUS:
        assert f"check OK   qoi V_total tau {tau:.0e}: guarantee held" in out
    for design in cs.DESIGNS:
        assert f"check OK   kernel {design}" in out
    assert "check OK   degraded groups 0 == 0" in out
    assert "smoke timing (not a metric): write" in out
    assert not os.path.exists(tmp_path / "store")   # scratch store removed


def test_ladder_fails_on_violated_bound(tiny_store):
    root, fields = tiny_store
    svc = RetrievalService(DatasetStore.open(root))
    good = cs.phase_ladder(svc, "density", fields["density"], (1e-2, 1e-4))
    assert all(ok for _, ok in good)
    # a host copy the store never held: the error exceeds every bound
    bad = cs.phase_ladder(svc, "density", fields["density"] + 1.0,
                          (1e-2, 1e-4))
    assert not any(ok for _, ok in bad)


def test_qoi_fails_on_violated_guarantee(tiny_store):
    root, fields = tiny_store
    svc = RetrievalService(DatasetStore.open(root))
    vs = [fields[n] for n in ("vx", "vy", "vz")]
    assert all(ok for _, ok in cs.phase_qoi(svc, ("vx", "vy", "vz"), vs))
    wrong = [v * 1.5 for v in vs]
    assert not any(ok for _, ok in cs.phase_qoi(svc, ("vx", "vy", "vz"),
                                                wrong))


def _stats(degraded=0, fetch_errors=0, errors_propagated=0):
    return {"sessions": {1: {"degraded_groups": degraded}, 2:
                         {"degraded_groups": 0}},
            "backend": {"fetch_errors": fetch_errors},
            "serving": {"errors_propagated": errors_propagated}}


@pytest.mark.parametrize("stats,retry,failing", [
    (_stats(), [], None),
    (_stats(degraded=2), [], "degraded groups"),
    (_stats(fetch_errors=1), [], "backend fetch errors"),
    (_stats(errors_propagated=1), [], "serving errors"),
    (_stats(), [{"retries": 1, "exhausted": 0}], "backend retries"),
    (_stats(), [{"retries": 0, "exhausted": 1}], "backend retries"),
])
def test_health_checks_fail_on_hidden_failures(stats, retry, failing):
    checks = cs.health_checks(stats, retry)
    failed = [name for name, ok in checks if not ok]
    if failing is None:
        assert not failed
    else:
        assert len(failed) == 1 and failed[0].startswith(failing)
        assert not cs.report(checks)


def test_health_checks_clean_on_real_service(tiny_store):
    root, fields = tiny_store
    store = DatasetStore.open(root)
    svc = RetrievalService(store)
    cs.phase_ladder(svc, "density", fields["density"], (1e-3,))
    checks = cs.health_checks(svc.stats(), cs._retry_stats(store.backend))
    assert [ok for _, ok in checks] == [True] * 4


def test_result_line_format():
    stub = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = cs.result_line(True, stub)
    assert line == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')
    assert json.loads(cs.result_line(False, stub))["ok"] is False


def _run_script(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_main_exits_nonzero_without_tpu():
    r = _run_script(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU found" in r.stderr


def test_script_alone_exits_nonzero(tmp_path):
    """Copied out of the repository, the script cannot run the program."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run_script(str(tmp_path), "chip_smoke.py")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_sharded_smoke_on_virtual_devices(subproc, tmp_path):
    """The --chips 4 path on four virtual CPU devices: byte-identical
    segments, equal reads, dispatches on every device."""
    out = subproc(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke as cs
        ok = cs.run_sharded({str(tmp_path / "store")!r},
                            n_devices=4, side=16, chunk_elems=1024,
                            backend="jnp")
        print("SHARDED_OK", ok)
        """, n_devices=4)
    assert "SHARDED_OK True" in out, out
    assert "check OK   sharded segments byte-identical" in out
    assert "dispatches_by_device {0: 1, 1: 1, 2: 1, 3: 1}" in out


_CACHE_SCRIPT = """
import os, jax, jax.numpy as jnp
from repro import compile_cache
d = compile_cache.enable()
print("DIR", d)
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    print("ENTRIES", len(os.listdir(d)))
"""


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_placement(tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins and receives even a fast compile;
    without it the cache is the fixed ``<checkout>/.jax_cache``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    want = str(tmp_path) if env_dir else os.path.join(REPO, ".jax_cache")
    assert f"DIR {want}\n" in r.stdout
    if env_dir:
        assert int(r.stdout.split("ENTRIES ")[1]) > 0
