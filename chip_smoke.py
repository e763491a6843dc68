"""Chip smoke: the write -> store -> progressive-retrieval path, once, on TPU.

    python chip_smoke.py              # one chip: the main path at NYX size
    python chip_smoke.py --chips 4    # four chips: sharded write/read only

One chip: NYX-shaped fields (``repro.data.fields``, made from ``--seed``)
are written through ``DatasetWriter`` — a 512^3 fp32 density variable plus
the three velocity components, cut to 256^3 (see ``VELOCITY_SIDE``) — then
the store is reopened cold and served
through ``RetrievalService``: the relative tolerance ladder 1e-1 .. 1e-5 on
the density, then a guaranteed-QoI retrieval of V_total = vx^2+vy^2+vz^2 at
two taus.  Every returned bound is checked against the host copy of the
data, and the run fails on any degraded group, backend error or retry.

``--chips 4`` runs only the sharded path and its oracle: the density is
written on a 4-device mesh and on a mesh of one; the segment bytes must be
identical, both stores must read back equal at two tolerances, and the
fused dispatches must spread over all four devices.  A program's compile
key on a TPU includes its device, so before the timed phases one thread per
device writes and reads a few chunks on that device alone: the per-device
compiles overlap instead of running one device after another.

Everything runs in this one process (a chip belongs to one process).  The
script refuses to run without a TPU: it exits non-zero and prints no result.
Its last line is ``{"ok": ..., "device": {"platform", "kind", "count"}}``.
Wall times printed on the way are smoke timing, not metrics.
"""
from __future__ import annotations

import argparse
import filecmp
import json
import os
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import decompose as dc  # noqa: E402
from repro.core import qoi as qq  # noqa: E402
from repro.core import refactor as rf  # noqa: E402
from repro.core import refactor_fused as rff  # noqa: E402
from repro.core import sharded as shd  # noqa: E402
from repro.data.fields import DATASETS, gaussian_field, velocity_field  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.store import (DatasetStore, DatasetWriter,  # noqa: E402
                         RetrievalService, RetryingBackend)
from repro.tune import RefactorConfig  # noqa: E402

NYX = DATASETS["nyx"]
LADDER = (1e-1, 1e-2, 1e-3, 1e-4, 1e-5)   # relative to the value range
QOI_TAUS = (1e-2, 1e-4)                    # absolute, on V_total
SHARDED_TOLS = (1e-1, 1e-2)                # relative
# The velocity components are cut to 256^3; the density stays at NYX's
# 512^3.  On one TPU v5e a cold run with 256^3 velocities takes about 9
# minutes.  At 512^3 the velocities add 336 chunks to the write, about a
# minute of data generation and 8x the QoI work; projected from measured
# phase times that is 10.5-12.5 minutes cold, over the 10 minutes a
# smoke run is allowed before its inputs are cut.
VELOCITY_SIDE = 256
WARM_CHUNKS = 4        # chunks per device written by the sharded warm-up
DESIGNS = ("register_block", "locality", "shuffle")

Check = Tuple[str, bool]


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ------------------------------------------------------------------ device --

def device_info() -> Dict[str, object]:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def result_line(ok: bool, info: Dict[str, object]) -> str:
    return json.dumps({"ok": bool(ok), "device": {
        "platform": info["platform"], "kind": info["kind"],
        "count": info["count"]}})


class CompileMeter:
    """Backend compiles (count, seconds) and persistent-cache hits, read
    from JAX's monitoring events; ``phase`` reports the deltas of a block."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def phase(self, name: str, fn: Callable[[], object]):
        c0, s0, h0 = self.compiles, self.compile_s, self.cache_hits
        t0 = time.perf_counter()
        out = fn()
        say(f"smoke timing (not a metric): {name}: wall "
            f"{time.perf_counter() - t0:.3f} s; compile "
            f"{self.compile_s - s0:.3f} s over {self.compiles - c0} "
            f"programs; {self.cache_hits - h0} persistent-cache hits")
        return out


_METER: List[CompileMeter] = []


def meter() -> CompileMeter:
    if not _METER:
        _METER.append(CompileMeter())
    return _METER[0]


def report(checks: Sequence[Check]) -> bool:
    for name, ok in checks:
        say(f"check {'OK  ' if ok else 'FAIL'} {name}")
    return all(ok for _, ok in checks)


# ------------------------------------------------------------------ phases --

def make_fields(side: int, velocity_side: int, seed: int
                ) -> Dict[str, np.ndarray]:
    """NYX-shaped proxies: a density field and three velocity components."""
    out = {"density": gaussian_field((side,) * 3, NYX["slope"], seed)}
    vs = velocity_field((velocity_side,) * 3, seed=seed + 1)
    out.update(zip(("vx", "vy", "vz"), vs))
    return out


def phase_kernels(n: int, backend: str, seed: int) -> List[Check]:
    """All three bitplane designs, encode and prefix decode, bit-exact
    against the NumPy / jnp references."""
    rng = np.random.default_rng(seed)
    mag = rng.integers(0, 1 << 24, size=n, dtype=np.uint32)
    checks = []
    for design in DESIGNS:
        planes = kops.encode_bitplanes(jnp.asarray(mag), 24, design, backend)
        enc_ok = np.array_equal(np.asarray(planes),
                                kref.encode_np(mag, 24, design))
        prefix = planes[:8]
        got = kops.decode_bitplanes(prefix, 24, n, design, backend)
        want = kref.decode(prefix, 24, n, design)
        checks.append((f"kernel {design}: encode + 8-plane decode bit-exact",
                       enc_ok and np.array_equal(np.asarray(got),
                                                 np.asarray(want))))
    return checks


def phase_write(root: str, fields: Dict[str, np.ndarray],
                config: RefactorConfig, chunk_elems: int,
                mesh: shd.MeshLike = None) -> Dict[str, object]:
    """Write every field with ``DatasetWriter``; returns byte counts."""
    with DatasetWriter(root, chunk_elems=chunk_elems, config=config,
                       use_tune_cache=False, mesh=mesh) as w:
        entries = {name: w.write(name, x) for name, x in fields.items()}
    return {"raw": sum(int(x.nbytes) for x in fields.values()),
            "stored": sum(int(e.stored_bytes) for e in entries.values()),
            "chunks": {n: len(e.chunks) for n, e in entries.items()},
            "segments": {n: e.segment_file for n, e in entries.items()}}


def fused_program_text(config: RefactorConfig, chunk_elems: int) -> str:
    """Compiled text of the one-dispatch write program the writer ran for a
    full chunk (the donated twin where the backend donates)."""
    levels = dc.num_levels((chunk_elems,))
    mag_bits = config.resolved_mag_bits()
    plan = rff.fused_encode_plan(
        (chunk_elems,), levels, config.design, mag_bits,
        tuple(rf._group_plane_split(mag_bits, config.group_size)),
        config.backend, config.tiles_per_block, config.unroll)
    run = plan.run_donated if rff.donation_supported() else plan.run
    x = jax.ShapeDtypeStruct((chunk_elems,), jnp.float32)
    return run.lower(x).compile().as_text()


def phase_ladder(service: RetrievalService, name: str, x: np.ndarray,
                 tols: Sequence[float] = LADDER) -> List[Check]:
    """One session, the relative tolerance ladder: each step must satisfy
    max|x - x^| <= bound <= tol * range."""
    session = service.open_session()
    rng = service.store.variable(name).range
    checks = []
    for tol in tols:
        xh, bound, fetched = session.retrieve(name, tol, relative=True)
        err = float(np.max(np.abs(xh - x)))
        tol_abs = tol * rng
        say(f"ladder {name} tol {tol:.0e}: max|x-x^| {err:.6e} <= bound "
            f"{bound:.6e} <= tol*range {tol_abs:.6e}; fetched {fetched} B "
            f"(session total {session.bytes_fetched} B)")
        checks.append((f"ladder {name} tol {tol:.0e}: error <= bound <= tol",
                       xh.shape == x.shape and err <= bound <= tol_abs))
    service.close_session(session)
    return checks


def phase_qoi(service: RetrievalService, names: Sequence[str],
              vs: Sequence[np.ndarray], taus: Sequence[float] = QOI_TAUS,
              method: str = "cp") -> List[Check]:
    """Guaranteed V_total retrieval: actual <= estimated <= tau."""
    session = service.open_session()
    truth = sum(v.astype(np.float32) ** 2 for v in vs)
    checks = []
    for tau in taus:
        res = session.retrieve_qoi(list(names), qq.V_TOTAL, tau,
                                   method=method)
        actual = float(np.max(np.abs(sum(v ** 2 for v in res.values)
                                     - truth)))
        say(f"qoi V_total ({method}) tau {tau:.0e}: actual {actual:.6e} <= "
            f"estimated {res.tau_estimated:.6e} <= tau; {res.iterations} "
            f"iterations, bitrate {res.bitrate:.3f}, fetched "
            f"{res.bytes_fetched} B")
        checks.append((f"qoi V_total tau {tau:.0e}: guarantee held",
                       actual <= res.tau_estimated <= tau))
    service.close_session(session)
    return checks


def _retry_stats(backend) -> List[Dict[str, int]]:
    """``RetryStats`` snapshots of every retrying layer of a backend chain."""
    out = []
    while backend is not None:
        if isinstance(backend, RetryingBackend):
            out.append(backend.stats.snapshot())
        backend = getattr(backend, "inner", None)
    return out


def health_checks(stats: Dict[str, object],
                  retry_stats: Sequence[Dict[str, int]] = ()) -> List[Check]:
    """Fail on anything best-effort code could have hidden: degraded plane
    groups, failed backend reads (warm and prefetch swallow theirs),
    errors fanned out by the serving tier, and retries."""
    sessions = stats["sessions"].values()
    backend = stats["backend"] or {}
    serving = stats["serving"] or {}
    degraded = sum(s["degraded_groups"] for s in sessions)
    retries = sum(r["retries"] + r["exhausted"] for r in retry_stats)
    return [
        (f"degraded groups {degraded} == 0", degraded == 0),
        (f"backend fetch errors {backend.get('fetch_errors', 0)} == 0",
         backend.get("fetch_errors", 0) == 0),
        (f"serving errors {serving.get('errors_propagated', 0)} == 0",
         serving.get("errors_propagated", 0) == 0),
        (f"backend retries {retries} == 0", retries == 0),
    ]


# --------------------------------------------------------------- one chip --

def run_single(root: str, side: int = NYX["shape"][0],
               velocity_side: int = VELOCITY_SIDE,
               seed: int = 0, chunk_elems: int = 1 << 20,
               backend: str = "auto", kernel_elems: int = 1 << 20,
               read_backend: Optional[str] = None) -> bool:
    """The main path.  Off the TPU (tests: tiny sizes, interpret-mode
    kernels on the CPU) only the checks that need the compiled Pallas
    backend are skipped; ``read_backend`` overrides the decode backend the
    readers replay from the manifest (default: the write's)."""
    m = meter()
    on_chip = jax.devices()[0].platform == "tpu"
    config = RefactorConfig(backend=backend)
    resolved = kops._resolve(config.backend)
    say(f"kernel backend {config.backend!r} resolves to {resolved!r}")
    checks: List[Check] = []
    if on_chip:
        checks.append(("kernel backend resolves to pallas",
                       resolved == "pallas"))
    if velocity_side != side:
        say(f"SIZE CUT: velocity components at {velocity_side}^3 "
            f"(density stays {side}^3)")

    checks += m.phase("kernels", lambda: phase_kernels(kernel_elems,
                                                       backend, seed))
    fields = m.phase("make data", lambda: make_fields(side, velocity_side,
                                                      seed))
    shutil.rmtree(root, ignore_errors=True)
    try:
        w = m.phase("write", lambda: phase_write(root, fields, config,
                                                 chunk_elems))
        say(f"wrote {w['raw']} B raw as {w['stored']} B stored "
            f"(ratio {w['raw'] / max(w['stored'], 1):.4f}); chunks "
            f"{w['chunks']}")
        text = m.phase("inspect write program",
                       lambda: fused_program_text(config, chunk_elems))
        has_kernel = "tpu_custom_call" in text
        say(f"compiled write program contains tpu_custom_call: "
            f"{has_kernel} ({text.count('tpu_custom_call')} mentions)")
        if on_chip:
            checks.append(("tpu_custom_call in the compiled write program",
                           has_kernel))

        store = DatasetStore.open(root)      # cold: manifest only
        service = RetrievalService(store, backend=read_backend)
        checks += m.phase("ladder", lambda: phase_ladder(
            service, "density", fields["density"]))
        checks += m.phase("qoi", lambda: phase_qoi(
            service, ("vx", "vy", "vz"),
            [fields[n] for n in ("vx", "vy", "vz")]))
        stats = service.stats()
        be = stats["backend"]
        say(f"backend: {be['fetches']} range reads, {be['bytes_fetched']} B "
            f"fetched of {stats['store_bytes']} B stored")
        checks += health_checks(stats, _retry_stats(store.backend))
        store.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return report(checks)


# ------------------------------------------------------------- four chips --

def warm_devices(root: str, x: np.ndarray, config: RefactorConfig,
                 chunk_elems: int, n_devices: int,
                 tols: Sequence[float] = SHARDED_TOLS) -> None:
    """Compile the write and read programs of every device concurrently.

    One thread per device writes ``WARM_CHUNKS`` chunks of ``x`` (a
    different slice on each device) on a mesh of that device alone, and
    reads them back at ``tols``.  The sharded and the 1-device runs then find
    most of their programs compiled for the device they run on."""
    flat = x.reshape(-1)
    n = min(WARM_CHUNKS * chunk_elems, flat.size // n_devices)
    devices = jax.devices()[:n_devices]

    def one(k: int) -> None:
        mesh = Mesh(np.array([devices[k]]), (shd.CHUNK_AXIS,))
        r = os.path.join(root, f"warm{k}")
        phase_write(r, {"density": flat[k * n:(k + 1) * n]}, config,
                    chunk_elems, mesh=mesh)
        store = DatasetStore.open(r)
        session = RetrievalService(store, mesh=mesh).open_session()
        for tol in tols:
            session.retrieve("density", tol, relative=True)
        store.close()

    with ThreadPoolExecutor(n_devices) as pool:
        list(pool.map(one, range(n_devices)))


def run_sharded(root: str, n_devices: int = 4, side: int = NYX["shape"][0],
                seed: int = 0, chunk_elems: int = 1 << 20,
                backend: str = "auto",
                tols: Sequence[float] = SHARDED_TOLS) -> bool:
    """Sharded write/read against its single-device oracle, and nothing
    else: byte-identical segments, equal reads, dispatches on every device."""
    m = meter()
    config = RefactorConfig(backend=backend)
    say(f"kernel backend {config.backend!r} resolves to "
        f"{kops._resolve(config.backend)!r}")
    x = m.phase("make data", lambda: gaussian_field((side,) * 3,
                                                    NYX["slope"], seed))
    roots = {"sharded": os.path.join(root, "sharded"),
             "single": os.path.join(root, "single")}
    shutil.rmtree(root, ignore_errors=True)
    checks: List[Check] = []
    try:
        m.phase("warm-up, one thread per device", lambda: warm_devices(
            os.path.join(root, "warm"), x, config, chunk_elems, n_devices))
        shd.STATS.reset()
        ws = m.phase(f"write on {n_devices} devices", lambda: phase_write(
            roots["sharded"], {"density": x}, config, chunk_elems,
            mesh=n_devices))
        by_dev = shd.STATS.snapshot()["dispatches_by_device"]
        say(f"sharded.STATS.dispatches_by_device {by_dev}")
        n_chunks = ws["chunks"]["density"]
        checks.append((f"dispatches spread over all {n_devices} devices",
                       sorted(by_dev) == list(range(n_devices))
                       and sum(by_dev.values()) == n_chunks))
        w1 = m.phase("write on 1 device", lambda: phase_write(
            roots["single"], {"density": x}, config, chunk_elems, mesh=1))
        same = filecmp.cmp(
            os.path.join(roots["sharded"], ws["segments"]["density"]),
            os.path.join(roots["single"], w1["segments"]["density"]),
            shallow=False)
        say(f"segment bytes: {ws['stored']} B on {n_devices} devices, "
            f"{w1['stored']} B on 1; identical: {same}")
        checks.append(("sharded segments byte-identical to 1 device", same))

        stores = {k: DatasetStore.open(r) for k, r in roots.items()}
        services = {"sharded": RetrievalService(stores["sharded"],
                                                mesh=n_devices),
                    "single": RetrievalService(stores["single"])}
        sessions = {k: s.open_session() for k, s in services.items()}
        tol_range = stores["single"].variable("density").range

        def reads() -> List[Check]:
            out = []
            for tol in tols:
                got = {k: s.retrieve("density", tol, relative=True)
                       for k, s in sessions.items()}
                (xa, ba, _), (xb, bb, _) = got["sharded"], got["single"]
                err = float(np.max(np.abs(xa - x)))
                say(f"sharded read tol {tol:.0e}: equal to 1-device read: "
                    f"{np.array_equal(xa, xb)}; bounds {ba:.6e} / {bb:.6e};"
                    f" max|x-x^| {err:.6e}")
                out.append((f"sharded read tol {tol:.0e} equals 1-device "
                            "read", np.array_equal(xa, xb) and ba == bb))
                out.append((f"sharded read tol {tol:.0e}: error <= bound <= "
                            "tol", err <= ba <= tol * tol_range))
            return out

        checks += m.phase("reads", reads)
        for k, svc in services.items():
            checks += [(f"{k}: {n}", ok) for n, ok in health_checks(
                svc.stats(), _retry_stats(stores[k].backend))]
            stores[k].close()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return report(checks)


# ------------------------------------------------------------------- main --

def main(argv: Sequence[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--root", default=os.path.join(REPO, "out",
                                                   "chip_smoke"),
                    help="scratch store directory (removed afterwards)")
    args = ap.parse_args(argv)

    cache_dir = compile_cache.enable()
    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{info['platform']!r}); refusing to run on another backend",
              file=sys.stderr)
        return 2
    say(f"device platform {info['platform']}, kind {info['kind']!r}, "
        f"count {info['count']}")
    say(f"compile cache {cache_dir}")
    if args.chips > info["count"]:
        print(f"chip_smoke: --chips {args.chips} but only {info['count']} "
              f"devices", file=sys.stderr)
        return 2
    meter()
    if args.chips == 1:
        ok = run_single(args.root, seed=args.seed)
    else:
        ok = run_sharded(args.root, n_devices=args.chips, seed=args.seed)
    print(result_line(ok, info), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
