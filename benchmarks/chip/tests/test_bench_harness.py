"""The benchmark harness on the CPU: resolution, the contract's shape of
BENCHMARK.json, each cell at a tiny size, and the refusals."""
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

from bench_tiny import CHECKOUT, CHIP, SEED, WORKLOADS, full_bench, tiny_cell
import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_workload_resolves_to_its_files():
    bench = full_bench()
    for w in bench["workloads"]:
        cell = harness.resolve(bench, w["name"])
        assert os.path.isfile(cell.op_file), cell.op_file
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(cell.readers[m["name"]])
            assert m["moves"] in names


def test_benchmark_json_keeps_to_the_contract():
    bench = harness.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(CHECKOUT, p))
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith(
            bench["paths"][0] + "/")
        spec = json.load(open(os.path.join(CHECKOUT, c["file"])))
        assert set(c["reduced"]) == set(spec["reduced"])
    configs = {c["name"] for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert w["config"] in configs and len(w["why"]) <= 200
        used.add(w["config"])
    assert used == configs
    cells = {w["name"] for w in bench["workloads"]}
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", ())) <= cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert "bound" not in m


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_and_holds_at_a_tiny_size(workload, tmp_path):
    cell = tiny_cell(workload)
    res = harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(),
                           out_dir=str(tmp_path))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert not os.listdir(tmp_path)        # stores removed at exit


def test_traced_run_reports_per_layer_metrics(tmp_path):
    cell = tiny_cell("isabel.ladder")
    res = harness.run_cell(cell, SEED, 0.5, True, time.perf_counter(),
                           out_dir=str(tmp_path))
    assert res["correct"]
    assert set(res["metrics"]) <= {m["name"] for m in cell.per_layer}
    # the counters' metrics need no device trace; the CPU has no device
    # plane, so the trace's metrics are left out rather than read as 0
    assert {"fetched_MB.ladder", "window_compiles.ladder"} <= set(
        res["metrics"])
    assert "device_idle.ladder" not in res["metrics"]
    assert res["device"]["window_s"] > 0 and "breakdown" in res
    assert not os.listdir(tmp_path)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "nyx.write",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run(CHECKOUT)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_run_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout == ""


def test_a_new_config_mix_op_and_metric_are_new_files_only(tmp_path):
    """A configuration, a traffic mix, an operation and a per-layer metric
    added as new files and entries, with no edit to any file there."""
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    base = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, base, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    cfg = json.load(open(base / "configs" / "isabel.json"))
    cfg.update(name="tiny", shape=[4, 10, 40], chunk_elems=1024,
               fields={"a": -2.5, "b": -1.5}, reduced={})
    (base / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (base / "traffic" / "ladder2.json").write_text(json.dumps(
        {"op": "walk", "tolerances": [0.01, 0.0001]}))
    (base / "ops" / "walk.py").write_text(
        "import os\nfrom generator import load_file\n"
        "Op = load_file(os.path.join(os.path.dirname(__file__), "
        "'ladder.py'), 'walk_').Op\n")
    (base / "layer_metrics" / "walks.ladder2.py").write_text(
        "def read(r):\n    return float(r.counters['units'])\n")
    bench = full_bench(str(tmp_path))
    bench["configs"].append({"name": "tiny", "source": "https://example.org",
                             "file": "benchmarks/chip/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.ladder2", "config": "tiny",
                               "traffic": "ladder2", "chips": 1,
                               "why": "test"})
    next(m for m in bench["end_to_end"] if m["name"] == "ladder_s")[
        "workloads"].append("tiny.ladder2")
    bench["per_layer"].append({"name": "walks.ladder2", "unit": "walks",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "store backend",
                               "moves": "ladder_s",
                               "workloads": ["tiny.ladder2"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve(bench, "tiny.ladder2", str(tmp_path))
    assert cell.op_file == str(base / "ops" / "walk.py")
    res = harness.run_cell(cell, SEED, 0.3, True, time.perf_counter(),
                           out_dir=str(tmp_path / "out"))
    assert res["correct"]
    assert res["metrics"]["walks.ladder2"]["value"] >= 1
    assert all(p.read_bytes() == b for p, b in before.items())
