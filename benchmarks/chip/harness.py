"""Resolve a cell of ``BENCHMARK.json`` from its files, and run it once.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``:

    configs/<config>.json          the configuration as it is run
    traffic/<traffic>.json         the mix's parameters (``generator.py``)
    ops/<op>.py                    the operation a mix names in ``"op"``
    layer_metrics/<metric>.py      ``read(reading) -> float | None``

so a cell, a configuration or a metric is added with new files and new
entries, and no edit.  ``run_cell`` makes no look for a chip: ``run.py``
does that before it calls in.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

import generator
from generator import Check, Phase, say
import trace_reduce
from compile_meter import CompileMeter

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
BENCHMARK_JSON = os.path.join(CHECKOUT, "BENCHMARK.json")
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")
OUT_DIR = os.path.join(CHECKOUT, "out", "bench")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    mix: Dict[str, Any]
    op_file: str
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    readers: Dict[str, Callable[["Reading"], Optional[float]]]


def load_benchmark(path: str = BENCHMARK_JSON) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_reader(path: str) -> Callable[["Reading"], Optional[float]]:
    """The ``read`` function of one per-layer metric's file."""
    return generator.load_file(path, "layer_metric_").read


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: Dict[str, Any], workload: str,
            checkout: str = CHECKOUT) -> Cell:
    """The cell named ``workload``, with its configuration, mix, operation
    and readers."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(checkout, configs[w["config"]]["file"]))
    base = os.path.join(checkout, bench["paths"][0])
    mix = _load_json(os.path.join(base, "traffic", w["traffic"] + ".json"))
    op_file = os.path.join(base, "ops", f"{mix.get('op')}.py")
    if not os.path.isfile(op_file):
        raise ValueError(f"traffic {w['traffic']!r} names the op "
                         f"{mix.get('op')!r}, which has no file {op_file}")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload) and m["moves"] in reported]
    readers = {m["name"]: load_reader(os.path.join(
        base, "layer_metrics", m["name"] + ".py")) for m in per_layer}
    return Cell(workload, int(w["chips"]), config, mix, op_file, e2e,
                per_layer, readers)


def enable_compile_cache(path: str = CACHE_DIR) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached however fast it compiled."""
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size limit: JAX's eviction keeps a per-entry access-time file
    # that threads compiling at once can leave missing, after which every
    # later write to the cache fails
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class MemoryWatch:
    """The device memory that the window itself holds, on the fullest chip.

    Where the window raised a chip's ``peak_bytes_in_use``, that new peak
    is exact.  Where it did not, the window's own high mark lies under the
    set-up's (which holds every field of the run at once), and the most
    ``bytes_in_use`` seen in the window, read every ``EVERY_S``, stands
    for it."""
    EVERY_S = 0.01

    def __init__(self, devices):
        self.devices = list(devices)
        self.peak = 0

    def _stats(self, key: str) -> List[int]:
        return [int((d.memory_stats() or {}).get(key, 0))
                for d in self.devices]

    def _sample(self) -> None:
        while True:
            self.seen = [max(a, b) for a, b in
                         zip(self.seen, self._stats("bytes_in_use"))]
            if self._stop.wait(self.EVERY_S):
                return

    def __enter__(self) -> "MemoryWatch":
        self.before = self._stats("peak_bytes_in_use")
        self.seen = [0] * len(self.devices)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._sample, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> bool:
        self._stop.set()
        self._thread.join()
        after = self._stats("peak_bytes_in_use")
        self.peak = max((a if a > b else s) for a, b, s in
                        zip(after, self.before, self.seen))
        return False


def device_info(devs: List[Any], memory_peak: int) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": memory_peak}


def verdict(checks: List[Check], failed: int = 0) -> bool:
    """``correct``: no unit failed, and every compared number is within its
    limit."""
    return failed == 0 and bool(checks) and all(c.ok for c in checks)


@dataclasses.dataclass
class Reading:
    """What a per-layer reader may read: the program's counters over the
    window, the compile count in it, and the reduced trace."""
    counters: Dict[str, float]
    window_compiles: int
    trace: Optional[trace_reduce.Summary]


def _window(op: generator.BaseOp, seconds: float,
            recorder: Optional[trace_reduce.Recorder] = None
            ) -> Tuple[int, int]:
    """Whole units until ``seconds`` have passed; (attempted, failed).
    With a recorder, the first unit is traced: a whole window of the read
    traffic overflows the profiler's buffer (PERF.md, section 3)."""
    failed = attempted = 0
    t0 = time.perf_counter()
    try:
        while True:
            if recorder and attempted == 0:
                recorder.start()
            attempted += 1
            try:
                with trace_reduce.annotate(trace_reduce.UNIT_SPAN):
                    op.unit(attempted - 1)
            except Exception as e:  # noqa: BLE001 - a failed unit counts
                failed += 1
                say(f"unit {attempted - 1} failed: {e!r}")
            if recorder:
                recorder.stop()
            if time.perf_counter() - t0 >= seconds:
                return attempted, failed
    finally:
        if recorder:
            recorder.stop()


def _make(cell: Cell, seed: int, seconds: float, out_dir: str
          ) -> generator.BaseOp:
    """The cell's operation, set up and warmed up: the window's own units,
    run for as long as the window, then forgotten."""
    op = generator.make_op(cell.op_file, cell.config, cell.mix,
                           os.path.join(out_dir, cell.name))
    op.setup(seed)
    with Phase("warm-up: the window's units, for as long as the window"):
        _window(op, seconds)
    op.begin_window()
    return op


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, out_dir: str = OUT_DIR) -> Dict[str, Any]:
    """Set up, run the window, check; returns the result line's object."""
    meter = CompileMeter()
    root = os.path.join(out_dir, cell.name)
    recorder = trace_reduce.Recorder(root + ".trace") if trace else None
    devs = jax.devices()[:cell.chips]
    try:
        op = _make(cell, seed, seconds, out_dir)
        c0, cs0, hits = meter.reading()
        say(f"set-up compiled {c0} programs ({cs0:.3f} s) and loaded {hits} "
            f"from the persistent cache")
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        with MemoryWatch(devs) as mem:
            attempted, failed = _window(op, seconds, recorder)
        window_s = time.perf_counter() - t0
        c1, cs1, _ = meter.reading()
        say(f"window: {window_s:.3f} s, {attempted} units, {failed} failed; "
            f"compiles inside the window: {c1 - c0} ({cs1 - cs0:.3f} s); "
            f"device memory held in the window: {mem.peak} B")
        device = device_info(devs, mem.peak)
        summary = recorder.reduce() if recorder else None
        metrics: Dict[str, Dict[str, Any]] = {}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            reading = Reading(op.counters(), c1 - c0, summary)
            for m in cell.per_layer:
                v = cell.readers[m["name"]](reading)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            values = dict(op.e2e(window_s) if attempted > failed else {},
                          setup_s=setup_s)
            for m in cell.end_to_end:
                if m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        t_check = time.perf_counter()
        checks = op.check(op.answers()) if attempted > failed else []
        say(f"check: {time.perf_counter() - t_check:.3f} s")
        out = {"correct": verdict(checks, failed), "attempted": attempted,
               "failed": failed, "metrics": metrics, "device": device}
        if summary is not None:
            out["breakdown"] = summary.breakdown()
        out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                         for c in checks}
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)
        if recorder:
            shutil.rmtree(recorder.directory, ignore_errors=True)


def run_readings(cell: Cell, seed: int, seconds: float,
                 out_dir: str = OUT_DIR) -> Dict[str, List[Check]]:
    """One run's compared numbers for the program and for the control (the
    reference in bfloat16 in the program's place, on the same inputs)."""
    try:
        op = _make(cell, seed, seconds, out_dir)
        attempted, failed = _window(op, seconds)
        if failed:
            raise RuntimeError(f"{failed} of {attempted} units failed")
        return {side: op.check(op.answers(control=side == "control"))
                for side in ("program", "control")}
    finally:
        shutil.rmtree(os.path.join(out_dir, cell.name), ignore_errors=True)
