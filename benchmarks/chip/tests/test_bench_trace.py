"""Trace reduction: interval arithmetic, a trace built to known numbers,
and a small trace recorded on one TPU v5e."""
import json
import os

import pytest

import bench_tiny  # noqa: F401  (import paths)
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000_000   # picoseconds per millisecond


def test_union_and_gaps():
    cover = tr.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert cover == [(0, 3), (5, 9)]
    assert tr.gaps(cover, (0, 12)) == [(3, 5), (9, 12)]
    assert tr.gaps(cover, (1, 8)) == [(3, 5)]
    assert tr.gaps([], (2, 4)) == [(2, 4)]


def test_label_points_takes_the_innermost_open_span():
    spans = [(0, 100, "store.write"), (10, 20, "write.serialize"),
             (50, 60, "write.dispatch")]
    assert tr.label_points(spans, [15, 55, 30, 200]) == [
        "write.serialize", "write.dispatch", "store.write", "none"]


def _event(meta, start_ms, dur_ms):
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ms * MS)} "
            f"duration_ps: {int(dur_ms * MS)} }}")


def _plane(pid, name, lines):
    """``lines``: [(line name, [(event name, start ms, duration ms)])]."""
    names = sorted({e[0] for _, evs in lines for e in evs})
    ids = {n: i for i, n in enumerate(names, 1)}
    body = " ".join(
        f'lines {{ id: {k} name: "{ln}" timestamp_ns: 0 '
        + " ".join(_event(ids[n], a, d) for n, a, d in evs) + " }"
        for k, (ln, evs) in enumerate(lines, 1))
    meta = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                    f"name: {json.dumps(n)} }} }}" for n, i in ids.items())
    return f'planes {{ id: {pid} name: "{name}" {body} {meta} }}'


def synthetic():
    """A 10 ms window: the host fetches (1-4 ms) and serializes (6-9 ms);
    the device runs the write program (0-2 ms, half before the window, and
    5-5.5 ms) and a decode program (4-5 ms); op events (which the
    reduction does not read) overlap the gaps."""
    from jax.profiler import ProfileData
    host = _plane(1, "/host:CPU", [("python", [
        ("bench.window", 1, 10), ("serve.fetch", 1, 3),
        ("write.serialize", 6, 3), ("ParseArguments", 1.2, 1),
        ("bench.unit", 1, 9)])])
    dev = _plane(2, "/device:TPU:0", [
        ("XLA Modules", [("jit__run(5243745995199455410)", 0, 2),
                         ("jit__decode_shifted_batch(77)", 4, 1),
                         ("jit__run(5243745995199455410)", 5, 0.5)]),
        ("XLA Ops", [("%fusion.1 = f32[8]", 0, 2),
                     ("%while.10 = (s32[])", 2.5, 1)])])
    return ProfileData.from_text_proto(host + "\n" + dev)


def test_reduce_a_trace_built_to_known_numbers():
    s = tr.reduce(synthetic())
    assert s.window_s == pytest.approx(10e-3)
    assert s.busy_s == pytest.approx(2.5e-3)      # 1 + 1 + 0.5 ms
    assert s.idle_share == pytest.approx(0.75)
    assert s.n_devices == 1
    assert s.op_s == pytest.approx({"jit__run": 1.5e-3,
                                    "jit__decode_shifted_batch": 1e-3})
    # idle: 2-4 ms (midpoint in serve.fetch), 5.5-11 ms (midpoint 8.25 in
    # write.serialize, 6-9)
    assert s.gaps_s == pytest.approx({"serve.fetch": 2e-3,
                                      "write.serialize": 5.5e-3})
    b = s.breakdown()
    assert [k for k, _ in b["device_ops"]] == [
        "jit__run", "jit__decode_shifted_batch"]
    assert len(b["idle_gaps"]) == 2


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(_plane(
        1, "/host:CPU", [("python", [("serve.fetch", 0, 1)])]))
    with pytest.raises(ValueError):
        tr.reduce(pd)
