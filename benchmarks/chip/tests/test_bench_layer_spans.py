"""The write path's idle time split by program spans: the three readers on
a summary built to known numbers, the labels a reduced trace gives nested
codec spans, and a guard that every span the program opens is one the
trace reduction can label a gap with."""
import os

import numpy as np
import pytest

from bench_tiny import CHIP
import harness
import trace_reduce as tr
from test_bench_trace import _plane

READERS = ("codec_host_idle.write", "pipeline_idle.write",
           "store_idle.write")
# idle seconds by innermost host span, in a 10-s window
GAPS = {"codec.stats": 0.25, "codec.select": 1.0, "codec.pack": 0.125,
        "codec.payload": 0.0625, "codec.segments": 0.5,
        "encode.scalars": 0.03125, "encode.finish_many": 0.015625,
        "encode.assemble": 0.0078125, "sharded.finish_many": 0.0,
        "write.serialize": 0.25, "write.refactor": 0.125,
        "store.write": 0.0625, "store.entry": 0.5, "store.finalize": 0.25,
        "store.segment_close": 0.125, "bench.unit": 0.375, "none": 0.0625}
EXPECT = {"codec_host_idle.write": 19.375, "pipeline_idle.write": 4.296875,
          "store_idle.write": 9.375}
RESIDUE = 4.375   # bench.unit and none


def _readers():
    return {n: harness.load_reader(
        os.path.join(CHIP, "layer_metrics", n + ".py")) for n in READERS}


def _reading(trace):
    return harness.Reading(counters={}, window_compiles=0, trace=trace)


def _summary(gaps, window_s=10.0):
    return tr.Summary(window_s=window_s,
                      busy_s=window_s - sum(gaps.values()), n_devices=1,
                      op_s={"jit__run": 1.0}, gaps_s=dict(gaps))


def test_readers_split_the_labels_as_specified():
    got = {n: f(_reading(_summary(GAPS))) for n, f in _readers().items()}
    assert got == pytest.approx(EXPECT)


def test_the_three_and_the_residue_make_the_idle_share():
    s = _summary(GAPS)
    got = sum(f(_reading(s)) for f in _readers().values())
    assert got + RESIDUE == pytest.approx(100.0 * s.idle_share)


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_a_device_trace(name):
    f = _readers()[name]
    assert f(_reading(None)) is None
    no_device = tr.Summary(window_s=1.0, busy_s=0.0, n_devices=0, op_s={},
                           gaps_s={"codec.select": 1.0})
    assert f(_reading(no_device)) is None


def test_a_family_no_gap_carries_reads_nothing():
    """A program that opens none of a reader's spans (an older build with
    no ``codec.*`` or ``store.*`` span) leaves that metric out, and no
    reader raises."""
    s = _summary({"encode.finish_many": 3.0, "bench.unit": 0.5})
    got = {n: f(_reading(s)) for n, f in _readers().items()}
    assert got == {"codec_host_idle.write": None,
                   "pipeline_idle.write": pytest.approx(30.0),
                   "store_idle.write": None}


def test_a_gap_under_nested_codec_spans_takes_the_innermost():
    """A 10 ms window: the drain (``encode.finish_many``, 1-9 ms) holds the
    selection (2-5 ms) and the segments (6-8 ms); the device runs 0-2 ms
    and 5-6 ms.  Each gap takes the innermost span open at its midpoint:
    3.5 ms lies in the selection; 8 ms in the drain alone, the segments'
    span having just closed."""
    from jax.profiler import ProfileData
    host = _plane(1, "/host:CPU", [("python", [
        ("bench.window", 0, 10), ("bench.unit", 0, 10.5),
        ("store.write", 0, 10.5), ("encode.finish_many", 1, 8),
        ("codec.select", 2, 3), ("codec.segments", 6, 2)])])
    dev = _plane(2, "/device:TPU:0", [("XLA Modules", [
        ("jit__run(1)", 0, 2), ("jit__huffman_pack_batch(2)", 5, 1)])])
    s = tr.reduce(ProfileData.from_text_proto(host + "\n" + dev))
    assert s.gaps_s == pytest.approx({"codec.select": 3e-3,
                                      "encode.finish_many": 4e-3})
    got = {n: f(_reading(s)) for n, f in _readers().items()}
    assert got == pytest.approx({"codec_host_idle.write": 30.0,
                                 "pipeline_idle.write": 40.0,
                                 "store_idle.write": None})


def test_every_span_of_a_write_and_a_read_is_a_gap_label(tmp_path):
    """A span whose name ``trace_reduce.SPAN`` does not match would drop
    out of the gap labels without a sound: a renamed span fails here."""
    from repro.core import pipeline as pl
    from repro.data.fields import gaussian_field
    from repro.obs import trace as obs_trace
    from repro.store import DatasetStore, DatasetWriter, RetrievalService
    x = gaussian_field((4 * 4096,), slope=-2.0, seed=8)
    root = str(tmp_path / "s")
    with obs_trace.tracing() as t:
        with DatasetWriter(root, chunk_elems=4096) as w:
            w.write("v", x)
        store = DatasetStore.open(root)
        try:
            RetrievalService(store).open_session().retrieve(
                "v", 1e-3, relative=True)
        finally:
            store.close()
        blobs = pl.ChunkedRefactorPipeline(chunk_elems=4096).refactor(x)
        y = pl.ChunkedReconstructPipeline().reconstruct(blobs, tol=1e-3)
    assert np.abs(y - x).max() <= 1e-3
    names = {s.name for s in t.spans()}
    assert {"store.write", "store.entry", "codec.select", "encode.scalars",
            "serve.retrieve", "read.reconstruct"} <= names
    assert sorted(n for n in names if not tr.SPAN.match(n)) == []
