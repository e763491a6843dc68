"""Unified tracing & metrics layer (repro.obs) + the CI regression gate.

Pins the observability contracts:
  * span/event mechanics: nesting, thread joining, disabled fast path;
  * sync-budget attribution: a traced >=4-chunk pipelined write produces a
    Chrome trace whose host_sync event count exactly matches the codec
    engine's counters (3 per chunk, labeled), and the read path adds
    1/chunk;
  * context-local stats (``lossless_batch.stats_scope``): concurrent scopes
    never cross-contaminate, worker threads join their caller's scope;
  * per-device Chrome-trace tracks for the sharded write path;
  * store metrics: compression accounting + expansion warning, backend
    cache hit/miss across cached re-reads and sessions,
    ``RetrievalService.stats()``;
  * ``benchmarks/check_regressions.py``: passes on an artifact that meets
    its budgets, fails non-zero on a doctored one (and on missing artifacts
    or unresolvable budget paths).
"""
import json
import logging
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import lossless_batch as lb
from repro.core import pipeline as pl
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.data.fields import gaussian_field
from repro.store import (CachingBackend, DatasetStore, DatasetWriter,
                         InMemoryBackend, LocalFileBackend, RetrievalService)


# ------------------------------------------------------------ span mechanics

def test_span_disabled_is_shared_null():
    """Off the tracing path, span() must return the shared no-op manager
    (one ContextVar read, no allocation — the <2% overhead contract)."""
    assert obs_trace.current_tracer() is None
    s1 = obs_trace.span("write.copy_in", chunk=1)
    s2 = obs_trace.span("anything")
    assert s1 is obs_trace.NULL_SPAN and s2 is obs_trace.NULL_SPAN
    with s1:  # usable as a context manager
        obs_trace.event("host_sync", label="x")  # and events are no-ops


def test_nested_spans_events_and_attribution():
    with obs_trace.tracing() as tr:
        with obs_trace.span("outer", name="v"):
            with obs_trace.span("inner", chunk=3):
                obs_trace.event(obs_trace.EV_HOST_SYNC, label="codec.stats")
            obs_trace.event(obs_trace.EV_HOST_SYNC)  # unlabeled -> span name
        obs_trace.event(obs_trace.EV_DISPATCH)  # orphan
    spans = {s.name: s for s in tr.spans()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    assert spans["outer"].attrs == {"name": "v"}  # attr may be called "name"
    assert tr.event_counts() == {"host_sync": 2, "dispatch": 1}
    assert tr.attribute_events(obs_trace.EV_HOST_SYNC) == {
        "codec.stats": 1, "outer": 1}
    assert len(tr.orphan_events()) == 1
    assert tr.summary()["host_syncs_by_span"] == {"codec.stats": 1,
                                                  "outer": 1}


def test_wrap_for_thread_joins_callers_trace():
    with obs_trace.tracing() as tr:
        def work():
            with obs_trace.span("worker.span"):
                obs_trace.event(obs_trace.EV_SERIALIZE, bytes=10)
        t = threading.Thread(target=obs_trace.wrap_for_thread(work))
        t.start(); t.join()
        # a bare thread (no wrap) must NOT land in the trace
        t2 = threading.Thread(target=lambda: obs_trace.event("host_sync"))
        t2.start(); t2.join()
    names = [s.name for s in tr.spans()]
    assert names == ["worker.span"]
    assert tr.event_counts() == {"serialize": 1}


def test_no_tracing_scope_disables():
    with obs_trace.tracing() as tr:
        with obs_trace.no_tracing():
            assert obs_trace.span("x") is obs_trace.NULL_SPAN
            obs_trace.event("host_sync")
        with obs_trace.span("y"):
            pass
    assert [s.name for s in tr.spans()] == ["y"]
    assert tr.event_counts() == {}


# ------------------------------------------------------------------ metrics

def test_metrics_counters_gauges_histograms():
    # earlier suites encode for real and land series in the default
    # registry, so isolation is asserted as "unchanged", not "absent"
    default_before = obs_metrics.snapshot()["counters"].get(
        "codec.bytes_in{codec=huffman}")
    with obs_metrics.scope() as m:
        m.inc("codec.bytes_in", 100, codec="huffman")
        m.inc("codec.bytes_in", 50, codec="huffman")
        m.inc("codec.bytes_in", 7, codec="rle")
        m.gauge("store.compression_ratio", 1.5, var="v")
        for v in [1.0, 2.0, 3.0, 4.0]:
            m.observe("serve.retrieve_s", v)
        snap = m.snapshot()
    assert snap["counters"]["codec.bytes_in{codec=huffman}"] == 150
    assert snap["counters"]["codec.bytes_in{codec=rle}"] == 7
    assert snap["gauges"]["store.compression_ratio{var=v}"] == 1.5
    h = snap["histograms"]["serve.retrieve_s"]
    assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 4.0
    assert h["p50"] == 2.0 and h["p99"] == 4.0
    # scope() isolated the numbers from the default registry
    assert obs_metrics.snapshot()["counters"].get(
        "codec.bytes_in{codec=huffman}") == default_before


def test_metrics_scope_isolation_across_threads():
    """Two concurrent scopes in different threads never share series."""
    out = {}

    def worker(tag, n):
        with obs_metrics.scope() as m:
            for _ in range(n):
                m.inc("c")
            out[tag] = m.counter_value("c")

    ts = [threading.Thread(target=worker, args=("a", 100)),
          threading.Thread(target=worker, args=("b", 7))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert out == {"a": 100, "b": 7}


# --------------------------------------------- context-local lossless stats

def test_stats_scope_concurrent_isolation():
    """Satellite regression: lossless_batch.STATS is context-local — two
    scopes mutating concurrently (as dispatch-ahead worker threads do) never
    cross-contaminate, and the module global keeps its .add/.snapshot API."""
    import jax.numpy as jnp
    barrier = threading.Barrier(2)
    results = {}

    def worker(tag, n):
        with lb.stats_scope() as st:
            barrier.wait()
            for _ in range(n):
                lb.host_sync(jnp.zeros(4), label=f"test.{tag}")
            results[tag] = st.host_syncs

    ts = [threading.Thread(target=worker, args=("a", 5)),
          threading.Thread(target=worker, args=("b", 2))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert results == {"a": 5, "b": 2}


def test_stats_scope_worker_thread_joins_caller():
    """A wrap_for_thread worker lands its counters in the caller's scope
    (the pipeline's prefetch/serialize threads rely on this)."""
    import jax.numpy as jnp
    with lb.stats_scope() as st:
        def work():
            lb.host_sync(jnp.zeros(4))

        t = threading.Thread(target=obs_trace.wrap_for_thread(work))
        t.start(); t.join()
        assert st.host_syncs == 1


# --------------------------------------------------- traced write sync budget

def _traced_write(n_chunks=4, chunk=4096, mesh=None, pipelined=True,
                  dispatch_ahead=2):
    x = gaussian_field((n_chunks * chunk,), slope=-2.0, seed=5)
    with obs_metrics.scope() as m, obs_trace.tracing() as tr, \
            lb.stats_scope() as st:
        pipe = pl.ChunkedRefactorPipeline(chunk_elems=chunk, levels=2,
                                          pipelined=pipelined, mesh=mesh,
                                          dispatch_ahead=dispatch_ahead)
        blobs = pipe.refactor(x, name="v")
    return x, blobs, tr, st, m


def test_traced_write_host_sync_budget_matches_chrome_trace():
    """Acceptance: a traced 4-chunk pipelined write's Chrome trace contains
    EXACTLY the host_sync events the codec counters promise — 3 per DRAINED
    WINDOW of dispatch_ahead(=2) chunks (one encode.scalars gather + codec
    stats + codec payload), each attributed to its originating label; the
    amortized per-chunk budget is 1.5, half the old 3/chunk round budget."""
    n, drains = 4, 2  # 4 chunks drain in 2 full windows of dispatch_ahead=2
    _, blobs, tr, st, m = _traced_write(n_chunks=n)
    assert len(blobs) == n
    assert st.host_syncs == 3 * drains
    trace_json = obs.chrome_trace(tr)
    assert obs_export.event_count(trace_json, "host_sync") == st.host_syncs
    assert tr.attribute_events(obs_trace.EV_HOST_SYNC) == {
        "encode.scalars": drains, "codec.stats": drains,
        "codec.payload": drains}
    # every write stage span is present, once per chunk (batched finishes
    # show up as one sharded.finish_many span per drain)
    per = tr.summary()["spans"]
    for stage in ["write.copy_in", "write.dispatch", "write.serialize"]:
        assert per[stage]["count"] == n, stage
    assert per["write.refactor"]["count"] == 1
    assert per["sharded.finish_many"]["count"] == drains
    snap = m.snapshot()
    assert snap["gauges"]["write.syncs_per_chunk"] == 3 * drains / n
    assert snap["gauges"]["write.dispatches_per_chunk"] == 1.0
    # async-drain attribution gauge: mean in-flight depth per device at
    # drain time equals the full window
    assert snap["gauges"]["write.inflight_depth.d0"] == 2.0


CODEC_SPANS = ("codec.stats", "codec.select", "codec.pack", "codec.payload",
               "codec.segments")


def test_traced_write_codec_stages_nest_under_the_drain():
    """Each drain of a pipelined write opens each codec host stage once, in
    order, inside one ``codec.encode`` under ``encode.finish_many``; the
    scalar gather has its own ``encode.scalars`` span; every host sync sits
    in the span of its label's name, so a trace gap under a sync names the
    stage."""
    n, drains = 4, 2
    _, _, tr, st, _ = _traced_write(n_chunks=n)
    spans = tr.spans()
    by_id = {s.span_id: s for s in spans}

    def parents(name):
        mine = [s for s in spans if s.name == name]
        assert len(mine) == drains, name
        return {by_id[s.parent_id].name for s in mine}

    for name in CODEC_SPANS:
        assert parents(name) == {"codec.encode"}, name
    assert parents("codec.encode") == {"encode.finish_many"}
    assert parents("encode.scalars") == {"sharded.finish_many"}
    # per drain, the stages run in order and do not overlap
    for enc in (s for s in spans if s.name == "codec.encode"):
        kids = sorted((s for s in spans if s.parent_id == enc.span_id),
                      key=lambda s: s.t0)
        assert [s.name for s in kids] == list(CODEC_SPANS)
        assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
    syncs = tr.events(obs_trace.EV_HOST_SYNC)
    assert len(syncs) == st.host_syncs == 3 * drains
    assert all(span_.name == ev.attrs["label"] for span_, ev in syncs)


def test_store_spans_open_once_per_write_and_finalize(tmp_path):
    """``store.write`` holds the whole write (the pipeline, the segment
    file's close and the manifest entry); ``store.finalize`` the manifest
    commit."""
    x = gaussian_field((2 * 4096,), slope=-2.0, seed=6)
    with obs_trace.tracing() as tr:
        with DatasetWriter(str(tmp_path / "s"), chunk_elems=4096) as w:
            w.write("a", x)
            w.write("b", x[::-1].copy())
    spans = tr.spans()
    by_id = {s.span_id: s for s in spans}
    per = tr.summary()["spans"]
    assert per["store.write"]["count"] == 2
    assert per["store.entry"]["count"] == 2
    assert per["store.segment_close"]["count"] == 2
    assert per["store.finalize"]["count"] == 1
    for s in spans:
        if s.name in ("store.entry", "store.segment_close", "write.refactor"):
            assert by_id[s.parent_id].name == "store.write", s.name
    fin = next(s for s in spans if s.name == "store.finalize")
    assert fin.parent_id is None


def test_traced_read_adds_one_sync_per_chunk():
    """The read path's budget: at most 1 host sync per chunk (codec.decode)
    — the '28 syncs for 7 chunks' finding is 3/chunk write + 1/chunk read.
    The decode sync fires only when non-dc (huffman/rle) groups decode, so
    the chunks must be big enough (> HybridConfig.size_threshold bytes per
    plane group) for Algorithm-2 to pick huffman."""
    n, chunk = 4, 32768
    x, blobs, *_ = _traced_write(n_chunks=n, chunk=chunk)
    with obs_trace.tracing() as tr, lb.stats_scope() as st:
        y = pl.ChunkedReconstructPipeline().reconstruct(blobs, tol=1e-4)
    assert np.abs(y - x.reshape(-1)).max() <= 1e-4
    assert st.host_syncs == n
    assert tr.attribute_events(obs_trace.EV_HOST_SYNC) == {"codec.decode": n}
    per = tr.summary()["spans"]
    assert per["read.decompress"]["count"] == n
    assert per["read.recompose"]["count"] == n


def test_serial_mode_budget_unchanged():
    n = 3
    _, blobs, tr, st, _ = _traced_write(n_chunks=n, pipelined=False)
    assert len(blobs) == n and st.host_syncs == 3 * n
    assert sum(tr.attribute_events(obs_trace.EV_HOST_SYNC).values()) == 3 * n


# ------------------------------------------------------ per-device tracks

def test_mesh_of_one_has_single_device_track():
    from repro.core import sharded as shd
    _, _, tr, _, _ = _traced_write(n_chunks=4, mesh=shd.make_chunk_mesh(1))
    trace_json = obs.chrome_trace(tr)
    assert obs_export.device_tracks(trace_json) == ["device:0"]


def test_two_device_sharded_write_two_device_tracks(subproc):
    """Acceptance: a traced 2-device sharded write exports a Chrome trace
    with two distinct device tracks carrying that device's chunk spans."""
    out = subproc("""
        import json
        import numpy as np, jax
        assert len(jax.devices()) >= 2
        from repro import obs
        from repro.core import pipeline as pl, sharded as shd
        from repro.obs import export as ex
        from repro.obs import trace as obs_trace
        x = np.random.default_rng(3).standard_normal(4 * 4096).astype(np.float32)
        with obs_trace.tracing() as tr:
            pl.ChunkedRefactorPipeline(chunk_elems=4096, levels=2,
                                       mesh=shd.make_chunk_mesh(2)
                                       ).refactor(x, "v")
        tj = obs.chrome_trace(tr)
        tracks = ex.device_tracks(tj)
        assert tracks == ["device:0", "device:1"], tracks
        # round-robin: chunks 0,2 on device 0; 1,3 on device 1
        by_dev = {}
        for s in tr.spans():
            if s.name == "sharded.dispatch":
                by_dev.setdefault(s.attrs["device"], []).append(s.attrs["chunk"])
        assert {d: sorted(cs) for d, cs in by_dev.items()} == \
            {0: [0, 2], 1: [1, 3]}
        print("TRACKS " + json.dumps(tracks))
    """, n_devices=2)
    assert "TRACKS" in out


# -------------------------------------------------------- store accounting

def _write_store(tmp_path, x, name="v", chunk_elems=4096):
    root = str(tmp_path / "store")
    with DatasetWriter(root, chunk_elems=chunk_elems) as w:
        entry = w.write(name, x)
    return root, entry


def test_writer_compression_metrics_and_expansion_warning(tmp_path, caplog):
    """Satellite: the writer records raw/stored bytes + ratio per variable
    and warns loudly when a write EXPANDS the data (stored > raw)."""
    # white noise with per-element random exponents defeats the lossless
    # stage -> guaranteed expansion (bitplane + group framing overhead)
    rng = np.random.default_rng(0)
    x = (rng.standard_normal(8192)
         * np.exp(rng.uniform(-30, 30, 8192))).astype(np.float32)
    with obs_metrics.scope() as m:
        with caplog.at_level(logging.WARNING, logger="repro.store"):
            root, entry = _write_store(tmp_path, x)
    raw, stored = x.nbytes, entry.stored_bytes
    assert stored > raw  # the premise of the warning
    assert any("EXPANDED" in r.message for r in caplog.records)
    snap = m.snapshot()
    assert snap["counters"]["store.bytes_raw{var=v}"] == raw
    assert snap["counters"]["store.bytes_stored{var=v}"] == stored
    assert snap["gauges"]["store.compression_ratio{var=v}"] == \
        pytest.approx(raw / stored)


def test_writer_no_warning_on_compressible_data(tmp_path, caplog):
    # one large chunk of a smooth field compresses (ratio > 1): per-chunk
    # framing overhead is what drives small-chunk expansion (ROADMAP item)
    x = gaussian_field((32768,), slope=-3.0, seed=11)
    with caplog.at_level(logging.WARNING, logger="repro.store"):
        _write_store(tmp_path, x, chunk_elems=32768)
    assert not [r for r in caplog.records if "EXPANDED" in r.message]


def test_backend_stats_cached_reread_and_service_stats(tmp_path):
    """Satellite: BackendStats across a cached re-read + multi-session
    serving, surfaced through layout.stats() and RetrievalService.stats()."""
    x = gaussian_field((3 * 4096,), slope=-2.0, seed=9)
    root, entry = _write_store(tmp_path, x)
    store = DatasetStore.open(
        root, backend=CachingBackend(LocalFileBackend(root)))
    # serving=False: the subject is the BYTE cache's hit accounting; the
    # serving tier's plane cache would serve session 2 without any backend
    # reads at all (tests/test_serving.py covers that layer)
    svc = RetrievalService(store, serving=False)
    tol = 1e-3 * float(x.max() - x.min())

    s1 = svc.open_session()
    xh, _, fetched1 = s1.retrieve("v", tol)
    assert fetched1 > 0 and np.abs(xh - x.reshape(-1)).max() <= tol
    st1 = store.stats().snapshot()
    assert st1["cache_misses"] > 0 and st1["bytes_fetched"] > 0
    misses_after_first = st1["cache_misses"]

    # a second session re-reads the same ranges: all hits, no new fetches
    s2 = svc.open_session()
    _, _, fetched2 = s2.retrieve("v", tol)
    st2 = store.stats().snapshot()
    assert st2["cache_misses"] == misses_after_first
    assert st2["cache_hits"] > st1["cache_hits"]
    assert st2["bytes_fetched"] == st1["bytes_fetched"]
    assert 0 < st2["hit_rate"] < 1

    # service-level stats: per-session accounting + backend snapshot
    stats = svc.stats()
    assert stats["store_bytes"] == entry.stored_bytes
    assert stats["backend"] == st2
    assert stats["sessions"][s1.sid]["requests"] == 1
    assert stats["sessions"][s1.sid]["bytes_fetched"] == fetched1
    assert stats["sessions"][s2.sid]["bytes_fetched"] == fetched2
    # a tighter request on session 1 is incremental: only delta bytes
    _, _, fetched3 = s1.retrieve("v", tol / 10)
    assert svc.stats()["sessions"][s1.sid]["requests"] == 2
    assert svc.stats()["sessions"][s1.sid]["bytes_fetched"] == \
        fetched1 + fetched3
    svc.close_session(s2)
    assert s2.sid not in svc.stats()["sessions"]
    store.close()


def test_backend_read_events_and_metrics(tmp_path):
    x = gaussian_field((2 * 4096,), slope=-2.0, seed=4)
    root, _ = _write_store(tmp_path, x)
    store = DatasetStore.open(
        root, backend=CachingBackend(LocalFileBackend(root)))
    svc = RetrievalService(store)
    tol = 1e-2 * float(x.max() - x.min())
    with obs_metrics.scope() as m, obs_trace.tracing() as tr:
        svc.open_session().retrieve("v", tol)
    snap = m.snapshot()
    reads = tr.events(obs_trace.EV_BACKEND_READ)
    assert reads, "cache-backed retrieval must emit backend_read events"
    assert snap["counters"]["backend.bytes_served"] == \
        sum(ev.attrs["bytes"] for _, ev in reads)
    assert snap["counters"]["serve.requests"] == 1
    assert snap["counters"]["serve.bytes_fetched"] > 0
    assert snap["histograms"]["serve.retrieve_s"]["count"] == 1
    per = tr.summary()["spans"]
    assert per["serve.retrieve"]["count"] == 1
    assert "serve.fetch" in per
    store.close()


# ------------------------------------------------------- regression gate

def _gate(tmp_path, artifact: dict, budgets: list) -> int:
    from benchmarks import check_regressions as cr
    art_dir = tmp_path / "artifacts"
    base_dir = tmp_path / "baselines"
    art_dir.mkdir(exist_ok=True)
    base_dir.mkdir(exist_ok=True)
    (art_dir / "bench.json").write_text(json.dumps(artifact))
    (base_dir / "bench.json").write_text(json.dumps(
        {"artifact": "bench.json", "budgets": budgets}))
    return cr.main(["--baselines", str(base_dir), "--artifacts", str(art_dir)])


def test_check_regressions_passes_within_budget(tmp_path):
    art = {"syncs_per_chunk": 4.0, "pipelined": {"codec": {"host_syncs": 21}},
           "compression_ratio": 1.8}
    assert _gate(tmp_path, art, [
        {"path": "syncs_per_chunk", "op": "<=", "value": 4.0},
        {"path": "pipelined.codec.host_syncs", "op": "<=", "value": 25},
        {"path": "compression_ratio", "op": ">=", "value": 1.5},
    ]) == 0


def test_check_regressions_fails_on_doctored_snapshot(tmp_path):
    """Acceptance: doctor the artifact past any single budget -> exit 1."""
    art = {"syncs_per_chunk": 6.0,  # doctored: budget is 4
           "pipelined": {"codec": {"host_syncs": 21}}}
    assert _gate(tmp_path, art, [
        {"path": "syncs_per_chunk", "op": "<=", "value": 4.0,
         "note": "3/chunk write + 1/chunk read"},
        {"path": "pipelined.codec.host_syncs", "op": "<=", "value": 25},
    ]) == 1


def test_check_regressions_fails_on_missing_artifact_or_path(tmp_path):
    from benchmarks import check_regressions as cr
    base_dir = tmp_path / "baselines"
    base_dir.mkdir()
    (base_dir / "b.json").write_text(json.dumps(
        {"artifact": "nope.json",
         "budgets": [{"path": "x", "op": "<=", "value": 1}]}))
    empty_art = tmp_path / "artifacts"
    empty_art.mkdir()
    assert cr.main(["--baselines", str(base_dir),
                    "--artifacts", str(empty_art)]) == 1
    # artifact present but budget path unresolvable -> still a failure
    assert _gate(tmp_path, {"present": 1},
                 [{"path": "absent.leaf", "op": "<=", "value": 1}]) == 1


def test_check_regressions_real_baselines_are_wellformed():
    """Every committed baseline parses, names a real benchmark artifact
    name, and uses known ops (the gate itself runs in the CI bench job)."""
    from benchmarks import check_regressions as cr
    specs = sorted(cr.BASELINES.glob("*.json"))
    assert specs, "no committed baselines under benchmarks/baselines/"
    for p in specs:
        spec = json.loads(p.read_text())
        assert spec["artifact"].endswith(".json")
        assert spec["budgets"], p.name
        for b in spec["budgets"]:
            assert b["op"] in cr.OPS, (p.name, b)
            assert isinstance(b["value"], (int, float)), (p.name, b)


# ------------------------------------------------------- benchmark artifact

def test_write_json_attaches_obs_section(tmp_path, monkeypatch):
    from benchmarks import common
    monkeypatch.setattr(common, "REPO", tmp_path)
    with obs_metrics.scope() as m, obs_trace.tracing():
        with obs_trace.span("bench.stage", chunk=0):
            obs_trace.event(obs_trace.EV_HOST_SYNC, label="codec.stats")
        m.inc("store.bytes_raw", 100)
        path = common.write_json("t", {"x": 1})
    data = json.loads(path.read_text())
    assert data["x"] == 1
    assert data["obs"]["metrics"]["counters"]["store.bytes_raw"] == 100
    assert data["obs"]["trace_summary"]["host_syncs_by_span"] == {
        "codec.stats": 1}
    trace_file = path.parent / data["obs"]["trace_file"]
    tj = json.loads(trace_file.read_text())
    assert obs_export.event_count(tj, "host_sync") == 1


def test_write_json_plain_without_obs(tmp_path, monkeypatch):
    from benchmarks import common
    monkeypatch.setattr(common, "REPO", tmp_path)
    # fresh empty metrics scope + no tracer: the artifact stays plain
    with obs_metrics.scope():
        path = common.write_json("t2", {"x": 2})
    assert "obs" not in json.loads(path.read_text())
