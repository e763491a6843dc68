"""Tests for the ``repro.tune`` autotune subsystem (PR 7).

Covers the contracts the rest of the stack leans on:

  * ``RefactorConfig`` JSON round-trip, unknown-key tolerance (manifest
    forward-compat), and ``as_config`` precedence (explicit legacy kwargs >
    ``config=`` > defaults);
  * ``lossless.exact_stored_bytes`` matches REAL ``Segment.to_bytes()``
    serializations for every codec (the property the Algorithm-2 store-raw
    fallback depends on), and the fallback never lets a chosen codec expand
    past storing the group raw;
  * the batched engine's ``_select`` mirrors ``compress_group``
    decision-for-decision, fallback included;
  * the ``config=`` spelling is byte-identical to the legacy loose kwargs
    through ``refactor_array`` (fused and per-piece paths);
  * the on-disk cache: store/load, hit/miss counters, corrupt-entry
    tolerance, ``REPRO_TUNE_CACHE`` override, and ``cached_config``;
  * ``tune()`` search logic with the cost model and probe runner stubbed
    out (fast): measured-best-wins, default-always-probed (winner can only
    tie or beat it), cache hit on the second call with NO re-search;
  * one real ``CostModel`` lowering on a small shape (HBM bytes > 0,
    probe calibration moves the scale).
"""
import json

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import tune as tn
from repro.core import lossless as ll
from repro.tune import cache as tcache
from repro.tune import search as tsearch
from repro.tune.config import DEFAULT_CONFIG, RefactorConfig, as_config


# ------------------------------------------------------------------ config --

@settings(max_examples=20, deadline=None)
@given(st.sampled_from(["register_block", "locality", "shuffle"]),
       st.sampled_from([4, 8, 16]),
       st.sampled_from(["naive", "butterfly"]),
       st.integers(1, 16), st.integers(1, 8), st.integers(1, 4))
def test_config_json_roundtrip(design, tiles, unroll, group_size,
                               dispatch_ahead, depth):
    cfg = RefactorConfig(design=design, tiles_per_block=tiles, unroll=unroll,
                         group_size=group_size, dispatch_ahead=dispatch_ahead,
                         depth=depth)
    j = cfg.to_json()
    assert RefactorConfig.from_json(j) == cfg
    # JSON-serializable end to end (what the manifest / cache files store)
    assert RefactorConfig.from_json(json.loads(json.dumps(j))) == cfg


def test_config_from_json_ignores_unknown_keys():
    j = DEFAULT_CONFIG.to_json()
    j["from_the_future"] = {"nested": True}
    j["another"] = 7
    assert RefactorConfig.from_json(j) == DEFAULT_CONFIG


def test_as_config_precedence():
    base = RefactorConfig(design="locality", group_size=8, depth=3)
    # no explicit kwargs: the config passes through untouched (same object)
    assert as_config(base) is base
    assert as_config(None) is DEFAULT_CONFIG
    # explicit legacy kwargs override the base config's fields
    out = as_config(base, design="shuffle", depth=1)
    assert out.design == "shuffle" and out.depth == 1
    assert out.group_size == 8          # untouched fields come from base
    # a hybrid kwarg maps onto the three lossless-policy fields
    hyb = ll.HybridConfig(group_size=2, size_threshold=123, cr_threshold=1.5)
    out = as_config(base, hybrid=hyb)
    assert (out.group_size, out.size_threshold, out.cr_threshold) \
        == (2, 123, 1.5)


def test_program_key_ignores_pipeline_knobs():
    a = DEFAULT_CONFIG
    b = a.replace(dispatch_ahead=4, depth=3, chunk_elems=1 << 12,
                  size_threshold=1, cr_threshold=2.0)
    assert a.program_key() == b.program_key()   # one lowering, shared
    assert a.replace(design="locality").program_key() != a.program_key()


# ------------------------------------------- exact sizes + store-raw fallback

def _profile(kind: str, n: int, rng: np.random.Generator) -> np.ndarray:
    if kind == "const":
        return np.zeros(n, np.uint8)
    if kind == "runs":
        return np.repeat(rng.integers(0, 4, n // 64 + 1).astype(np.uint8),
                         64)[:n]
    if kind == "skew":
        p = np.r_[0.95, np.full(255, 0.05 / 255)]
        return rng.choice(np.arange(256, dtype=np.uint8), n, p=p)
    return rng.integers(0, 256, n).astype(np.uint8)    # incompressible


@settings(max_examples=12, deadline=None)
@given(st.sampled_from([4097, 5000, 8191, 8192, 12288]),
       st.sampled_from(["const", "runs", "skew", "random"]))
def test_exact_stored_bytes_matches_real_serialization(n, kind):
    """The fallback's size oracle is EXACT: ``exact_stored_bytes`` computed
    from selection-time stats equals ``len(Segment.to_bytes())`` of the real
    encoder output, for every codec."""
    import jax.numpy as jnp

    d = _profile(kind, n, np.random.default_rng(n * 31 + len(kind)))
    hist = np.bincount(d, minlength=256)
    bits = int(np.sum(hist * ll.build_codebook(hist)[0].astype(np.int64)))
    _, _, nruns = ll._rle_scan(jnp.asarray(d))
    assert len(ll.dc_encode(d).to_bytes()) == ll.exact_stored_bytes("dc", n)
    assert len(ll.huffman_encode(d).to_bytes()) \
        == ll.exact_stored_bytes("huffman", n, total_bits=bits)
    assert len(ll.rle_encode(d).to_bytes()) \
        == ll.exact_stored_bytes("rle", n, n_runs=int(nruns))


def test_exact_stored_bytes_unknown_method():
    with pytest.raises(ValueError):
        ll.exact_stored_bytes("zstd", 10)


@settings(max_examples=12, deadline=None)
@given(st.sampled_from([4097, 5000, 8192, 12288]),
       st.sampled_from(["const", "runs", "skew", "random"]))
def test_store_raw_fallback_never_expands(n, kind):
    """Algorithm-2 with the fallback: whatever codec wins, the serialized
    group is never larger than storing it raw — and still round-trips."""
    d = _profile(kind, n, np.random.default_rng(n * 17 + len(kind)))
    cfg = ll.HybridConfig(size_threshold=4096)
    seg = ll.compress_group(d, cfg)
    assert len(seg.to_bytes()) <= ll.exact_stored_bytes("dc", n)
    np.testing.assert_array_equal(ll.decompress_group(seg), d)


def test_fallback_picks_dc_near_break_even():
    """Incompressible bytes: the huffman CR estimator can sit just above the
    threshold while the exact serialization expands — the fallback must
    store raw.  (Random uint8 huffman-codes to ~8 bits/sym + codebook, so
    the exact size always exceeds dc's n + 50.)"""
    d = np.random.default_rng(3).integers(0, 256, 8192).astype(np.uint8)
    seg = ll.compress_group(d, ll.HybridConfig(cr_threshold=0.5))
    assert seg.method == "dc"
    # force modes skip the fallback: benchmarks measure the codec asked for
    forced = ll.compress_group(d, ll.HybridConfig(force="huffman"))
    assert forced.method == "huffman"
    assert len(forced.to_bytes()) > ll.exact_stored_bytes("dc", d.size)


@settings(max_examples=10, deadline=None)
@given(st.sampled_from([4097, 5000, 8192]),
       st.sampled_from(["const", "runs", "skew", "random"]))
def test_batched_select_mirrors_compress_group(n, kind):
    """The batched engine's host-side ``_select`` makes the same call as
    ``compress_group`` — fallback included — and the full batched encode is
    byte-identical to the per-group reference."""
    import jax.numpy as jnp

    from repro.core import lossless_batch as lb

    d = _profile(kind, n, np.random.default_rng(n * 7 + len(kind)))
    cfg = ll.HybridConfig(size_threshold=4096)
    ref = ll.compress_group(d, cfg)
    hist = np.bincount(d, minlength=256)
    _, _, nruns = ll._rle_scan(jnp.asarray(d))
    method, _ = lb._select(n, hist, int(nruns), cfg)
    assert method == ref.method
    (seg,) = lb.encode_groups([jnp.asarray(d)], cfg)
    assert seg.to_bytes() == ref.to_bytes()


# ------------------------------------------------- config path byte-identity

@pytest.mark.parametrize("fused", [True, False])
def test_config_path_matches_legacy_kwargs(fused):
    """``config=`` and the legacy loose kwargs are the same write: every
    serialized segment byte-identical (the tuned path can never change the
    bytes a given effective config produces)."""
    from repro.core import refactor as rf

    rng = np.random.default_rng(5)
    t = np.linspace(0.0, 4.0, 4096, dtype=np.float64)
    x = (np.sin(t) + 0.02 * rng.standard_normal(4096)).astype(np.float32)

    legacy = rf.refactor_array(
        x, levels=2, design="locality",
        hybrid=ll.HybridConfig(group_size=8), fused=fused)
    cfg = RefactorConfig(design="locality", group_size=8)
    viacfg = rf.refactor_array(x, levels=2, config=cfg, fused=fused)

    a = [(pi, k, gi, s.to_bytes()) for pi, k, gi, s in rf.iter_segments(legacy)]
    b = [(pi, k, gi, s.to_bytes()) for pi, k, gi, s in rf.iter_segments(viacfg)]
    assert a == b


# ------------------------------------------------------------------- cache --

def _isolate(tmp_path, monkeypatch):
    monkeypatch.setenv(tcache._ENV, str(tmp_path))
    tcache.invalidate_memo()
    tcache.STATS.reset()
    tsearch.STATS.reset()


def test_cache_store_load_and_stats(tmp_path, monkeypatch):
    _isolate(tmp_path, monkeypatch)
    cfg = RefactorConfig(design="shuffle", group_size=2)
    assert tcache.load("fp", "prob") is None            # cold: miss
    assert tcache.STATS.snapshot()["misses"] == 1
    p = tcache.store("fp", "prob", cfg, meta={"probe_s": 0.5})
    assert p.is_file() and str(p).startswith(str(tmp_path))
    assert tcache.load("fp", "prob") == cfg             # memo hit
    tcache.invalidate_memo()
    assert tcache.load("fp", "prob") == cfg             # disk hit
    snap = tcache.STATS.snapshot()
    assert snap["hits"] == 2 and snap["stores"] == 1
    # the stored file carries the meta + identifying keys
    j = json.loads(p.read_text())
    assert j["meta"]["fingerprint"] == "fp" and j["meta"]["probe_s"] == 0.5


def test_cache_corrupt_entry_is_miss(tmp_path, monkeypatch):
    _isolate(tmp_path, monkeypatch)
    tcache.store("fp", "prob", DEFAULT_CONFIG)
    path = tcache._path(tcache.cache_root(), "fp", "prob")
    path.write_text("{not json")
    tcache.invalidate_memo()
    assert tcache.load("fp", "prob") is None            # never raises
    path.write_text(json.dumps({"wrong": "shape"}))
    tcache.invalidate_memo()
    assert tcache.load("fp", "prob") is None


def test_cached_config_consults_env_root(tmp_path, monkeypatch):
    """``cached_config`` (the writer/pipeline lookup) resolves the same
    fingerprint+problem keying as ``tune`` and honors REPRO_TUNE_CACHE."""
    _isolate(tmp_path, monkeypatch)
    shape, levels = (2048,), 2
    assert tn.cached_config(shape, levels=levels) is None
    fp = tcache.backend_fingerprint("auto", 1)
    prob = tcache.problem_key(shape, "float32", levels)
    cfg = RefactorConfig(design="locality")
    tcache.store(fp, prob, cfg)
    assert tn.cached_config(shape, levels=levels) == cfg
    # different problem key: still a miss
    assert tn.cached_config((4096,), levels=levels) is None


# ---------------------------------------------------------------- tune() ----

class _FakeModel:
    """Stands in for ``CostModel``: deterministic scores, no lowering."""

    def __init__(self, shape, levels=None, dtype="float32", peaks=None):
        self.scale = 1.0

    def score(self, cfg):
        # prefer shuffle/group-8 so the probe set reliably contains it
        return 0.1 if (cfg.design == "shuffle" and cfg.group_size == 8) \
            else 1.0

    def calibrate(self, cfg, measured_s):
        self.scale = measured_s
        return self.scale


def _patch_tuner(monkeypatch, measure, pipeline_measure=None,
                 read_measure=None):
    monkeypatch.setattr(tsearch, "CostModel", _FakeModel)
    monkeypatch.setattr(tsearch, "_measure_write", measure)
    # the dispatch_ahead probe runs real multi-chunk pipelined writes;
    # fake it too (deterministic: the default depth measures fastest) so
    # tune() tests stay compile-free
    monkeypatch.setattr(
        tsearch, "_measure_pipeline_write",
        pipeline_measure if pipeline_measure is not None
        else (lambda x, cfg, levels, repeats=2:
              0.5 if cfg.dispatch_ahead == DEFAULT_CONFIG.dispatch_ahead
              else 1.0))
    # likewise the read-depth probe (real pipelined write + reads): stub
    # both the blob production and the measured reconstruct
    monkeypatch.setattr(
        tsearch, "_probe_blobs",
        lambda best, n, levels, dtype, n_chunks:
        (np.linspace(0.0, 1.0, n_chunks * n, dtype=np.float32),
         [b"blob"] * n_chunks))
    monkeypatch.setattr(
        tsearch, "_measure_pipeline_read",
        read_measure if read_measure is not None
        else (lambda blobs, cfg, tol, repeats=2:
              0.5 if cfg.depth == DEFAULT_CONFIG.depth else 1.0))


def test_tune_measured_best_wins_then_cache_hit(tmp_path, monkeypatch):
    _isolate(tmp_path, monkeypatch)

    def measure(x, cfg, levels, repeats=2):
        return 0.25 if (cfg.design == "shuffle" and cfg.group_size == 8) \
            else 1.0

    _patch_tuner(monkeypatch, measure)
    r1 = tn.tune((1024,), levels=2, probes=2)
    assert not r1.cache_hit
    assert r1.config.design == "shuffle" and r1.config.group_size == 8
    assert r1.config.dispatch_ahead in tsearch.DISPATCH_AHEAD
    assert r1.probes and min(s for _, s in r1.probes) == 0.25
    s1 = tsearch.STATS.snapshot()
    assert s1["searches"] == 1 and s1["candidates_scored"] > 0

    # second call: cached winner replayed, NO search, NO scoring
    r2 = tn.tune((1024,), levels=2, probes=2)
    assert r2.cache_hit and r2.config == r1.config
    assert r2.scores == () and r2.probes == ()
    assert tsearch.STATS.snapshot() == s1
    # force=True ignores the hit but refreshes the cache
    r3 = tn.tune((1024,), levels=2, probes=2, force=True)
    assert not r3.cache_hit and r3.config == r1.config
    assert tsearch.STATS.snapshot()["searches"] == 2


def test_tune_winner_never_loses_to_default(tmp_path, monkeypatch):
    """The default config is ALWAYS probed; when nothing measures faster,
    the tuner returns it unchanged (tuning can't regress the default)."""
    _isolate(tmp_path, monkeypatch)
    probed = []

    def measure(x, cfg, levels, repeats=2):
        probed.append(cfg)
        return 1.0                       # everything ties: first probe wins

    _patch_tuner(monkeypatch, measure)
    r = tn.tune((512,), levels=1, probes=3)
    assert probed[0] == DEFAULT_CONFIG   # default heads the probe set
    assert r.config == DEFAULT_CONFIG


def test_tune_survives_probe_failures(tmp_path, monkeypatch):
    _isolate(tmp_path, monkeypatch)

    def measure(x, cfg, levels, repeats=2):
        raise RuntimeError("probe exploded")

    _patch_tuner(monkeypatch, measure)
    r = tn.tune((512,), levels=1, probes=2)
    assert r.config == DEFAULT_CONFIG    # pathological: default, cached
    assert tn.tune((512,), levels=1).cache_hit


class _LoweringFailsModel(_FakeModel):
    """A cost model whose lowering of every ``locality`` candidate fails,
    as the chip's compiler refuses a kernel."""

    def score(self, cfg):
        if cfg.design == "locality":
            raise NotImplementedError("lowering refused: locality kernel")
        return super().score(cfg)


def test_tune_reports_candidate_lowering_failures(tmp_path, monkeypatch):
    """A candidate that fails to lower is reported — in the result, in the
    counters and in the cache entry — never silently dropped."""
    _isolate(tmp_path, monkeypatch)
    _patch_tuner(monkeypatch, lambda x, cfg, levels, repeats=2: 1.0)
    monkeypatch.setattr(tsearch, "CostModel", _LoweringFailsModel)
    tsearch.STATS.reset()
    r = tn.tune((1024,), levels=2, probes=2)
    n_loc = sum(1 for c in tsearch.candidate_space(DEFAULT_CONFIG, "jnp")
                if c.design == "locality")
    assert n_loc > 0 and len(r.failures) == n_loc
    assert all(f.stage == "score" and f.config.design == "locality"
               and "lowering refused" in f.error for f in r.failures)
    assert all(c.design != "locality" for c, _ in r.scores)
    snap = tsearch.STATS.snapshot()
    assert snap["candidates_failed"] == n_loc
    assert snap["candidates_scored"] == len(r.scores)
    p = tcache._path(tcache.cache_root(),
                     tcache.backend_fingerprint("auto", 1),
                     tcache.problem_key((1024,), "float32", 2))
    meta = json.loads(p.read_text())["meta"]
    assert len(meta["failures"]) == n_loc
    assert meta["failures"][0]["config"]["design"] == "locality"


# -------------------------------------------------------------- cost model --

def test_cost_model_real_program():
    """One real lowering: the fused program's HLO yields a nonzero memory
    term (FLOPs may legitimately be 0 — the encode chain is bitwise), and a
    probe calibration rescales predictions to measured units."""
    from repro.tune.cost import CostModel

    m = CostModel((256,), levels=1)
    cost = m.cost(DEFAULT_CONFIG)
    assert cost.hbm_bytes > 0
    assert m.score(DEFAULT_CONFIG) > 0
    before = m.score(DEFAULT_CONFIG)
    m.calibrate(DEFAULT_CONFIG, measured_s=before * 10)
    assert m.score(DEFAULT_CONFIG) == pytest.approx(before * 10)
    # pipeline-knob-only variants share the lowering cache
    assert m.cost(DEFAULT_CONFIG.replace(dispatch_ahead=4)) is cost


def test_tune_probes_dispatch_ahead_through_pipeline(tmp_path, monkeypatch):
    """The window-depth knob is picked by MEASURED multi-chunk pipelined
    probes (one per candidate depth, on probe-shape chunks), and the probe
    chunking never leaks into the cached winner."""
    _isolate(tmp_path, monkeypatch)
    seen = []

    def pmeasure(x, cfg, levels, repeats=2):
        seen.append((cfg.dispatch_ahead, cfg.chunk_elems, x.size))
        return {1: 0.9, 2: 0.2, 4: 0.8}[cfg.dispatch_ahead]

    _patch_tuner(monkeypatch, lambda x, cfg, levels, repeats=2: 1.0,
                 pipeline_measure=pmeasure)
    r = tn.tune((1024,), levels=2, probes=1)
    assert r.config.dispatch_ahead == 2  # fastest measured depth wins
    assert [d for d, _, _ in seen] == list(tsearch.DISPATCH_AHEAD)
    assert all(ce == 1024 and nx == 6 * 1024 for _, ce, nx in seen)
    assert r.config.chunk_elems == DEFAULT_CONFIG.chunk_elems
    # the depth survives the cache round-trip
    assert tn.tune((1024,), levels=2).config.dispatch_ahead == 2


def test_tune_probes_read_depth_through_pipeline(tmp_path, monkeypatch):
    """The read-side ``depth`` knob (ROADMAP gap from PR 8) is picked by
    MEASURED pipelined reconstructs of the winner's own probe blobs — one
    per candidate depth — recorded in the winner (and thus the manifest
    plan), with the probe chunking never leaking into the cached config."""
    _isolate(tmp_path, monkeypatch)
    seen = []

    def rmeasure(blobs, cfg, tol, repeats=2):
        seen.append((cfg.depth, cfg.chunk_elems, len(blobs)))
        return {1: 0.8, 2: 0.9, 4: 0.1}[cfg.depth]

    _patch_tuner(monkeypatch, lambda x, cfg, levels, repeats=2: 1.0,
                 read_measure=rmeasure)
    r = tn.tune((1024,), levels=2, probes=1)
    assert r.config.depth == 4           # fastest measured depth wins
    assert [d for d, _, _ in seen] == list(tsearch.DEPTHS)
    assert all(ce == 1024 and nb == 6 for _, ce, nb in seen)
    assert r.config.chunk_elems == DEFAULT_CONFIG.chunk_elems
    # the depth survives the cache round-trip (what store readers replay
    # from the manifest plan)
    assert tn.tune((1024,), levels=2).config.depth == 4
    # and the cache file records the per-depth probe curve
    p = tcache._path(tcache.cache_root(),
                     tcache.backend_fingerprint("auto", 1),
                     tcache.problem_key((1024,), "float32", 2))
    meta = json.loads(p.read_text())["meta"]
    assert [d for d, _ in meta["depth_probes"]] == list(tsearch.DEPTHS)


def test_platform_peaks_calibrated_from_roofline_artifact(tmp_path,
                                                          monkeypatch):
    """tune.cost reads the machine's roofline.json 'calibrated' section when
    present (env-pointed artifact), and falls back to the nominal PEAKS row
    on any device-kind mismatch, corruption, or unusable rates."""
    from repro.tune import cost as tc

    art = tmp_path / "roofline.json"
    art.write_text(json.dumps({"calibrated": {
        "device_kind": "cpu", "scale": 2.0,
        "flops": 5e10, "hbm_bw": 1.5e10, "link_bw": 5e9}}))
    monkeypatch.setenv(tc.ROOFLINE_ARTIFACT_ENV, str(art))
    p = tc.device_peaks("cpu")
    assert (p.flops, p.hbm_bw, p.link_bw) == (5e10, 1.5e10, 5e9)
    # another device kind's artifact must not apply
    assert tc.device_peaks(tc.V5E) == tc.PEAKS[tc.V5E]
    # corrupt artifact -> nominal, never an exception
    art.write_text("{not json")
    assert tc.device_peaks("cpu") == tc.PEAKS["cpu"]
    # zero/non-finite rates are unusable -> nominal
    art.write_text(json.dumps({"calibrated": {
        "device_kind": "cpu", "flops": 0.0, "hbm_bw": 1e9, "link_bw": 1e9}}))
    assert tc.device_peaks("cpu") == tc.PEAKS["cpu"]
    # absent artifact -> nominal
    monkeypatch.delenv(tc.ROOFLINE_ARTIFACT_ENV)
    monkeypatch.chdir(tmp_path)
    assert tc.device_peaks("cpu") == tc.PEAKS["cpu"]


def test_unknown_device_kind_raises(tmp_path, monkeypatch):
    """The peaks table is keyed by device_kind: a device it does not know
    is an error, not a silent CPU (or any other) default — even when a
    calibration artifact for that kind is present."""
    from repro.tune import cost as tc

    art = tmp_path / "roofline.json"
    art.write_text(json.dumps({"calibrated": {
        "device_kind": "TPU v9 imaginary", "scale": 1.0,
        "flops": 1e12, "hbm_bw": 1e11, "link_bw": 1e10}}))
    monkeypatch.setenv(tc.ROOFLINE_ARTIFACT_ENV, str(art))
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        tc.device_peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        tc.nominal_peaks("gpu")
    assert set(tc.PEAKS) == {tc.V5E, "cpu"}
    assert tc.PEAKS[tc.V5E] == tc.Peaks(197e12, 819e9, 200e9)
