"""Fused one-dispatch write engine: byte-identity with the per-piece oracles
(property-tested over shapes/levels/designs incl. 0-d and empty pieces), the
O(1)-dispatch + O(1)-sync budget contract, stacked lossless entry, and the
dispatch-ahead / stage-timing pipeline semantics."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax

from repro.core import align as al
from repro.core import decompose as dc
from repro.core import lossless as ll
from repro.core import lossless_batch as lb
from repro.core import pipeline as pl
from repro.core import refactor as rf
from repro.core import refactor_fused as rff
from repro.core import retrieve as rt
from repro.kernels import ops as kops
from repro.tune import DEFAULT_CONFIG
from repro.data.fields import gaussian_field

RNG = np.random.default_rng(17)


def _field(shape):
    n = int(np.prod(shape, dtype=int))
    if n == 0:
        return np.zeros(shape, np.float32)
    if n <= 4:
        return RNG.normal(size=shape).astype(np.float32)
    return gaussian_field(shape, slope=-2.0, seed=n % 97)


# ------------------------------------------------------------- byte identity

@pytest.mark.parametrize("shape,design,levels", [
    ((36, 36), "register_block", 2),
    ((33, 47), "locality", 3),
    ((2000,), "shuffle", 2),
    ((), "register_block", 1),          # 0-d
    ((3, 0), "register_block", 2),      # empty
    ((9, 9, 9), "register_block", 1),
])
def test_fused_serialization_identical_to_oracles(shape, design, levels):
    x = _field(shape)
    r_f = rf.refactor_array(x, "t", levels=levels, design=design, fused=True)
    r_b = rf.refactor_array(x, "t", levels=levels, design=design,
                            fused=False, batched=True)
    r_p = rf.refactor_array(x, "t", levels=levels, design=design,
                            batched=False)
    blob = rf.refactored_to_bytes(r_f)
    assert blob == rf.refactored_to_bytes(r_b)
    assert blob == rf.refactored_to_bytes(r_p)
    if x.size:
        xh, bound, _ = rt.ProgressiveReader(r_f).retrieve(1e-4)
        assert np.abs(xh - x).max() <= bound


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 3), st.integers(0, 2), st.integers(0, 2 ** 31 - 1),
       st.sampled_from([1, 2, 3]), st.sampled_from([4, 8, 23]))
def test_fused_identity_property(ndim, extra, seed, levels, group_size):
    rng = np.random.default_rng(seed)
    shape = tuple(int(d) for d in rng.integers(1, 40, ndim)) + (1,) * extra
    x = rng.normal(size=shape).astype(np.float32)
    cfg = ll.HybridConfig(group_size=group_size)
    r_f = rf.refactor_array(x, "p", levels=levels, hybrid=cfg, fused=True)
    r_b = rf.refactor_array(x, "p", levels=levels, hybrid=cfg, fused=False,
                            batched=True)
    assert rf.refactored_to_bytes(r_f) == rf.refactored_to_bytes(r_b)


@pytest.mark.parametrize("force", ["huffman", "rle", "dc"])
def test_fused_identical_under_forced_codecs(force):
    x = gaussian_field((40, 40), slope=-2.0, seed=11)
    cfg = ll.HybridConfig(force=force)
    r_f = rf.refactor_array(x, "t", levels=2, hybrid=cfg, fused=True)
    r_b = rf.refactor_array(x, "t", levels=2, hybrid=cfg, fused=False,
                            batched=True)
    assert rf.refactored_to_bytes(r_f) == rf.refactored_to_bytes(r_b)


# ------------------------------------------------------- stacked lossless API

def test_encode_groups_stacked_matches_rowwise():
    import jax.numpy as jnp
    rows_a = (RNG.geometric(0.25, (3, 4096)) % 256).astype(np.uint8)
    rows_b = RNG.integers(0, 256, (2, 512)).astype(np.uint8)
    rows_c = np.zeros((2, 0), np.uint8)  # empty blobs stay host-side
    segs = lb.encode_groups_stacked(
        [jnp.asarray(rows_a), jnp.asarray(rows_b), jnp.asarray(rows_c)])
    flat = [r for rows in (rows_a, rows_b, rows_c) for r in rows]
    assert len(segs) == len(flat)
    for seg, row in zip(segs, flat):
        assert seg.to_bytes() == ll.compress_group(row).to_bytes()


def test_encode_groups_stacked_two_syncs():
    import jax.numpy as jnp
    rows = (RNG.geometric(0.25, (4, 4096)) % 256).astype(np.uint8)
    more = RNG.integers(0, 256, (3, 4096)).astype(np.uint8)  # same size bucket
    lb.STATS.reset()
    lb.encode_groups_stacked([jnp.asarray(rows), jnp.asarray(more)])
    snap = lb.STATS.snapshot()
    assert snap["host_syncs"] == 2
    assert snap["hist_batches"] == 1  # same-size stacks merged into one bucket


# ----------------------------------------------------------- dispatch budget

def _count_calls(monkeypatch, mod, names):
    counts = {n: 0 for n in names}
    for n in names:
        orig = getattr(mod, n)

        def wrapper(*a, _n=n, _orig=orig, **kw):
            counts[_n] += 1
            return _orig(*a, **kw)

        monkeypatch.setattr(mod, n, wrapper)
    return counts


def test_fused_write_O1_dispatches_and_syncs(monkeypatch):
    """One jitted dispatch + three host syncs per chunk on the fused path,
    regardless of pieces x groups; the per-piece oracle's dispatch count
    scales with the piece count."""
    x = gaussian_field((48, 48), slope=-2.0, seed=5)
    # warm the jit/plan caches so trace-time Python calls don't count
    for levels, gs in [(1, 8), (3, 2)]:
        rf.refactor_array(x, "w", levels=levels,
                          hybrid=ll.HybridConfig(group_size=gs), fused=True)

    kcounts = _count_calls(monkeypatch, kops,
                           ["encode_bitplanes", "encode_bitplanes_batch"])
    acounts = _count_calls(monkeypatch, al, ["align_encode"])
    fused_dispatches, fused_syncs = [], []
    for levels, gs in [(1, 8), (3, 2)]:  # 2 pieces x 4 groups vs 4 x 12
        lb.STATS.reset()
        rff.STATS.reset()
        r = rf.refactor_array(x, "w", levels=levels,
                              hybrid=ll.HybridConfig(group_size=gs),
                              fused=True)
        assert len(r.pieces) == levels + 1
        fused_dispatches.append(rff.STATS.snapshot()["dispatches"])
        fused_syncs.append(lb.STATS.snapshot()["host_syncs"])
    # O(1): one fused dispatch and three syncs, independent of decomposition
    assert fused_dispatches == [1, 1]
    assert fused_syncs == [3, 3]
    # warm path never re-enters the per-piece dispatch sites
    assert kcounts["encode_bitplanes"] == 0
    assert kcounts["encode_bitplanes_batch"] == 0
    assert acounts["align_encode"] == 0

    # per-piece oracle: 2 encode dispatches + 1 align dispatch per piece
    r = rf.refactor_array(x, "w", levels=3, fused=False, batched=True)
    assert kcounts["encode_bitplanes"] == 2 * len(r.pieces)
    assert acounts["align_encode"] == len(r.pieces)


def test_fused_requires_batched():
    with pytest.raises(ValueError, match="fused=True requires batched=True"):
        rf.refactor_array(np.ones((8,), np.float32), batched=False, fused=True)


def test_fused_is_default_and_plan_cache_reused():
    x = gaussian_field((32, 32), slope=-2.0, seed=3)
    rff.STATS.reset()
    rf.refactor_array(x, "a", levels=2)
    builds_first = rff.STATS.snapshot()["plan_builds"]
    rf.refactor_array(x * 2, "b", levels=2)
    snap = rff.STATS.snapshot()
    assert snap["dispatches"] == 2          # fused is the default path
    assert snap["plan_builds"] == builds_first  # second chunk reuses the plan


def test_fused_program_names_its_stages():
    """Each stage of the fused program lowers under its ``jax.named_scope``,
    so a device trace can split the program's time by stage."""
    cfg = DEFAULT_CONFIG
    mag_bits = cfg.resolved_mag_bits()
    plan = rff.fused_encode_plan(
        (4096,), 2, cfg.design, mag_bits,
        tuple(rf._group_plane_split(mag_bits, cfg.group_size)), "jnp",
        cfg.tiles_per_block, cfg.unroll)
    text = plan.run.lower(jax.ShapeDtypeStruct((4096,), np.float32)
                          ).as_text(debug_info=True)
    for scope in ("decompose", "align", "stack", "bitplane", "rows"):
        assert f"jit(_run)/{scope}/" in text, scope


@pytest.mark.parametrize("shape", [(1 << 20,), (40, 36, 33)])
def test_fused_program_has_no_gather(shape):
    """The decompose moves data by static slices only: a strided ``jnp``
    index (``x[0::2]``) or an index-array take would lower to an element
    gather, the slowest way to move the chunk on the chip."""
    cfg = DEFAULT_CONFIG
    mag_bits = cfg.resolved_mag_bits()
    plan = rff.fused_encode_plan(
        shape, dc.num_levels(shape), cfg.design, mag_bits,
        tuple(rf._group_plane_split(mag_bits, cfg.group_size)), "jnp",
        cfg.tiles_per_block, cfg.unroll)
    text = plan.run.lower(jax.ShapeDtypeStruct(shape, np.float32)).as_text()
    assert "gather" not in text and "scatter" not in text


# ------------------------------------------------- pipeline dispatch-ahead

def test_pipelined_copy_in_never_blocks(monkeypatch):
    """The pipelined write path must not pay a per-chunk H2D sync; serial
    mode keeps the barrier for the stage-timing contract."""
    calls = []
    orig = pl._sync_stage
    monkeypatch.setattr(pl, "_sync_stage",
                        lambda dev: (calls.append(1), orig(dev))[1])
    x = gaussian_field((64, 64, 4), slope=-2.0, seed=8)
    p = pl.ChunkedRefactorPipeline(chunk_elems=1 << 13, pipelined=True,
                                   levels=2)
    assert p.stage_timing is False
    blobs = p.refactor(x, "v")
    assert calls == []
    s = pl.ChunkedRefactorPipeline(chunk_elems=1 << 13, pipelined=False,
                                   levels=2)
    assert s.stage_timing is True
    blobs_serial = s.refactor(x, "v")
    assert len(calls) >= s.stats.chunks  # serial mode synced every copy-in
    assert blobs == blobs_serial


@pytest.mark.parametrize("dispatch_ahead", [1, 2, 3])
def test_dispatch_ahead_preserves_order_and_bytes(dispatch_ahead):
    x = gaussian_field((64, 64, 4), slope=-2.0, seed=8)
    base = pl.ChunkedRefactorPipeline(chunk_elems=1 << 13, pipelined=False,
                                      levels=2).refactor(x, "v")
    p = pl.ChunkedRefactorPipeline(chunk_elems=1 << 13, pipelined=True,
                                   levels=2, dispatch_ahead=dispatch_ahead)
    assert p.refactor(x, "v") == base


def test_dispatch_ahead_sink_exception_propagates():
    x = gaussian_field((32, 32, 4), slope=-2.0, seed=8)

    def sink(ci, refd):
        if ci == 2:
            raise RuntimeError("sink boom")
        return b""

    p = pl.ChunkedRefactorPipeline(chunk_elems=1 << 10, pipelined=True,
                                   levels=1, sink=sink, dispatch_ahead=3)
    with pytest.raises(RuntimeError, match="sink boom"):
        p.refactor(x, "v")


def test_writer_fused_store_roundtrip(tmp_path):
    from repro.store import DatasetStore, DatasetWriter, RetrievalService
    x = gaussian_field((24, 24, 24), slope=-2.0, seed=9)
    root_f, root_o = str(tmp_path / "fused"), str(tmp_path / "oracle")
    with DatasetWriter(root_f, chunk_elems=8000) as w:
        w.write("v", x)
    with DatasetWriter(root_o, chunk_elems=8000, fused=False) as w:
        w.write("v", x)
    # identical segment payload bytes on disk, modulo the generation token
    seg_f = [p for p in (tmp_path / "fused").rglob("*.seg")]
    seg_o = [p for p in (tmp_path / "oracle").rglob("*.seg")]
    assert seg_f and seg_o  # layout names segment files <var>-<gen>.seg
    assert seg_f[0].read_bytes() == seg_o[0].read_bytes()
    svc = RetrievalService(DatasetStore.open(root_f), depth=3)
    s = svc.open_session()
    xh, bound, fetched = s.retrieve("v", 1e-4)
    assert float(np.abs(xh - x).max()) <= bound <= 1e-4
    assert fetched > 0
