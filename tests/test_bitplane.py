"""Bitplane kernels: Pallas (interpret) vs pure-jnp ref vs numpy oracle,
swept over shapes/dtypes/designs — the portability contract is bit-exactness.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref, ops

DESIGNS = ["register_block", "locality", "shuffle"]


@pytest.mark.parametrize("design", DESIGNS)
@pytest.mark.parametrize("n", [1, 100, 4096, 5000, 12289])
@pytest.mark.parametrize("planes", [1, 7, 30, 32])
def test_ref_roundtrip(design, n, planes):
    rng = np.random.default_rng(n + planes)
    mag = rng.integers(0, 2 ** min(planes, 31), n).astype(np.uint32)
    p = ref.encode(jnp.asarray(mag), planes, design)
    assert np.array_equal(np.asarray(p), ref.encode_np(mag, planes, design))
    dec = ref.decode(p, planes, n, design)
    assert np.array_equal(np.asarray(dec), mag)


@pytest.mark.parametrize("design", DESIGNS)
@pytest.mark.parametrize("unroll", ["naive", "butterfly"])
@pytest.mark.parametrize("tiles_per_block", [1, 4])
def test_pallas_interpret_matches_ref(design, unroll, tiles_per_block):
    if design != "register_block" and unroll == "butterfly":
        pytest.skip("butterfly is the register_block unroll")
    rng = np.random.default_rng(0)
    n = 9000
    mag = rng.integers(0, 2 ** 30, n).astype(np.uint32)
    enc = ops.encode_bitplanes(jnp.asarray(mag), 30, design,
                               backend="pallas_interpret",
                               tiles_per_block=tiles_per_block, unroll=unroll)
    enc_ref = ref.encode(jnp.asarray(mag), 30, design)
    assert np.array_equal(np.asarray(enc), np.asarray(enc_ref))
    dec = ops.decode_bitplanes(enc_ref[:9], 30, n, design,
                               backend="pallas_interpret",
                               tiles_per_block=tiles_per_block, unroll=unroll)
    dec_ref = ref.decode(enc_ref[:9], 30, n, design)
    assert np.array_equal(np.asarray(dec), np.asarray(dec_ref))


@pytest.mark.parametrize("design", DESIGNS)
def test_prefix_is_truncation(design):
    """A plane prefix decodes to the magnitude with low bits zeroed."""
    rng = np.random.default_rng(7)
    n = 4500
    mag = rng.integers(0, 2 ** 30, n).astype(np.uint32)
    planes = ref.encode(jnp.asarray(mag), 30, design)
    for p in [1, 4, 17, 30]:
        dec = np.asarray(ref.decode(planes[:p], 30, n, design))
        assert np.array_equal(dec, (mag >> (30 - p)) << (30 - p)), p


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 3000), st.integers(1, 31), st.integers(0, 2 ** 31 - 1))
def test_roundtrip_property(n, planes, seed):
    rng = np.random.default_rng(seed)
    mag = rng.integers(0, 2 ** planes, n, dtype=np.int64).astype(np.uint32)
    p = ref.encode(jnp.asarray(mag), planes, "register_block")
    dec = ref.decode(p, planes, n, "register_block")
    assert np.array_equal(np.asarray(dec), mag)


@pytest.mark.parametrize("design", DESIGNS)
def test_unroll_naive_butterfly_parity_interpret(design):
    """unroll='naive' and unroll='butterfly' are execution strategies, not
    formats: encode and decode must be bit-identical across all three Pallas
    designs (for locality/shuffle the knob is inert by design)."""
    rng = np.random.default_rng(13)
    n, planes = 5000, 12
    mag = rng.integers(0, 2 ** planes, n).astype(np.uint32)
    encs = {u: np.asarray(ops.encode_bitplanes(
        jnp.asarray(mag), planes, design, backend="pallas_interpret",
        unroll=u)) for u in ("naive", "butterfly")}
    assert np.array_equal(encs["naive"], encs["butterfly"])
    assert np.array_equal(encs["naive"],
                          np.asarray(ref.encode(jnp.asarray(mag), planes,
                                                design)))
    prefix = jnp.asarray(encs["naive"][:5])
    decs = {u: np.asarray(ops.decode_bitplanes(
        prefix, planes, n, design, backend="pallas_interpret", unroll=u))
        for u in ("naive", "butterfly")}
    assert np.array_equal(decs["naive"], decs["butterfly"])
    assert np.array_equal(decs["naive"],
                          np.asarray(ref.decode(prefix, planes, n, design)))


@pytest.mark.parametrize("design", DESIGNS)
@pytest.mark.parametrize("unroll", ["naive", "butterfly"])
def test_tiles_per_block_sweep_identical(design, unroll):
    """tiles_per_block only changes the grid blocking — 1/4/8 must produce
    identical planes and identical decodes."""
    rng = np.random.default_rng(29)
    n, planes = 13000, 10  # > 8 tiles, not a whole block at any sweep point
    mag = rng.integers(0, 2 ** planes, n).astype(np.uint32)
    encs = [np.asarray(ops.encode_bitplanes(
        jnp.asarray(mag), planes, design, backend="pallas_interpret",
        tiles_per_block=t, unroll=unroll)) for t in (1, 4, 8)]
    assert np.array_equal(encs[0], encs[1])
    assert np.array_equal(encs[0], encs[2])
    prefix = jnp.asarray(encs[0][:7])
    decs = [np.asarray(ops.decode_bitplanes(
        prefix, planes, n, design, backend="pallas_interpret",
        tiles_per_block=t, unroll=unroll)) for t in (1, 4, 8)]
    assert np.array_equal(decs[0], decs[1])
    assert np.array_equal(decs[0], decs[2])


def test_formats_are_distinct_but_sizes_equal():
    rng = np.random.default_rng(3)
    mag = rng.integers(0, 2 ** 30, 8192).astype(np.uint32)
    a = np.asarray(ref.encode(jnp.asarray(mag), 30, "locality"))
    b = np.asarray(ref.encode(jnp.asarray(mag), 30, "register_block"))
    assert a.shape == b.shape
    assert not np.array_equal(a, b)  # different interleave, same size


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("design", DESIGNS)
def test_offset_decode_one_program_for_every_offset(design, backend):
    """A 4-plane slice at any offset decodes to exactly that slice's bits of
    the magnitude, and every offset reuses one compiled program (the offset
    is a traced shift)."""
    rng = np.random.default_rng(11)
    n, total, rows = 5000, 24, 4
    mags = rng.integers(0, 2 ** total, (2, n)).astype(np.uint32)
    planes = jnp.stack([ref.encode(jnp.asarray(m), total, design)
                        for m in mags])
    ops._decode_shifted_batch.clear_cache()
    for offset in range(0, total - rows + 1, rows):
        got = ops.decode_bitplanes_offset_batch(
            planes[:, offset:offset + rows], total, n, offset, design,
            backend=backend)
        lo = total - offset - rows
        want = mags & np.uint32(((1 << rows) - 1) << lo)
        assert np.array_equal(np.asarray(got), want), offset
    assert ops._decode_shifted_batch._cache_size() == 1
    with pytest.raises(ValueError, match="overrun"):
        ops.decode_bitplanes_offset_batch(planes[:, :rows], total, n,
                                          total - rows + 1, design,
                                          backend=backend)
