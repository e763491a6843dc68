"""Core HP-MDR numerics: alignment, decomposition, lossless, refactoring."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import align as al
from repro.core import decompose as dc
from repro.core import lossless as ll
from repro.data.fields import gaussian_field


# ------------------------------------------------------------------- align --

@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, al.DEFAULT_MAG_BITS))
def test_align_truncation_bound(seed, planes):
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=512) * 10.0 ** float(rng.integers(-6, 6))).astype(np.float32)
    mag, sign, e = al.align_encode(jnp.asarray(x))
    tail = al.DEFAULT_MAG_BITS - planes
    mag_t = (np.asarray(mag) >> tail) << tail if tail else np.asarray(mag)
    xh = al.align_decode(jnp.asarray(mag_t), sign, e, planes_kept=planes)
    bound = al.truncation_error(int(e), planes)
    assert float(np.abs(np.asarray(xh) - x).max()) <= bound * (1 + 1e-6)


def test_align_zero_array():
    mag, sign, e = al.align_encode(jnp.zeros(64))
    assert int(jnp.sum(mag)) == 0
    x = al.align_decode(mag, sign, e)
    assert float(jnp.abs(x).max()) <= al.truncation_error(int(e), 30)


# --------------------------------------------------------------- decompose --

@pytest.mark.parametrize("shape", [(64,), (33, 47), (16, 20, 24)])
def test_decompose_invertible(shape):
    x = gaussian_field(shape, seed=1)
    lv = dc.num_levels(shape, min_size=4, max_levels=3)
    pieces = dc.decompose(jnp.asarray(x), lv)
    assert sum(int(np.prod(p.shape)) for p in pieces) == x.size
    xr = np.asarray(dc.recompose(pieces, shape, lv))
    assert np.abs(xr - x).max() < 8 * 2 ** -24 * np.abs(x).max() * lv * len(shape)


def _np_split_axis(x, axis):
    """NumPy reference of one decomposition step: [even | detail]."""
    x = np.moveaxis(x, axis, -1)
    xe, xo = x[..., 0::2], x[..., 1::2]
    ne, no = xe.shape[-1], xo.shape[-1]
    right = xe[..., 1:no + 1] if ne > no else np.concatenate(
        [xe[..., 1:], xe[..., -1:]], axis=-1)
    detail = xo - np.float32(0.5) * (xe[..., :no] + right)
    return np.moveaxis(np.concatenate([xe, detail], axis=-1), -1, axis)


def _np_decompose(x, levels):
    """NumPy reference of ``decompose``: strided slicing, and each level's
    detail taken by a boolean mask over the coarse corner, in index order."""
    cur, rev = x.astype(np.float32), []
    for _ in range(levels):
        shape = cur.shape
        for ax in range(cur.ndim):
            if shape[ax] > 1:
                cur = _np_split_axis(cur, ax)
        corner = tuple(slice(0, (d + 1) // 2 if d > 1 else d) for d in shape)
        mask = np.ones(cur.shape, dtype=bool)
        mask[corner] = False
        rev.append(cur.reshape(-1)[np.nonzero(mask.reshape(-1))[0]])
        cur = cur[corner]
    return [cur.reshape(-1)] + rev[::-1]


@pytest.mark.parametrize("shape", [
    (1 << 20,), (2,), (3,), (17,), (1000,), (1001,), (33, 47), (16, 20, 24),
    (1, 40), (40, 1), (1, 1, 33), (5, 1, 7), (9, 1, 1, 6), (0,), (3, 0),
    (2, 0, 3), ()])
def test_decompose_pieces_match_numpy_reference(shape):
    """Every piece equals the reference bit for bit: same values, same
    order, so the stored bytes do not depend on how the data is moved."""
    x = np.random.default_rng(len(shape) * 1009 + sum(shape)).normal(
        size=shape).astype(np.float32)
    lv = dc.num_levels(shape, min_size=2)
    got = jax.jit(dc.decompose, static_argnums=1)(x, lv)
    want = _np_decompose(x, lv)
    assert len(got) == len(want) == lv + 1
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert g.dtype == np.float32 and g.shape == w.shape
        assert np.array_equal(g.view(np.uint32), w.view(np.uint32))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_decompose_error_bound_property(seed):
    """Quantizing the pieces keeps reconstruction within the advertised bound."""
    rng = np.random.default_rng(seed)
    shape = tuple(rng.integers(9, 24, size=rng.integers(1, 3)))
    x = gaussian_field(shape, slope=float(rng.uniform(-3, -1)), seed=seed)
    lv = dc.num_levels(shape, min_size=4, max_levels=3)
    pieces = dc.decompose(jnp.asarray(x), lv)
    eps = []
    noisy = []
    for p in pieces:
        e = float(10.0 ** rng.uniform(-6, -2))
        eps.append(e)
        noise = rng.uniform(-e, e, size=p.shape).astype(np.float32)
        noisy.append(p + noise)
    bound = dc.error_bound(eps, ndim=len(shape), data_amax=float(np.abs(x).max()))
    xr = np.asarray(dc.recompose(noisy, shape, lv))
    assert np.abs(xr - x).max() <= bound * (1 + 1e-5)


# ---------------------------------------------------------------- lossless --

CASES = {
    "skewed": lambda rng: (rng.geometric(0.25, 30000) % 256).astype(np.uint8),
    "zeros": lambda rng: np.zeros(40000, np.uint8),
    "uniform": lambda rng: rng.integers(0, 256, 30000).astype(np.uint8),
    "runs": lambda rng: np.repeat(rng.integers(0, 5, 60),
                                  rng.integers(1, 3000, 60)).astype(np.uint8),
    "tiny": lambda rng: rng.integers(0, 256, 3).astype(np.uint8),
    "empty": lambda rng: np.zeros(0, np.uint8),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("codec", ["huffman", "rle", "dc", "hybrid"])
def test_lossless_roundtrip(case, codec):
    data = CASES[case](np.random.default_rng(1))
    if codec == "hybrid":
        seg = ll.compress_group(data)
    else:
        seg = {"huffman": ll.huffman_encode, "rle": ll.rle_encode,
               "dc": ll.dc_encode}[codec](data)
    seg2 = ll.Segment.from_bytes(seg.to_bytes())
    out = ll.decompress_group(seg2)
    assert np.array_equal(out, data), (case, codec)


@settings(max_examples=25, deadline=None)
@given(st.binary(min_size=0, max_size=20000), st.sampled_from(["huffman", "rle"]))
def test_lossless_roundtrip_property(blob, codec):
    data = np.frombuffer(blob, dtype=np.uint8)
    enc = ll.huffman_encode if codec == "huffman" else ll.rle_encode
    dec = ll.huffman_decode if codec == "huffman" else ll.rle_decode
    assert np.array_equal(dec(enc(data)), data)


def test_huffman_estimate_close_to_actual():
    rng = np.random.default_rng(2)
    data = (rng.geometric(0.3, 50000) % 256).astype(np.uint8)
    hist = np.bincount(data, minlength=256)
    cr_est, lengths, codes = ll.estimate_huffman(hist, data.size)
    seg = ll.huffman_encode(data, hist=hist, codebook=(lengths, codes))
    cr_act = data.size / seg.stored_bytes
    assert abs(cr_est - cr_act) / cr_act < 0.25


def test_algorithm2_selection_logic():
    rng = np.random.default_rng(3)
    cfg = ll.HybridConfig(size_threshold=4096, cr_threshold=1.0)
    small = rng.integers(0, 2, 100).astype(np.uint8)
    assert ll.compress_group(small, cfg).method == "dc"         # S <= T_s
    compressible = np.zeros(50000, np.uint8)
    assert ll.compress_group(compressible, cfg).method == "huffman"
    incompressible = rng.integers(0, 256, 50000).astype(np.uint8)
    assert ll.compress_group(incompressible, cfg).method == "dc"
