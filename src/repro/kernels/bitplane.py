"""Pallas TPU bitplane encode/decode kernels — the paper's three designs.

TPU adaptation (see DESIGN.md §2):

``register_block`` (paper §4.3, the winner; default)
    Input tile (32, 128) int32 in VMEM: lane ``l`` owns the 32 lane-strided
    elements ``x[0..31, l]`` (flat indices ``128 i + l``) — the TPU analogue
    of a thread loading warp-strided elements: loads are fully coalesced and
    encoding needs NO cross-lane communication.  Per lane we perform a 32x32
    bit-matrix transpose in vector registers; ``unroll='naive'`` is the
    direct O(B^2) extraction, ``unroll='butterfly'`` the 5-stage
    Hacker's-Delight transpose (O(B log B)) — the §Perf kernel iteration.

``locality`` (paper §4.1)
    Input tile (128, 32): each sublane-row owns 32 *consecutive* elements
    (one output word).  The narrow 32-lane block is the TPU analogue of the
    design's uncoalesced loads; an in-register transpose puts a word's bits
    on sublanes and a sublane reduction assembles it.  It preserves
    bit-order locality (better downstream compressibility).

``shuffle`` (paper §4.2)
    Same (128, 32) layout and transpose, but the word is assembled with a
    log2(32)-step shift tree of sublane rotations (``pltpu.roll``) — the
    TPU-native analogue of the warp shift-reduce.  Warp
    ``ballot``/``match-any``/``redux`` have no TPU equivalent (no
    warp-collective datapath).

Formats match ``ref.py`` bit-exactly (portability contract): `locality` and
`shuffle` share the consecutive-element format; `register_block` uses the
lane-strided interleave.  Planes are MSB-first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_SUB = 32
TILE_LANE = 128
TILE = TILE_SUB * TILE_LANE


def _u32(x):
    return x.astype(jnp.uint32)


# ------------------------------------------------------ register_block ----

def _transpose32_butterfly(rows):
    """5-stage bit-matrix transpose of 32 uint32 'rows' (vector over lanes).

    rows[i] holds bit b of element i at bit position b.  Returns t with
    t[b] holding bit b of element i at bit position i.
    """
    a = list(rows)
    m = jnp.uint32(0x0000FFFF)
    j = 16
    while j:
        k = 0
        while k < 32:
            t = (a[k] ^ (a[k + j] >> jnp.uint32(j))) & m
            a[k] = a[k] ^ t
            a[k + j] = a[k + j] ^ (t << jnp.uint32(j))
            k = (k + j + 1) & ~j
        j >>= 1
        m = m ^ (m << jnp.uint32(j)) if j else m
    # Orientation (probed empirically, asserted in tests):
    #   in[i] bit b  ->  out[31-b] bit (31-i)
    # so callers reverse the ELEMENT-side row index to get plane words whose
    # bit i corresponds to element i.
    return a


def _encode_register_block_kernel(x_ref, out_ref, *, num_planes: int,
                                  tiles: int, unroll: str):
    x = _u32(x_ref[...])  # (32*tiles, 128)
    for t in range(tiles):
        xt = x[t * TILE_SUB:(t + 1) * TILE_SUB, :]  # (32, 128)
        if unroll == "butterfly":
            # left-align so magnitude bit (num_planes-1) sits at bit 31;
            # reverse element rows so plane-word bit i <- element i.
            shift = jnp.uint32(32 - num_planes)
            rows = [xt[31 - i, :] << shift for i in range(TILE_SUB)]
            tr = _transpose32_butterfly(rows)
            for j in range(num_planes):
                out_ref[j, t * TILE_LANE:(t + 1) * TILE_LANE] = tr[j]
        else:
            for j in range(num_planes):
                b = jnp.uint32(num_planes - 1 - j)
                acc = jnp.zeros((TILE_LANE,), jnp.uint32)
                for i in range(TILE_SUB):
                    acc = acc | (((xt[i, :] >> b) & jnp.uint32(1)) << jnp.uint32(i))
                out_ref[j, t * TILE_LANE:(t + 1) * TILE_LANE] = acc


def _decode_register_block_kernel(p_ref, out_ref, *, num_planes_total: int,
                                  tiles: int, unroll: str):
    p = _u32(p_ref[...])  # (P, 128*tiles)
    P = p.shape[0]
    for t in range(tiles):
        pt = p[:, t * TILE_LANE:(t + 1) * TILE_LANE]
        if unroll == "butterfly":
            rows = [jnp.zeros((TILE_LANE,), jnp.uint32)] * 32
            for j in range(P):
                rows[j] = pt[j, :]
            tr = _transpose32_butterfly(rows)
            shift = jnp.uint32(32 - num_planes_total)
            for i in range(TILE_SUB):
                out_ref[t * TILE_SUB + i, :] = tr[31 - i] >> shift
        else:
            for i in range(TILE_SUB):
                acc = jnp.zeros((TILE_LANE,), jnp.uint32)
                for j in range(P):
                    b = jnp.uint32(num_planes_total - 1 - j)
                    acc = acc | (((pt[j, :] >> jnp.uint32(i)) & jnp.uint32(1)) << b)
                out_ref[t * TILE_SUB + i, :] = acc


# ------------------------------------------------------------ locality ----

def _encode_locality_kernel(x_ref, out_ref, *, num_planes: int, tiles: int):
    # block (128*tiles, 32): row = one word's 32 consecutive elements.  The
    # in-register transpose puts the 32 bits of a word on sublanes, so each
    # plane word is a sublane reduction into a lane-dense row.  The OR-fold
    # is an int32 sum (the bits are disjoint) bitcast back: the TPU compiler
    # implements no unsigned reductions.
    x = _u32(x_ref[...]).T  # (32, 128*tiles)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 0)
    for j in range(num_planes):
        b = jnp.uint32(num_planes - 1 - j)
        w = ((x >> b) & jnp.uint32(1)) << shifts
        s = jnp.sum(jax.lax.bitcast_convert_type(w, jnp.int32), axis=0)
        out_ref[j, :] = jax.lax.bitcast_convert_type(s, jnp.uint32)


def _decode_locality_kernel(p_ref, out_ref, *, num_planes_total: int, tiles: int):
    # bits are built word-per-lane (32, 128*tiles) by a sublane broadcast of
    # each plane row, then transposed once into the (128*tiles, 32)
    # consecutive-element output block
    p = _u32(p_ref[...])  # (P, 128*tiles)
    P, W = p.shape
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (32, W), 0)
    acc = jnp.zeros((32, W), jnp.uint32)
    for j in range(P):
        b = jnp.uint32(num_planes_total - 1 - j)
        acc = acc | (((p[j:j + 1, :] >> shifts) & jnp.uint32(1)) << b)
    out_ref[...] = acc.T


# ------------------------------------------------------------- shuffle ----

def _encode_shuffle_kernel(x_ref, out_ref, *, num_planes: int, tiles: int):
    """Shift-tree word assembly (warp-shuffle analogue): after the in-register
    transpose, element i of each word sits on sublane i and a log2(32)-step
    OR tree of sublane rotations (``pltpu.roll``) folds the word into row 0.
    Rotating the narrow 32-lane layout instead compiles for minutes at
    ``tiles_per_block`` >= 8."""
    x = _u32(x_ref[...]).T  # (32, 128*tiles)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 0)
    for j in range(num_planes):
        b = jnp.uint32(num_planes - 1 - j)
        w = ((x >> b) & jnp.uint32(1)) << shifts  # thread i contributes bit i
        s = 16
        while s >= 1:
            w = w | pltpu.roll(w, 32 - s, 0)  # row r gathers row r + s
            s //= 2
        out_ref[j, :] = w[0, :]


# ------------------------------------------------------------ wrappers ----

def _grid_pad(n: int, tiles_per_block: int) -> int:
    block_elems = TILE * tiles_per_block
    return (n + block_elems - 1) // block_elems


@functools.partial(
    jax.jit,
    static_argnames=("num_planes", "design", "tiles_per_block", "unroll", "interpret"),
)
def encode_pallas(mag: jax.Array, num_planes: int, design: str = "register_block",
                  tiles_per_block: int = 8, unroll: str = "butterfly",
                  interpret: bool = False) -> jax.Array:
    """(N,) uint32 -> (num_planes, W) uint32.  N is padded to a whole grid."""
    n = mag.shape[0]
    g = _grid_pad(n, tiles_per_block)
    n_pad = g * TILE * tiles_per_block
    mag = jnp.pad(mag.astype(jnp.uint32), (0, n_pad - n))
    W = n_pad // 32
    wpb = TILE_LANE * tiles_per_block  # words per block

    if design == "register_block":
        x2 = mag.reshape(-1, TILE_LANE)  # (32*tiles*g, 128)
        kern = functools.partial(_encode_register_block_kernel,
                                 num_planes=num_planes, tiles=tiles_per_block,
                                 unroll=unroll)
        in_spec = pl.BlockSpec((TILE_SUB * tiles_per_block, TILE_LANE),
                               lambda i: (i, 0))
    else:
        x2 = mag.reshape(-1, 32)  # (128*tiles*g, 32): consecutive elems per row
        if design == "locality":
            kern = functools.partial(_encode_locality_kernel,
                                     num_planes=num_planes, tiles=tiles_per_block)
        else:
            kern = functools.partial(_encode_shuffle_kernel,
                                     num_planes=num_planes, tiles=tiles_per_block)
        in_spec = pl.BlockSpec((TILE_LANE * tiles_per_block, 32), lambda i: (i, 0))

    out = pl.pallas_call(
        kern,
        grid=(g,),
        name=f"bitplane_encode_{design}",
        in_specs=[in_spec],
        out_specs=pl.BlockSpec((num_planes, wpb), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((num_planes, W), jnp.uint32),
        interpret=interpret,
    )(x2)
    # canonical plane width pads N to one tile, not a whole grid block
    w_canon = (n + ((-n) % TILE)) // 32
    return out[:, :w_canon]


@functools.partial(
    jax.jit,
    static_argnames=("num_planes_total", "n", "design", "tiles_per_block",
                     "unroll", "interpret"),
)
def decode_pallas(planes: jax.Array, num_planes_total: int, n: int,
                  design: str = "register_block", tiles_per_block: int = 8,
                  unroll: str = "butterfly", interpret: bool = False) -> jax.Array:
    """(P, W) uint32 prefix -> (n,) uint32 truncated magnitudes."""
    P, W = planes.shape
    g = _grid_pad(W * 32, tiles_per_block)
    wpb = TILE_LANE * tiles_per_block
    if W % wpb:  # pad planes to a whole grid block (zero words decode to 0)
        planes = jnp.pad(planes, ((0, 0), (0, g * wpb - W)))
        W = g * wpb

    if design == "register_block":
        kern = functools.partial(_decode_register_block_kernel,
                                 num_planes_total=num_planes_total,
                                 tiles=tiles_per_block, unroll=unroll)
        out2 = pl.pallas_call(
            kern,
            grid=(g,),
            name="bitplane_decode_register_block",
            in_specs=[pl.BlockSpec((P, wpb), lambda i: (0, i))],
            out_specs=pl.BlockSpec((TILE_SUB * tiles_per_block, TILE_LANE),
                                   lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((W * 32 // TILE_LANE, TILE_LANE),
                                           jnp.uint32),
            interpret=interpret,
        )(planes)
        return out2.reshape(-1)[:n]
    else:
        kern = functools.partial(_decode_locality_kernel,
                                 num_planes_total=num_planes_total,
                                 tiles=tiles_per_block)
        out2 = pl.pallas_call(
            kern,
            grid=(g,),
            name="bitplane_decode_locality",
            in_specs=[pl.BlockSpec((P, wpb), lambda i: (0, i))],
            out_specs=pl.BlockSpec((TILE_LANE * tiles_per_block, 32),
                                   lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((W, 32), jnp.uint32),
            interpret=interpret,
        )(planes)
        return out2.reshape(-1)[:n]
