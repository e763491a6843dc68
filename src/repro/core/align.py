"""Exponent alignment: float <-> sign-magnitude fixed point.

HP-MDR (Alg. 1, step 1) aligns all values of a (level-)array to the global
maximum exponent so bitplane boundaries are consistent across elements.

fp32 path: Bm = 23 magnitude bits in an int32 word (sign kept separately).
With ``e = frexp_exponent(max|x|)`` (i.e. ``max|x| = m * 2**e, m in [0.5,1)``)
and ``scale = 2**(Bm - e)`` we have ``|round(x*scale)| < 2**Bm`` for all x,
so the magnitude always fits in Bm bits.  Bm=23 keeps ``x*scale`` <= 2**23,
where float32 represents every integer EXACTLY — with a larger Bm the
product itself rounds (fp32 ulp > 1 above 2**24) and the 0.5-ulp
quantization bound would be violated.  23 bits is also precisely the
information content of fp32 at the aligned exponent, so nothing is lost:
this matches the paper's alignment to the global maximum exponent.

Error model (used by the retrieval planner, verified by property tests):
  keeping the top ``P`` of ``Bm`` planes, with midpoint reconstruction of the
  truncated tail, gives
      |x - decode(P)| <= (2**(Bm-P-1) + 0.5) / scale      for 0 < P < Bm
      |x - decode(Bm)| <= 0.5 / scale                     (near-lossless floor)
      |x - 0|         <= 2**e                             for P = 0
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_MAG_BITS = 23  # fp32 path: largest Bm with exact fp32 quantization


def max_exponent(x: jax.Array) -> jax.Array:
    """Return integer e with max|x| <= 2**e (frexp convention), e=0 if x==0."""
    if x.size == 0:
        return jnp.zeros((), jnp.int32)
    amax = jnp.max(jnp.abs(x))
    # frexp: amax = m * 2**e with m in [0.5, 1)
    _, e = jnp.frexp(amax)
    return jnp.where(amax > 0, e, jnp.zeros_like(e)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("mag_bits",))
def align_encode(
    x: jax.Array, mag_bits: int = DEFAULT_MAG_BITS
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Quantize to sign-magnitude fixed point aligned at the max exponent.

    Returns (magnitude uint32 [same shape], sign uint32 0/1, exponent int32 scalar).
    """
    x = x.astype(jnp.float32)
    e = max_exponent(x)
    scale = jnp.exp2((mag_bits - e).astype(jnp.float32))
    q = jnp.round(x * scale)
    sign = (q < 0).astype(jnp.uint32)
    mag = jnp.abs(q).astype(jnp.uint32)
    return mag, sign, e


def align_decode(
    mag: jax.Array,
    sign: jax.Array,
    e: jax.Array,
    mag_bits: int = DEFAULT_MAG_BITS,
    planes_kept: int | None = None,
) -> jax.Array:
    """Inverse of align_encode. If ``planes_kept`` < mag_bits, the magnitude is
    assumed already truncated to its top ``planes_kept`` planes and a midpoint
    correction of the truncated tail is applied (MDR-style unbiased decode).

    ``planes_kept`` is a traced argument: one compiled program per piece
    shape serves every precision a progressive read reaches."""
    p = mag_bits if planes_kept is None else planes_kept
    return _align_decode(mag, sign, e, jnp.uint32(max(mag_bits - p, 0)),
                         mag_bits)


@functools.partial(jax.jit, static_argnames=("mag_bits",))
def _align_decode(mag, sign, e, tail, mag_bits):
    mag = mag.astype(jnp.uint32)
    mag = (mag >> tail) << tail
    # midpoint of the truncation interval; applied even at mag==0 (the
    # sign plane travels with the first group, so sign is known).
    half = jnp.uint32(1) << (jnp.maximum(tail, jnp.uint32(1)) - jnp.uint32(1))
    mag = jnp.where(tail >= 1, mag + half, mag)
    scale = jnp.exp2((mag_bits - e).astype(jnp.float32))
    val = mag.astype(jnp.float32) / scale
    return jnp.where(sign > 0, -val, val)


def truncation_error(e: int | np.ndarray, planes_kept: int, mag_bits: int = DEFAULT_MAG_BITS) -> float:
    """Conservative max-norm error bound for keeping ``planes_kept`` planes."""
    e = np.asarray(e, dtype=np.float64)
    if planes_kept <= 0:
        return float(np.exp2(e))
    scale = np.exp2(mag_bits - e)
    if planes_kept >= mag_bits:
        return float(0.5 / scale)
    return float((np.exp2(mag_bits - planes_kept - 1) + 0.5) / scale)
