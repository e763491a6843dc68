"""Progressive gradient compression (the paper's encoder on the wire).

Two pieces:

1. ``compressed_psum``: a drop-in for ``jax.lax.psum`` over a mesh axis that
   transmits only the top-P bitplane groups:
       reduce_scatter(fp32) -> exponent-align -> bitplane encode ->
       all_gather(packed planes, P/31 of the bytes) -> decode locally
   The all-gather payload shrinks to ~P/31 of the raw gradient — directly
   visible in the dry-run HLO as a smaller collective term.  Built on
   shard_map; returns (result, local truncation residual) so callers can do
   error feedback.

2. ``ef_quantize``: error-feedback bitplane truncation for the optimizer
   path (grads quantized to P planes, the truncation error is carried to the
   next step) — the convergence-preserving half, testable on 1 device.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from repro.kernels import ref as kref

MAG_BITS = 23  # exact fp32 quantization bound (see core/align.py)


def _encode_planes(x: jax.Array, planes: int) -> Tuple[jax.Array, jax.Array]:
    """fp32 vector -> (packed top-`planes` magnitude planes + sign plane, e)."""
    amax = jnp.max(jnp.abs(x))
    _, e = jnp.frexp(amax)
    e = jnp.where(amax > 0, e, 0).astype(jnp.int32)
    scale = jnp.exp2((MAG_BITS - e).astype(jnp.float32))
    q = jnp.round(x * scale)
    sign = (q < 0).astype(jnp.uint32)
    # keep only the top `planes` magnitude bits before encoding (3.75x less
    # transpose work than encoding all 30 and slicing)
    mag_top = (jnp.abs(q).astype(jnp.uint32)) >> jnp.uint32(MAG_BITS - planes)
    mag_planes = kref.encode(mag_top, planes, "register_block")
    sign_plane = kref.encode(sign, 1, "register_block")
    packed = jnp.concatenate([sign_plane, mag_planes], axis=0)
    return packed, e


def _decode_planes(packed: jax.Array, e: jax.Array, n: int, planes: int
                   ) -> jax.Array:
    sign = kref.decode(packed[:1], 1, n, "register_block")
    mag = kref.decode(packed[1:], planes, n, "register_block")
    tail = MAG_BITS - planes
    mag = mag << jnp.uint32(tail)
    if tail > 0:
        mag = mag + jnp.uint32(1 << (tail - 1))  # midpoint decode
    scale = jnp.exp2((MAG_BITS - e).astype(jnp.float32))
    val = mag.astype(jnp.float32) / scale
    return jnp.where(sign > 0, -val, val)


def ef_quantize(x: jax.Array, residual: jax.Array, planes: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Bitplane-truncate (x+residual) to `planes`; return (q, new_residual)."""
    flat = (x + residual).astype(jnp.float32).reshape(-1)
    packed, e = _encode_planes(flat, planes)
    q = _decode_planes(packed, e, flat.shape[0], planes).reshape(x.shape)
    return q, (x + residual - q)


def compressed_psum(x: jax.Array, axis_name: str, planes: int = 8
                    ) -> Tuple[jax.Array, jax.Array]:
    """Inside shard_map: mean-reduce `x` over `axis_name` transmitting only
    `planes` magnitude planes in the gather phase.

    Returns (reduced, residual): `residual` is THIS device's truncation error
    on its reduce-scatter shard (for error feedback)."""
    n_dev = jax.lax.axis_size(axis_name)
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.shape[0]) % (n_dev * 4096)
    flat = jnp.pad(flat, (0, pad))
    # phase 1: reduce-scatter raw fp32 (wire = S*(n-1)/n, unavoidable for sum)
    shard = jax.lax.psum_scatter(flat.reshape(n_dev, -1), axis_name,
                                 scatter_dimension=0, tiled=False) / n_dev
    n_local = shard.shape[0]
    # phase 2: encode shard, all-gather only the packed planes
    packed, e = _encode_planes(shard, planes)
    e_all = jax.lax.all_gather(e, axis_name)                  # scalar each
    packed_all = jax.lax.all_gather(packed, axis_name)        # (n, P+1, W)
    decoded = jax.vmap(lambda pk, ee: _decode_planes(pk, ee, n_local, planes)
                       )(packed_all, e_all)
    residual = shard - _decode_planes(packed, e, n_local, planes)
    out = decoded.reshape(-1)[:x.size].reshape(x.shape)
    return out, residual


def make_compressed_allreduce(mesh, axis_name: str, planes: int = 8):
    """jit-ready f(x) -> (mean_over_axis, residual_shard) via shard_map."""
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=P(axis_name), out_specs=(P(axis_name), P(axis_name)),
    )
    def f(x_shard):
        out, res = compressed_psum(x_shard, axis_name, planes)
        return out, res
    return f
