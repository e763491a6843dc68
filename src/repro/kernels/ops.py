"""jit'd dispatch wrappers for the bitplane kernels.

Backend selection:
  'auto'             -> Pallas kernel on TPU, pure-jnp reference on CPU/GPU
  'pallas'           -> Pallas compiled (TPU)
  'pallas_interpret' -> Pallas interpret mode (CPU validation of the kernel body)
  'jnp'              -> pure-jnp reference (also the fast CPU path)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import bitplane as _bp

_DEFAULT_BACKEND = "auto"


def _resolve(backend: str) -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return backend


def config_kwargs(config) -> dict:
    """Kernel-facing kwargs of a ``repro.tune.RefactorConfig`` (duck-typed
    so this module stays import-light): expand with ``**`` into any
    encode/decode call below.  The single point coupling the kernel knob
    names to the config schema."""
    return {"design": config.design, "backend": config.backend,
            "tiles_per_block": config.tiles_per_block,
            "unroll": config.unroll}


@functools.partial(jax.jit, static_argnames=("num_planes", "design", "backend",
                                             "tiles_per_block", "unroll"))
def encode_bitplanes(mag: jax.Array, num_planes: int,
                     design: str = "register_block",
                     backend: str = _DEFAULT_BACKEND,
                     tiles_per_block: int = 8,
                     unroll: str = "butterfly") -> jax.Array:
    """(N,) uint32 magnitudes -> (num_planes, W) packed planes (MSB-first)."""
    b = _resolve(backend)
    if b == "jnp":
        return _ref.encode(mag, num_planes, design)
    return _bp.encode_pallas(mag, num_planes, design,
                             tiles_per_block=tiles_per_block, unroll=unroll,
                             interpret=(b == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("num_planes_total", "n", "design",
                                             "backend", "tiles_per_block",
                                             "unroll"))
def decode_bitplanes(planes: jax.Array, num_planes_total: int, n: int,
                     design: str = "register_block",
                     backend: str = _DEFAULT_BACKEND,
                     tiles_per_block: int = 8,
                     unroll: str = "butterfly") -> jax.Array:
    """(P, W) plane prefix -> (n,) uint32 magnitudes truncated to P planes."""
    b = _resolve(backend)
    if b == "jnp":
        return _ref.decode(planes, num_planes_total, n, design)
    return _bp.decode_pallas(planes, num_planes_total, n, design,
                             tiles_per_block=tiles_per_block, unroll=unroll,
                             interpret=(b == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("num_planes", "design", "backend",
                                             "tiles_per_block", "unroll"))
def encode_bitplanes_batch(mags: jax.Array, num_planes: int,
                           design: str = "register_block",
                           backend: str = _DEFAULT_BACKEND,
                           tiles_per_block: int = 8,
                           unroll: str = "butterfly") -> jax.Array:
    """(B, N) uint32 magnitudes -> (B, num_planes, W): one vmapped launch for
    B same-length encodes — the write-side twin of ``decode_bitplanes_batch``.
    Used by the fused write engine (``core.refactor_fused``) to encode every
    same-padded-size piece of a chunk in a single dispatch."""
    return jax.vmap(lambda m: encode_bitplanes(
        m, num_planes, design, backend, tiles_per_block, unroll))(mags)


@functools.partial(jax.jit, static_argnames=("num_planes_total", "n", "design",
                                             "backend", "tiles_per_block",
                                             "unroll"))
def decode_bitplanes_batch(planes: jax.Array, num_planes_total: int, n: int,
                           design: str = "register_block",
                           backend: str = _DEFAULT_BACKEND,
                           tiles_per_block: int = 8,
                           unroll: str = "butterfly") -> jax.Array:
    """(B, P, W) plane prefixes -> (B, n): one vmapped launch for B
    same-shape decodes — used by ``store.service.reconstruct_many`` to share
    kernel launches across chunks, variables, and sessions."""
    return jax.vmap(lambda p: decode_bitplanes(
        p, num_planes_total, n, design, backend, tiles_per_block, unroll))(planes)


def _shard_batch(fn, batch: jax.Array, mesh, axis: str):
    """Run a batched bitplane op under a mesh axis via ``shard_map``.

    ``batch``'s leading dimension is split across ``mesh``'s ``axis``; each
    device traces the same jitted batch op over its rows (collective-free,
    so results are bitwise placement-independent).  The thin wrapper is what
    lets the encode/decode batch ops trace under a mesh axis: their
    ``static_argnames`` jits can't be handed to ``shard_map`` directly with
    per-call statics bound."""
    from jax.sharding import PartitionSpec as P

    size = mesh.shape[axis]
    if int(batch.shape[0]) % size != 0:
        raise ValueError(
            f"batch dim {batch.shape[0]} not divisible by mesh axis "
            f"{axis!r} of size {size}")
    return jax.shard_map(fn, mesh=mesh, in_specs=P(axis),
                         out_specs=P(axis), check_vma=False)(batch)


def encode_bitplanes_sharded(mags: jax.Array, num_planes: int,
                             design: str = "register_block",
                             backend: str = _DEFAULT_BACKEND,
                             tiles_per_block: int = 8,
                             unroll: str = "butterfly", *,
                             mesh, axis: str = "chunk") -> jax.Array:
    """``encode_bitplanes_batch`` sharded over a mesh axis: (B, N) rows
    split across the axis's devices, each encoding its shard in place with
    no collectives.  B must divide by the axis size.  Bit-identical to the
    unsharded batch op (tests/test_sharded.py)."""
    return _shard_batch(
        lambda m: encode_bitplanes_batch(m, num_planes, design, backend,
                                         tiles_per_block, unroll),
        mags, mesh, axis)


def decode_bitplanes_sharded(planes: jax.Array, num_planes_total: int, n: int,
                             design: str = "register_block",
                             backend: str = _DEFAULT_BACKEND,
                             tiles_per_block: int = 8,
                             unroll: str = "butterfly", *,
                             mesh, axis: str = "chunk") -> jax.Array:
    """``decode_bitplanes_batch`` sharded over a mesh axis: (B, P, W) plane
    prefixes split across the axis's devices, decoded shard-local with no
    collectives.  B must divide by the axis size."""
    return _shard_batch(
        lambda p: decode_bitplanes_batch(p, num_planes_total, n, design,
                                         backend, tiles_per_block, unroll),
        planes, mesh, axis)


def decode_bitplanes_offset_batch(planes: jax.Array, num_planes_total: int,
                                  n: int, plane_offset: int,
                                  design: str = "register_block",
                                  backend: str = _DEFAULT_BACKEND,
                                  tiles_per_block: int = 8,
                                  unroll: str = "butterfly") -> jax.Array:
    """(B, P, W) same-offset plane-group slices -> (B, n) partial magnitudes
    in one vmapped launch.  Row ``j`` of each slice sits ``plane_offset``
    rows into the MSB-first stack and carries magnitude bit
    ``num_planes_total - 1 - (plane_offset + j)``.

    The magnitudes hold ONLY those bits — OR-ing the results of disjoint
    slices reproduces the full-stack decode exactly (integer bits are
    disjoint), which is what makes the incremental read path
    (``core.reconstruct``) bit-exact with the full-decode oracle.  Used to
    decode newly fetched groups across pieces, chunks, variables, and
    sessions in one call.

    Implemented as a decode of the slice alone (its rows land on bits
    ``P-1 .. 0``) followed by a left shift by the slice's depth below the
    top, ``num_planes_total - plane_offset - P``.  The shift is a traced
    operand, so one compiled program per slice shape serves every offset."""
    shift = num_planes_total - plane_offset - int(planes.shape[1])
    if shift < 0:
        raise ValueError(
            f"{planes.shape[1]} planes at offset {plane_offset} overrun a "
            f"{num_planes_total}-plane stack")
    return _decode_shifted_batch(planes, jnp.uint32(shift), n, design,
                                 backend, tiles_per_block, unroll)


@functools.partial(jax.jit, static_argnames=("n", "design", "backend",
                                             "tiles_per_block", "unroll"))
def _decode_shifted_batch(planes: jax.Array, shift: jax.Array, n: int,
                          design: str, backend: str, tiles_per_block: int,
                          unroll: str) -> jax.Array:
    return decode_bitplanes_batch(planes, int(planes.shape[1]), n, design,
                                  backend, tiles_per_block, unroll) << shift
