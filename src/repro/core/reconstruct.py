"""Device-resident incremental reconstruction engine (read path).

The transform chain of the read path is linear — bitplane expand -> sign /
scale -> multilevel recompose — so progressive refinement is exactly the
multi-component expansion x~_i = x~_{i-1} + D_i of Duan et al. (progressive
compression framework) and the level-reuse recomposition of HPDR: after the
first reconstruction, a tighter request should cost only a *delta* decode of
the newly fetched plane groups plus a partial recompose, never a from-scratch
rebuild.

``IncrementalReconstructor`` keeps all per-piece reconstruction state on
device:

  * ``mag``   — accumulated uint32 magnitudes.  Newly fetched plane groups
    are decoded *at their bit offsets* (``kernels.ops.decode_bitplanes_offset_batch``)
    and OR-ed in; disjoint bit ranges make the accumulation exact,
    so the magnitudes are bit-identical to a full-stack decode.
  * ``sign``  — decoded once, with the piece's first group.
  * ``value`` — the align-decoded float32 coefficients, refreshed only for
    pieces whose magnitudes changed.
  * per-level recompose intermediates — ``reconstruct_device`` re-runs only
    the recompose *suffix* from the coarsest changed piece (HPDR level
    reuse), through the cached per-(shape, levels) plans of
    ``decompose.recompose_plan``.

Bit-exactness contract: the full-decode oracle (``ProgressiveReader(...,
incremental=False)``) and this engine run the *same* jitted per-level merge
programs on bit-identical inputs (integer magnitude accumulation is exact,
``align_decode`` is shared, and a cached level intermediate is bitwise what
the full pass would have computed), so both paths produce bit-identical
reconstructions.  ``tests/test_reconstruct.py`` property-tests this across
shapes, levels, designs, and multi-step tolerance schedules.

Decoding is batchable *across* engines: ``batch_apply_pending`` drains the
staged plane groups of many engines (across pieces, chunks, variables, and
sessions — the store service's serving batch) and decodes every same-shaped
(rows, words, n, offset) bucket through ONE vmapped kernel call.  Nothing in
this module synchronizes with the host: staged rows go up, decoded state and
the reconstruction stay down on device until a caller materializes them.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import align as al
from repro.core import decompose as dc
from repro.core import lossless_batch as lb
from repro.core.refactor import Refactored


# ------------------------------------------------------------------- stats --

@dataclasses.dataclass
class ReconStats:
    """Counters for the incremental read path (thread-safe, process-global).

    ``bytes_decoded`` counts DELTA plane bytes actually run through the
    bitplane decoder; a full-decode path re-decodes every kept plane on every
    reconstruction (compare ``ProgressiveReader.decoded_plane_bytes``).
    ``levels_reused`` counts recompose stages served from the level cache
    instead of being recomputed."""
    groups_staged: int = 0
    rows_decoded: int = 0
    bytes_decoded: int = 0
    delta_decode_batches: int = 0
    sign_decode_batches: int = 0
    recompose_calls: int = 0
    levels_merged: int = 0
    levels_reused: int = 0
    cache_hits: int = 0

    def __post_init__(self):
        self._lock = threading.Lock()

    def add(self, **kw: int) -> None:
        with self._lock:
            for k, v in kw.items():
                setattr(self, k, getattr(self, k) + v)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {f.name: getattr(self, f.name)
                    for f in dataclasses.fields(self)}

    def reset(self) -> None:
        with self._lock:
            for f in dataclasses.fields(self):
                setattr(self, f.name, 0)


STATS = ReconStats()


@jax.jit
def _or_u32(a: jax.Array, b: jax.Array) -> jax.Array:
    return a | b


@dataclasses.dataclass
class _PendingRows:
    """Staged, not-yet-decoded plane rows of one piece (device-resident)."""
    piece: int
    rows: jax.Array        # (P', W) uint32, MSB-first slice
    row_offset: int        # rows already decoded into the piece's magnitudes


class IncrementalReconstructor:
    """Per-variable(-chunk) device-resident incremental reconstruction state.

    Fed by a ``ProgressiveReader``: ``stage_rows``/``stage_sign`` upload newly
    fetched plane groups, ``reconstruct_device`` returns the up-to-date
    reconstruction as a device array.  Decode work staged here may instead be
    drained by ``batch_apply_pending`` to share kernel launches across many
    engines (the store service's cross-session batch)."""

    def __init__(self, ref: Refactored, backend: str = "auto",
                 device: Optional[jax.Device] = None,
                 config=None):
        from repro import tune as tn  # local: keep import graph flat
        self.ref = ref
        self.backend = backend
        # replayed plan knobs (store manifest / tuned config): decode kernel
        # tiling — part of every batch bucket key so one drained batch never
        # mixes kernel variants
        cfg = config if config is not None else tn.DEFAULT_CONFIG
        self.tiles_per_block = cfg.tiles_per_block
        self.unroll = cfg.unroll
        # owning device of this engine's state (mesh-sharded read path:
        # core.sharded places each chunk's engine on the chunk's device).
        # None = today's single-device path: uncommitted default-device
        # arrays, bit-identical placement-free behavior.
        self.device = device
        # delta plane bytes decoded into THIS engine — per-instance so
        # callers (the QoI loop's per-iteration accounting) stay correct
        # under concurrent sessions; STATS is the process-global aggregate
        self.bytes_decoded = 0
        n_pieces = len(ref.pieces)
        self._mag: List[Optional[jax.Array]] = [None] * n_pieces
        self._sign: List[Optional[jax.Array]] = [None] * n_pieces
        self._value: List[Optional[jax.Array]] = [None] * n_pieces
        self._kept: List[int] = [0] * n_pieces     # planes decoded into _mag
        self._dirty: set = set()
        self._pending: List[_PendingRows] = []
        self._pending_sign: List[Tuple[int, jax.Array]] = []
        # serving-tier mode (repro.store.serving): staged work is a list of
        # (kind, piece, future) whose decoded plane groups arrive from the
        # SHARED cross-session decoder instead of this engine's private
        # kernel batch.  ``shared`` is the owning ServingTier (duck-typed —
        # core never imports store); drained via ``shared.drain_engines``.
        self.shared = None
        self._shared_pending: List[Tuple[str, int, object]] = []
        # recompose level cache: _levels[0] = reshaped corner, _levels[i] =
        # state after merging detail piece i; x_hat = _levels[levels]
        self._levels: Optional[List[jax.Array]] = None

    # ------------------------------------------------------------- staging --
    def _upload(self, rows) -> jax.Array:
        """Host rows -> this engine's device (uncommitted when device=None)."""
        if self.device is None:
            return jnp.asarray(rows, jnp.uint32)
        if isinstance(rows, jax.Array):
            return jax.device_put(rows.astype(jnp.uint32), self.device)
        return jax.device_put(np.asarray(rows, np.uint32), self.device)

    def stage_sign(self, piece: int, rows) -> None:
        """(1, W) uint32 sign plane of a piece's first fetch."""
        if self.ref.pieces[piece].n == 0:
            return
        self._pending_sign.append((piece, self._upload(rows)))

    def stage_rows(self, piece: int, rows, row_offset: int) -> None:
        """(P', W) uint32 plane rows sitting ``row_offset`` rows into the
        piece's MSB-first stack.  Upload only; decode happens batched."""
        if self.ref.pieces[piece].n == 0 or rows.shape[0] == 0:
            return
        self._pending.append(_PendingRows(
            piece, self._upload(rows), row_offset))
        STATS.add(groups_staged=1)

    def stage_shared(self, kind: str, piece: int, fut) -> None:
        """Register a serving-tier decode future (``kind`` is "sign" or
        "group").  The decoded planes are produced (or cache-served) by the
        shared tier and OR-applied at drain time — same exactness argument
        as private staging: magnitude accumulation over disjoint bit ranges
        commutes, so apply order across sessions does not matter."""
        if self.ref.pieces[piece].n == 0:
            return
        self._shared_pending.append((kind, piece, fut))
        STATS.add(groups_staged=1)

    def _take_pending(self) -> List[_PendingRows]:
        out, self._pending = self._pending, []
        return out

    def _take_pending_sign(self) -> List[Tuple[int, jax.Array]]:
        out, self._pending_sign = self._pending_sign, []
        return out

    def _apply_mag(self, piece: int, mag_delta: jax.Array, n_rows: int) -> None:
        cur = self._mag[piece]
        self._mag[piece] = (mag_delta if cur is None
                            else _or_u32(cur, mag_delta))
        self._kept[piece] += n_rows
        self._dirty.add(piece)

    def _apply_sign(self, piece: int, sign: jax.Array) -> None:
        self._sign[piece] = sign
        self._dirty.add(piece)

    # -------------------------------------------------------- reconstruction --
    def _piece_value(self, pi: int) -> jax.Array:
        v = self._value[pi]
        if v is None:
            v = jnp.zeros((self.ref.pieces[pi].n,), jnp.float32)
            if self.device is not None:
                v = jax.device_put(v, self.device)
            self._value[pi] = v
        return v

    def reconstruct_device(self) -> jax.Array:
        """Current reconstruction as a device array (shape ``ref.shape``).

        Decodes any still-pending plane groups (batched), align-decodes only
        the changed pieces, and re-runs only the recompose suffix below the
        coarsest changed piece; a clean engine returns the cached array."""
        if self._pending or self._pending_sign or self._shared_pending:
            batch_apply_pending([self])
        r = self.ref
        if not self._dirty and self._levels is not None:
            STATS.add(cache_hits=1)
            return self._levels[r.levels]
        for pi in self._dirty:
            pm = r.pieces[pi]
            if self._kept[pi] == 0 or pm.n == 0:
                continue
            self._value[pi] = al.align_decode(
                self._mag[pi], self._sign[pi], jnp.int32(pm.exponent),
                r.mag_bits, planes_kept=self._kept[pi])
        plan = dc.recompose_plan(r.shape, r.levels)
        if self._levels is None or 0 in self._dirty:
            shapes = dc.level_shapes(r.shape, r.levels)
            self._levels = [self._piece_value(0).reshape(shapes[-1])
                            ] + [None] * r.levels
            start = 1
        else:
            start = min(self._dirty)
        for i in range(start, r.levels + 1):
            _, merge = plan[i - 1]
            self._levels[i] = merge(self._levels[i - 1], self._piece_value(i))
        STATS.add(recompose_calls=1, levels_merged=r.levels - start + 1,
                  levels_reused=start - 1)
        self._dirty.clear()
        return self._levels[r.levels]


# ------------------------------------------------- cross-engine batched decode

def batch_apply_pending(engines: Sequence[IncrementalReconstructor]) -> None:
    """Drain and decode the staged plane groups of many engines.

    All staged (rows, words, n, row_offset)-compatible groups — across
    pieces, engines, chunks, variables, and sessions — decode through ONE
    vmapped ``decode_bitplanes_offset_batch`` launch per bucket (grouping via
    ``lossless_batch.batch_jobs``, the engine-shared pattern); sign planes
    batch the same way.  Decoded magnitudes are OR-accumulated into each
    engine's device state; no host sync happens here."""
    from repro.kernels import ops as kops  # local: keeps import graph flat

    # serving-tier engines first: their staged futures resolve through the
    # SHARED cross-session decoder (one combined, fairness-bounded batch per
    # tier), then each result is OR-applied into its engine.  Grouped by
    # tier so one drain merges every engine's futures into one pump.
    tiers: Dict[int, Tuple[object, List[IncrementalReconstructor]]] = {}
    for e in engines:
        if e._shared_pending and e.shared is not None:
            tiers.setdefault(id(e.shared), (e.shared, []))[1].append(e)
    for tier, tier_engines in tiers.values():
        tier.drain_engines(tier_engines)

    jobs: List[Tuple[IncrementalReconstructor, _PendingRows]] = [
        (e, p) for e in engines for p in e._take_pending()]
    sign_jobs: List[Tuple[IncrementalReconstructor, int, jax.Array]] = [
        (e, pi, rows) for e in engines
        for pi, rows in e._take_pending_sign()]

    def key(job):
        e, p = job
        # the engine's owning device is part of the bucket: sharded engines
        # (core.sharded) never mix devices in one stacked decode, so each
        # kernel launch runs where its engine state lives
        return (int(p.rows.shape[0]), int(p.rows.shape[1]), p.row_offset,
                e.ref.pieces[p.piece].n, e.ref.mag_bits, e.ref.design,
                e.backend, e.tiles_per_block, e.unroll, e.device)

    for k, pos in lb.batch_jobs(jobs, key).items():
        n_rows, _, offset, n, mag_bits, design, backend, tiles, unroll, _dev = k
        batch = [jobs[p] for p in pos]
        stacked = jnp.stack([p.rows for _, p in batch])
        mags = kops.decode_bitplanes_offset_batch(
            stacked, mag_bits, n, offset, design, backend=backend,
            tiles_per_block=tiles, unroll=unroll)
        row_bytes = 4 * n_rows * int(stacked.shape[2])
        STATS.add(delta_decode_batches=1, rows_decoded=n_rows * len(batch),
                  bytes_decoded=row_bytes * len(batch))
        for j, (e, p) in enumerate(batch):
            e.bytes_decoded += row_bytes
            e._apply_mag(p.piece, mags[j], n_rows)

    def sign_key(job):
        e, pi, rows = job
        return (int(rows.shape[1]), e.ref.pieces[pi].n, e.ref.design,
                e.backend, e.tiles_per_block, e.unroll, e.device)

    for k, pos in lb.batch_jobs(sign_jobs, sign_key).items():
        _, n, design, backend, tiles, unroll, _dev = k
        batch = [sign_jobs[p] for p in pos]
        stacked = jnp.stack([rows for _, _, rows in batch])
        sgs = kops.decode_bitplanes_batch(stacked, 1, n, design,
                                          backend=backend,
                                          tiles_per_block=tiles,
                                          unroll=unroll)
        # sign planes count toward the delta bytes: the full-decode baseline
        # (ProgressiveReader.decoded_plane_bytes) includes them too
        row_bytes = 4 * int(stacked.shape[2])
        STATS.add(sign_decode_batches=1, rows_decoded=len(batch),
                  bytes_decoded=row_bytes * len(batch))
        for j, (e, pi, _) in enumerate(batch):
            e.bytes_decoded += row_bytes
            e._apply_sign(pi, sgs[j])
