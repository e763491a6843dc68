"""Fused one-dispatch write engine (paper §4-§6 pipeline optimization).

The batched write path of ``refactor.refactor_array`` keeps everything on
device but still drives the encode chain piece by piece: an eager multilevel
decompose (one dispatch per transform op), then per piece one jitted
``align_encode`` plus two jitted ``encode_bitplanes`` calls over ragged
per-level shapes, then one bitcast/slice per merged group — the write path
stays launch-bound, which is exactly the bottleneck the paper's fused
refactoring kernel chain removes (HP-MDR §6, HPDR's fused encode chain).

This module compiles the WHOLE chain — decompose -> exponent alignment /
quantization -> bitplane encode -> per-group byte blobs, plus the scalar
pass (amax / range / per-piece exponents) — into ONE jitted program, cached
per ``(shape, levels, design, mag_bits, group_planes, backend, ...)`` like
``decompose.recompose_plan`` caches the read side:

  * pieces are padded with zeros to whole bitplane tiles (zero elements
    contribute zero bits — bit-identical to the kernels' own padding),
    bucketed by padded word count, and stacked;
  * each bucket's magnitudes and signs encode through one vmapped
    ``kernels.ops.encode_bitplanes_batch`` launch (the write-side twin of
    the read path's ``decode_bitplanes_batch``);
  * the plane stacks are sliced into merged groups and bitcast to stacked
    uint8 blob rows INSIDE the program, so group boundaries cost no extra
    dispatches and ``lossless_batch.encode_groups_stacked`` consumes the
    rows without re-slicing.

Per chunk that is exactly ONE jitted dispatch for the whole encode chain
(``STATS.dispatches``), independent of pieces x groups, and the same three
host syncs as the batched path: one for the fused scalar pass, two inside
the lossless engine.  ``finish_encode`` is separate from ``dispatch_encode``
so the chunked pipeline can keep chunk k+1's fused encode in flight on
device while chunk k's host-side lossless selection, packing, and serialize
run (dispatch-ahead; see ``core.pipeline.ChunkedRefactorPipeline``).

Bit-exactness contract: serializations are byte-identical to the per-piece
paths (``refactor_array(fused=False)`` and ``batched=False``), which stay as
oracles — property-tested in tests/test_refactor_fused.py across shapes,
levels, and designs, including 0-d and empty pieces.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import align as al
from repro.core import decompose as dc
from repro.core import lossless as ll
from repro.core import lossless_batch as lb
from repro.core import refactor as rf
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.obs import trace as obs_trace
from repro import tune as tn


# ------------------------------------------------------------------- stats --

@dataclasses.dataclass
class FusedStats:
    """Counters for the fused write engine (thread-safe, process-global).

    ``dispatches`` counts invocations of the single cached jitted program —
    the write path's dispatch budget is ONE per chunk.  ``plan_builds``
    counts cache misses (trace + compile), so steady-state writes show
    ``plan_builds`` << ``dispatches``."""
    dispatches: int = 0
    finishes: int = 0
    plan_builds: int = 0
    pieces_encoded: int = 0

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


STATS = FusedStats()


# -------------------------------------------------------------------- plan --

def piece_sizes(shape: Sequence[int], levels: int) -> List[int]:
    """Element count of every decompose piece, statically from the shape.

    Matches ``decompose.decompose`` order: [corner, detail_L (coarsest),
    ..., detail_1]; detail k holds everything of the level-k working shape
    except its coarse corner."""
    shapes = dc.level_shapes(shape, levels)  # [finest ... coarsest]
    prods = [int(np.prod(s, dtype=np.int64)) for s in shapes]
    return [prods[levels]] + [prods[k - 1] - prods[k]
                              for k in range(levels, 0, -1)]


@dataclasses.dataclass(frozen=True)
class _StackEntry:
    """One stacked blob family emitted by the fused program: rows are the
    ``kind`` ('sign' or 'group') blobs of the bucket's pieces."""
    kind: str
    group: int            # group index, -1 for sign
    piece_idxs: Tuple[int, ...]
    n_words: int


@dataclasses.dataclass(frozen=True)
class FusedPlan:
    shape: Tuple[int, ...]
    levels: int
    design: str
    mag_bits: int
    group_planes: Tuple[int, ...]
    piece_ns: Tuple[int, ...]
    entries: Tuple[_StackEntry, ...]
    empty_pieces: Tuple[int, ...]
    run: object           # jitted (x,) -> (exps, amax?, rng?, *blob stacks)
    run_donated: object   # same program, input buffer donated
    has_scalars: bool     # amax/range present (x.size > 0)


def _bytes_rows(planes: jax.Array) -> jax.Array:
    """(B, P, W) uint32 plane stacks -> (B, 4*P*W) uint8 blob rows.

    Row ``b`` is byte-for-byte ``refactor._device_bytes(planes[b])`` — the
    little-endian bitcast layout the per-piece paths serialize."""
    b = planes.shape[0]
    flat = planes.reshape(b, -1)
    return jax.lax.bitcast_convert_type(flat, jnp.uint8).reshape(b, -1)


# Cached like decompose.level_merge_fn: the live set is #distinct
# (chunk shape, levels, design) combinations of a workload, far below the
# cap; eviction re-derives the plan rather than pinning compiled programs.
@functools.lru_cache(maxsize=32)
def fused_encode_plan(shape: Tuple[int, ...], levels: int, design: str,
                      mag_bits: int, group_planes: Tuple[int, ...],
                      backend: str, tiles_per_block: int = 8,
                      unroll: str = "butterfly") -> FusedPlan:
    """Build (and cache) the one-dispatch encode program for a chunk shape.

    The returned plan's ``run(x)`` is a single jitted program emitting the
    per-piece exponent vector, the amax/range scalars, and every stacked
    blob family of the chunk (sign planes + merged groups per size bucket).
    """
    STATS.add(plan_builds=1)
    piece_ns = tuple(piece_sizes(shape, levels))
    size = int(np.prod(shape, dtype=np.int64)) if shape else 1
    # bucket non-empty pieces by padded word count: same-padded pieces stack
    # exactly and share one vmapped encode launch
    buckets: Dict[int, List[int]] = {}
    for pi, n in enumerate(piece_ns):
        if n > 0:
            buckets.setdefault(kref.padded_words(n, design), []).append(pi)
    empty_pieces = tuple(pi for pi, n in enumerate(piece_ns) if n == 0)

    entries: List[_StackEntry] = []
    for w, idxs in buckets.items():
        entries.append(_StackEntry("sign", -1, tuple(idxs), w))
        for gi in range(len(group_planes)):
            entries.append(_StackEntry("group", gi, tuple(idxs), w))

    # each stage under a ``jax.named_scope`` (decompose, align, stack,
    # bitplane, rows), so a device trace can split the program's time
    def _run(x):
        x = x.astype(jnp.float32)
        with jax.named_scope("decompose"):
            pieces = dc.decompose(x, levels)
        exps = []
        mags: List[jax.Array] = [None] * len(pieces)
        signs: List[jax.Array] = [None] * len(pieces)
        with jax.named_scope("align"):
            for pi, piece in enumerate(pieces):
                mag, sign, e = al.align_encode(piece, mag_bits)
                exps.append(e)
                mags[pi], signs[pi] = mag, sign
        outs = [jnp.stack(exps)]
        if size:
            outs.append(jnp.max(jnp.abs(x)))
            outs.append(jnp.max(x) - jnp.min(x))
        for w, idxs in buckets.items():
            n_pad = 32 * w
            with jax.named_scope("stack"):
                mstack = jnp.stack([jnp.pad(mags[i], (0, n_pad - piece_ns[i]))
                                    for i in idxs])
                sstack = jnp.stack([jnp.pad(signs[i],
                                            (0, n_pad - piece_ns[i]))
                                    for i in idxs])
            with jax.named_scope("bitplane"):
                planes = kops.encode_bitplanes_batch(
                    mstack, mag_bits, design, backend, tiles_per_block,
                    unroll)
                sign_planes = kops.encode_bitplanes_batch(
                    sstack, 1, design, backend, tiles_per_block, unroll)
            with jax.named_scope("rows"):
                outs.append(_bytes_rows(sign_planes))
                row = 0
                for g in group_planes:
                    outs.append(_bytes_rows(planes[:, row:row + g, :]))
                    row += g
        return tuple(outs)

    # run_donated aliases the input buffer into the program's workspace
    # (donate_argnums) so a pipeline that owns the placed chunk avoids one
    # encode-input allocation per chunk; jit compiles lazily, so the donated
    # twin costs nothing unless a caller opts in (``dispatch_encode(donate=
    # True)`` — gated on backends that implement donation).
    return FusedPlan(shape=tuple(shape), levels=levels, design=design,
                     mag_bits=mag_bits, group_planes=group_planes,
                     piece_ns=piece_ns, entries=tuple(entries),
                     empty_pieces=empty_pieces, run=jax.jit(_run),
                     run_donated=jax.jit(_run, donate_argnums=(0,)),
                     has_scalars=bool(size))


# ---------------------------------------------------------------- dispatch --

@dataclasses.dataclass
class PendingChunk:
    """One chunk's in-flight fused encode: device handles only, no syncs.

    Produced by ``dispatch_encode`` (one jitted dispatch), consumed by
    ``finish_encode`` (scalar sync + lossless engine).  The chunked pipeline
    holds ``dispatch_ahead`` of these so device encode overlaps host
    lossless/serialize work."""
    name: str
    plan: FusedPlan
    hybrid: ll.HybridConfig
    exps: jax.Array                      # (n_pieces,) int32
    amax: Optional[jax.Array]            # None when the chunk is empty
    rng: Optional[jax.Array]
    stacks: Tuple[jax.Array, ...]        # (B, S) uint8 rows, plan.entries order


def donation_supported() -> bool:
    """Whether the current backend implements input-buffer donation (XLA
    ignores donations on CPU with a warning, so the donated program twin is
    only selected on accelerator backends)."""
    return jax.default_backend() in ("gpu", "tpu")


def dispatch_encode(x, name: str = "var",
                    levels: Optional[int] = None,
                    design: Optional[str] = None,
                    mag_bits: Optional[int] = None,
                    hybrid: Optional[ll.HybridConfig] = None,
                    backend: Optional[str] = None,
                    config: Optional[tn.RefactorConfig] = None,
                    donate: bool = False) -> PendingChunk:
    """Launch one chunk's whole encode chain as a single jitted dispatch.

    Returns immediately with device handles; no host synchronization
    happens until ``finish_encode``.  All knobs normalize into ONE
    ``RefactorConfig`` (``config=`` or legacy kwargs — explicit kwargs win;
    see ``repro.tune.config.as_config``), and the fused program is keyed on
    that config's fields, kernel tiling included.

    ``donate=True`` marks ``x`` as dead after the dispatch so XLA may reuse
    its buffer for the encode workspace (no per-chunk input reallocation) —
    pass it ONLY when the caller owns ``x`` exclusively (the chunked
    pipeline's placed copies qualify; caller-held arrays do not).  On
    backends without donation support (CPU) it is a silent no-op and the
    non-donated program runs — output bytes are identical either way."""
    cfg = tn.as_config(config, design=design, mag_bits=mag_bits,
                       hybrid=hybrid, backend=backend)
    hybrid = cfg.hybrid(force=hybrid.force if hybrid is not None else None)
    mag_bits = cfg.resolved_mag_bits()
    x = jnp.asarray(x, dtype=jnp.float32)
    if levels is None:
        levels = dc.num_levels(x.shape)
    group_planes = tuple(rf._group_plane_split(mag_bits, hybrid.group_size))
    with obs_trace.span("encode.dispatch", name=name):
        plan = fused_encode_plan(tuple(x.shape), levels, cfg.design, mag_bits,
                                 group_planes, cfg.backend,
                                 cfg.tiles_per_block, cfg.unroll)
        run = plan.run_donated if donate and donation_supported() \
            else plan.run
        outs = run(x)
        STATS.add(dispatches=1, pieces_encoded=len(plan.piece_ns))
        obs_trace.event(obs_trace.EV_DISPATCH, kind="fused_encode", name=name,
                        pieces=len(plan.piece_ns))
    exps, rest = outs[0], outs[1:]
    amax = rng = None
    if plan.has_scalars:
        amax, rng, rest = rest[0], rest[1], rest[2:]
    return PendingChunk(name=name, plan=plan, hybrid=hybrid, exps=exps,
                        amax=amax, rng=rng, stacks=tuple(rest))


def finish_encode(p: PendingChunk, _scalars=None) -> rf.Refactored:
    """Resolve a dispatched chunk: ONE scalar sync, then the stacked
    lossless engine (two syncs), then host-side manifest assembly.

    ``_scalars`` lets a caller that already gathered the chunk's
    (exps, amax, rng) host values — the sharded round finisher syncs a whole
    round of chunks across devices in one ``host_sync`` — skip the per-chunk
    sync; values must be exactly ``host_sync((p.exps, p.amax, p.rng))``."""
    STATS.add(finishes=1)
    with obs_trace.span("encode.finish", name=p.name):
        scalars = _scalars
        if scalars is None:
            with obs_trace.span("encode.scalars"):
                scalars = lb.host_sync((p.exps, p.amax, p.rng),
                                       label="encode.scalars")
        segs_flat = lb.encode_groups_stacked(p.stacks, p.hybrid)
        return _assemble(p, scalars, segs_flat)


def stack_rows(p: PendingChunk) -> int:
    """Total blob rows ``p``'s stacks contribute to a flattened
    ``encode_groups_stacked`` call (the split key of the batched finish)."""
    return sum(int(st.shape[0]) for st in p.stacks)


def finish_encode_many(pendings: Sequence[PendingChunk], _scalars=None
                       ) -> List[rf.Refactored]:
    """Resolve MANY dispatched chunks with batch-amortized host work: ONE
    scalar sync gathers every chunk's (exps, amax, range), and ONE stacked
    lossless pass encodes every chunk's blob rows (two syncs total) — the
    whole batch costs 3 host syncs instead of 3 per chunk.

    Blob rows of all chunks flow through a single ``encode_groups_stacked``
    call (same-size stacks merge ACROSS chunks, so the vmapped pack/scan
    kernels run at batch width = the whole drain window); results come back
    in input order and are byte-identical to ``[finish_encode(p) for p in
    pendings]`` — the batch boundary is a scheduling choice, never a format
    one.  Chunks with differing ``HybridConfig``s are grouped and batched
    per config (the codec decision thresholds are config-dependent)."""
    pendings = list(pendings)
    if not pendings:
        return []
    if _scalars is None:
        with obs_trace.span("encode.scalars"):
            _scalars = lb.host_sync(
                [(p.exps, p.amax, p.rng) for p in pendings],
                label="encode.scalars")
    STATS.add(finishes=len(pendings))
    out: List[Optional[rf.Refactored]] = [None] * len(pendings)
    by_cfg = lb.batch_jobs(pendings, lambda p: (
        p.hybrid.group_size, p.hybrid.size_threshold, p.hybrid.cr_threshold,
        p.hybrid.force))
    with obs_trace.span("encode.finish_many", chunks=len(pendings)):
        for idxs in by_cfg.values():
            segs_flat = lb.encode_groups_stacked(
                [st for i in idxs for st in pendings[i].stacks],
                pendings[idxs[0]].hybrid)
            base = 0
            for i in idxs:
                n = stack_rows(pendings[i])
                out[i] = _assemble(pendings[i], _scalars[i],
                                   segs_flat[base:base + n])
                base += n
    return out


def _assemble(p: PendingChunk, scalars, segs_flat: List[ll.Segment]
              ) -> rf.Refactored:
    """Host-side manifest assembly for one finished chunk: scatter the
    chunk's flattened segment rows back to (piece, kind, group) slots and
    build the ``Refactored``.  ``scalars`` are the synced host values of
    (exps, amax, rng); ``segs_flat`` the chunk's segments in
    ``plan.entries`` row order."""
    plan = p.plan
    with obs_trace.span("encode.assemble", name=p.name):
        exps = [int(e) for e in scalars[0]]
        amax = float(scalars[1]) if p.amax is not None else 0.0
        rng = float(scalars[2]) if p.rng is not None else 0.0

        # scatter flattened rows back to (piece, kind, group) slots
        sign_segs: Dict[int, ll.Segment] = {}
        group_segs: Dict[Tuple[int, int], ll.Segment] = {}
        n_words: Dict[int, int] = {}
        base = 0
        for ent in plan.entries:
            for j, pi in enumerate(ent.piece_idxs):
                seg = segs_flat[base + j]
                if ent.kind == "sign":
                    sign_segs[pi] = seg
                    n_words[pi] = ent.n_words
                else:
                    group_segs[(pi, ent.group)] = seg
            base += len(ent.piece_idxs)
        for pi in plan.empty_pieces:
            # empty pieces reproduce the per-piece encoders exactly: every
            # blob is zero-length, n_words is 0
            sign_segs[pi] = ll.compress_group(np.zeros(0, np.uint8), p.hybrid)
            for gi in range(len(plan.group_planes)):
                group_segs[(pi, gi)] = ll.compress_group(
                    np.zeros(0, np.uint8), p.hybrid)
            n_words[pi] = 0

        ndim = len(plan.shape)
        group_planes = list(plan.group_planes)
        metas: List[rf.PieceMeta] = []
        for pi, n in enumerate(plan.piece_ns):
            groups = [group_segs[(pi, gi)] for gi in range(len(group_planes))]
            for g, seg in zip(group_planes, groups):
                seg.meta["n_planes"] = g
                seg.meta["n_words"] = n_words[pi]
            metas.append(rf.PieceMeta(
                n=n, exponent=exps[pi],
                weight=1.0 if pi == 0 else float((1 << ndim) - 1),
                sign_seg=sign_segs[pi], groups=groups,
                group_planes=group_planes))
        return rf.Refactored(name=p.name, shape=plan.shape,
                             levels=plan.levels, design=plan.design,
                             mag_bits=plan.mag_bits,
                             group_size=p.hybrid.group_size, data_amax=amax,
                             data_range=rng, pieces=metas)


def refactor_fused(x, name: str = "var", levels: Optional[int] = None,
                   design: Optional[str] = None,
                   mag_bits: Optional[int] = None,
                   hybrid: Optional[ll.HybridConfig] = None,
                   backend: Optional[str] = None,
                   config: Optional[tn.RefactorConfig] = None
                   ) -> rf.Refactored:
    """One-call fused refactor: ``finish_encode(dispatch_encode(...))``."""
    return finish_encode(dispatch_encode(
        x, name=name, levels=levels, design=design, mag_bits=mag_bits,
        hybrid=hybrid, backend=backend, config=config))
