"""Batched, device-resident lossless engine (paper §5 on wide batches).

The per-group codecs in ``repro.core.lossless`` are correct but launch one
host-side histogram plus one tiny jit call per (piece, group) and pull every
plane array to host before compressing — O(pieces x groups) host<->device
round-trips per chunk.  This module is the batched formulation the paper's
GPU encoder implies: the whole chunk's merged plane groups stay on device
and flow through a handful of wide kernels.

Write path (``encode_groups``), per call:

  1. stack the chunk's group blobs into same-size buckets (the groups of a
     piece share a size, so a chunk has ~#pieces buckets — stacking is
     exact, no padding work),
  2. one vmapped pass per bucket computes all 256-bin histograms AND all
     RLE run-break counts (``_group_stats_batch``),
  3. **sync #1** (small): every bucket's histograms + run counts come to
     host in one ``device_get``, where Algorithm-2 selection and
     canonical-codebook construction run (the codebook build is a 256-entry
     heap per group — negligible),
  4. the Huffman groups of each bucket are packed by one vmapped
     ``_huffman_pack_batch`` invocation (literally ``vmap`` of the
     reference ``_huffman_pack`` — bit-identity by construction), the RLE
     groups by one ``_rle_scan_batch``,
  5. **sync #2** (payloads): a single ``jax.device_get`` materializes every
     payload of the chunk; host code only trims per-row tails.

That is the one-big-sync-per-chunk contract: exactly two host syncs per
``encode_groups`` call (plus one for the alignment scalars — in
``repro.core.refactor.refactor_array`` on the piece-at-a-time path, in
``repro.core.refactor_fused.finish_encode`` on the default fused path),
and O(#pieces) kernel launches — independent of how many merged groups the
chunk decomposes into.  ``encode_groups_stacked`` is the same engine for
blob rows the fused write program already stacked on device (no re-slice).
Outputs are **bit-identical** to running ``lossless.compress_group`` per
group (tests/test_lossless_batch.py checks serialized bytes).

Read path (``decode_segments``): all same-shape Huffman (resp. RLE)
segments of a request are decoded through one vmapped
``_huffman_unpack``/``_rle_expand`` batch, with a single ``jax.device_get``
for every decoded blob.

All host materialization in this module goes through ``host_sync`` so tests
and benchmarks can count syncs (``STATS``) — and, under an ``obs.tracing``
context, every sync records a typed ``host_sync`` event tagged with its
call-site label on the current span, so traces attribute each sync to the
stage that caused it.

``STATS`` is **context-local** (``obs.trace.ContextLocal``): each
``stats_scope()`` context counts only its own work (dispatch-ahead worker
threads run under a copied context and add to their caller's instance),
while code outside any scope shares the process-global default — the
historical behaviour.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lossless as ll
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


# ------------------------------------------------------------------- stats --

@dataclasses.dataclass
class BatchStats:
    """Counters for the batched engine (thread-safe).

    ``host_syncs`` counts explicit device->host materializations
    (``host_sync`` calls); the refactor write path performs O(1) of them per
    chunk.  ``*_batches`` count kernel-batch invocations, i.e. how many
    launches served how many groups."""
    encode_calls: int = 0
    decode_calls: int = 0
    groups_encoded: int = 0
    groups_decoded: int = 0
    host_syncs: int = 0
    hist_batches: int = 0
    huffman_pack_batches: int = 0
    rle_scan_batches: int = 0
    huffman_unpack_batches: int = 0
    rle_expand_batches: int = 0

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


class _StatsProxy:
    """Module-level ``STATS`` facade over the context-local instance.

    Preserves the historical ``STATS.add/snapshot/reset`` surface (tests and
    benchmarks keep working unchanged) while routing every access to the
    current context's ``BatchStats`` — the process-global default outside
    any ``stats_scope()``."""

    def __init__(self, ctx: obs_trace.ContextLocal):
        self._ctx = ctx

    def add(self, **kw: int) -> None:
        self._ctx.get().add(**kw)

    def snapshot(self) -> Dict[str, int]:
        return self._ctx.get().snapshot()

    def reset(self) -> None:
        self._ctx.get().reset()

    def __getattr__(self, name: str):
        return getattr(self._ctx.get(), name)


_STATS_CTX = obs_trace.ContextLocal(BatchStats)
STATS = _StatsProxy(_STATS_CTX)


def stats_scope(stats: Optional[BatchStats] = None):
    """Install a fresh (or given) ``BatchStats`` for the current context.

    Worker threads spawned via ``obs.trace.wrap_for_thread`` inside the
    scope share the same instance, so a pipelined write's dispatch-ahead
    syncs land in the caller's scope; concurrent scopes never race on one
    global (regression-tested in tests/test_obs.py)."""
    return _STATS_CTX.scope(stats)


def host_sync(tree, label: str = "host_sync"):
    """The engine's single door to host memory: one counted device_get.

    ``label`` names the call site (``codec.stats``, ``codec.payload``,
    ``codec.decode``, ``encode.scalars``, ...) — under tracing it becomes
    the ``host_sync`` event's attribution key, so benchmarks can report
    syncs-per-chunk broken down by originating span."""
    STATS.add(host_syncs=1)
    obs_trace.event(obs_trace.EV_HOST_SYNC, label=label)
    return jax.device_get(tree)


# ------------------------------------------------------------ device kernels --

@jax.jit
def _group_stats_batch(syms: jax.Array):
    """(B, S) uint8 (a same-size bucket) -> (histograms (B,256) int32,
    RLE run counts (B,) int32), all in one launch.

    On CPU the histogram is sort + searchsorted (XLA CPU serializes
    scatter-adds — ~4x slower than the sort formulation at chunk scale); on
    accelerator backends it is the scatter-add formulation (hardware
    atomics).

    The run-break rule matches ``lossless._rle_scan`` exactly (neighbor
    change or forced break every RLE_BREAK symbols), so the Algorithm-2 RLE
    estimate agrees bit-for-bit with the per-group path."""
    S = syms.shape[1]

    if jax.default_backend() == "cpu":
        edges = jnp.arange(256, dtype=jnp.uint8)

        def hist_one(s):
            bounds = jnp.searchsorted(jnp.sort(s), edges, side="right")
            return jnp.diff(jnp.concatenate(
                [jnp.zeros(1, bounds.dtype), bounds])).astype(jnp.int32)
    else:
        def hist_one(s):
            return jnp.zeros((256,), jnp.int32).at[s.astype(jnp.int32)].add(1)

    hists = jax.vmap(hist_one)(syms)
    idx = jnp.arange(S, dtype=jnp.int32)
    prev = jnp.concatenate([syms[:, :1] ^ jnp.uint8(255), syms[:, :-1]],
                           axis=1)
    brk = (syms != prev) | (idx[None, :] % ll.RLE_BREAK == 0)
    nruns = jnp.sum(brk, axis=1, dtype=jnp.int32)
    return hists, nruns


def _group_stats_host(rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host twin of ``_group_stats_batch`` for the CPU backend: one
    ``np.bincount`` over offset-shifted symbols computes every row's 256-bin
    histogram, and the run-break rule matches ``lossless._rle_scan`` exactly
    (neighbor change or forced break every RLE_BREAK symbols).

    On the CPU backend the rows already live in host memory, so syncing the
    raw bytes and histogramming here beats the XLA sort-based histogram
    kernel by ~10x at chunk scale — equality with the device kernel is
    pinned in tests/test_lossless_batch.py."""
    B, S = rows.shape
    offs = (np.arange(B, dtype=np.int64) * 256)[:, None]
    hists = np.bincount((rows + offs).reshape(-1), minlength=B * 256)
    hists = hists.reshape(B, 256).astype(np.int32)
    brk = rows[:, 1:] != rows[:, :-1]
    forced = (np.arange(1, S) % ll.RLE_BREAK) == 0
    nruns = 1 + np.sum(brk | forced[None, :], axis=1, dtype=np.int32)
    return hists, nruns


# The batch pack/scan kernels ARE the reference per-group kernels, vmapped
# over a same-size bucket — bit-identity with the per-group encoders holds
# by construction, row for row.

@jax.jit
def _huffman_pack_batch(syms: jax.Array, lens_tab: jax.Array,
                        codes_tab: jax.Array):
    """(B, S) symbols + per-row codebooks -> vmapped ``_huffman_pack``:
    (words (B, cap), total_bits (B,), chunk_offs (B, ceil(S/CHUNK)))."""
    return jax.vmap(ll._huffman_pack)(syms, lens_tab, codes_tab)


@jax.jit
def _rle_scan_batch(syms: jax.Array):
    """(B, S) symbols -> vmapped ``_rle_scan``: per-row (values, lengths,
    nruns); run slots beyond a row's nruns are trimmed on host."""
    return jax.vmap(ll._rle_scan)(syms)


@functools.partial(jax.jit, static_argnames=("n_syms",))
def _huffman_unpack_batch(words: jax.Array, chunk_offs: jax.Array,
                          lut_sym: jax.Array, lut_len: jax.Array,
                          n_syms: int):
    return jax.vmap(lambda w, c, s, l: ll._huffman_unpack(w, c, s, l, n_syms))(
        words, chunk_offs, lut_sym, lut_len)


@functools.partial(jax.jit, static_argnames=("n",))
def _rle_expand_batch(values: jax.Array, lengths: jax.Array, n: int):
    return jax.vmap(lambda v, l: ll._rle_expand(v, l, n))(values, lengths)


# ---------------------------------------------------------------- utilities --

def _bucket(n: int) -> int:
    """Next power of two >= ``n``: decode batches and payload lengths are
    padded to these buckets, so the jitted decoders compile once per bucket
    instead of once per distinct compressed size."""
    return 1 << max(int(n) - 1, 0).bit_length()


def _pad_rows_host(rows: Sequence[np.ndarray], length: int) -> np.ndarray:
    """Stack 1-D host rows into a zero-padded (len(rows), length) array."""
    out = np.zeros((len(rows), length), dtype=rows[0].dtype)
    for j, r in enumerate(rows):
        out[j, :r.shape[0]] = r
    return out


def batch_jobs(items, key) -> Dict[tuple, List[int]]:
    """Group item indices by ``key(item)`` — the shared shape-batching
    pattern of this engine and ``repro.store.service.reconstruct_many``."""
    jobs: Dict[tuple, List[int]] = {}
    for i, it in enumerate(items):
        jobs.setdefault(key(it), []).append(i)
    return jobs


# ------------------------------------------------------------------- encode --

def _select(size: int, hist: np.ndarray, n_runs: int, cfg: ll.HybridConfig
            ) -> Tuple[str, Optional[Tuple[np.ndarray, np.ndarray]]]:
    """Algorithm-2 inner decision, host side, from device-computed stats.

    Mirrors ``lossless.compress_group`` decision-for-decision so the batched
    engine picks identical methods (and identical Huffman codebooks)."""
    if cfg.force == "huffman":
        return "huffman", ll.build_codebook(hist)
    if cfg.force == "rle":
        return "rle", None
    if cfg.force == "dc" or size <= cfg.size_threshold:
        return "dc", None
    r_h, lengths, codes = ll.estimate_huffman(hist, size)
    if r_h > cfg.cr_threshold:
        # store-raw fallback (mirrors compress_group): the estimator's
        # approximate overhead can pick a codec that still expands — compare
        # EXACT serialized sizes from the device stats before committing
        bits = int(np.sum(hist * lengths.astype(np.int64)))
        if ll.exact_stored_bytes("huffman", size, total_bits=bits) \
                >= ll.exact_stored_bytes("dc", size):
            return "dc", None
        return "huffman", (lengths, codes)
    if ll.estimate_rle(n_runs, size) > cfg.cr_threshold:
        if ll.exact_stored_bytes("rle", size, n_runs=n_runs) \
                >= ll.exact_stored_bytes("dc", size):
            return "dc", None
        return "rle", None
    return "dc", None


def _host_rows() -> bool:
    """True when every device is a host-memory device (CPU backend): rows
    committed to ANY mesh device are plain host bytes, so the encoder can
    gather them with numpy (zero-copy views, no XLA launch) and merge
    buckets ACROSS devices — one wide kernel batch per group size instead
    of one narrow batch per (size, device).  On accelerators a cross-device
    gather would ship payloads over the link, so there buckets stay
    device-keyed and every kernel runs where its rows live."""
    return jax.default_backend() == "cpu"


def _dev_key(a) -> object:
    """Bucket-key component for the device an array is committed to.

    The batched encoder may see rows from chunks pinned to different mesh
    devices in ONE call (``refactor_fused.finish_encode_many`` drains a
    whole in-flight window); stacking across devices is illegal in jax, so
    — exactly like the read side's ``reconstruct.batch_apply_pending`` —
    encode buckets never mix devices: each device's rows batch separately
    and every kernel runs where its rows live.  Host / uncommitted arrays
    key as ``None``."""
    devs = getattr(a, "devices", None)
    if callable(devs):
        try:
            devs = devs()
        except Exception:  # pragma: no cover - tracer/abstract arrays
            return None
        if devs:
            return tuple(sorted(d.id for d in devs))
    return None


def encode_groups(blobs: Sequence[jax.Array],
                  cfg: ll.HybridConfig = ll.HybridConfig()
                  ) -> List[ll.Segment]:
    """Batched Algorithm 2 over a chunk's merged plane groups.

    ``blobs`` are 1-D uint8 arrays (device-resident; host arrays are
    uploaded).  Returns one ``lossless.Segment`` per blob, bit-identical to
    ``[lossless.compress_group(b, cfg) for b in blobs]``, with exactly two
    host syncs for the whole batch.

    Groups are bucketed by size (the groups of one piece all share a size,
    so a chunk has ~#pieces distinct sizes): every bucket stacks exactly —
    no padding work — and runs through one vmapped stats/pack/scan
    invocation per codec; ALL buckets' stats respectively payloads are
    materialized by the same single ``host_sync``."""
    with obs_trace.span("codec.encode"):
        return _encode_groups(blobs, cfg)


def _encode_groups(blobs: Sequence[jax.Array], cfg: ll.HybridConfig
                   ) -> List[ll.Segment]:
    if not blobs:
        return []
    sizes = [int(np.prod(b.shape, dtype=np.int64)) for b in blobs]
    for s in sizes:
        ll._check_group_size(s)  # before any upload/dispatch
    STATS.add(encode_calls=1, groups_encoded=len(blobs))

    host = _host_rows()
    segs: List[Optional[ll.Segment]] = [None] * len(blobs)
    buckets: Dict[tuple, List[int]] = {}
    for i, s in enumerate(sizes):
        if s == 0:
            # empty groups never touch the device; compress_group reproduces
            # the per-group encoder (incl. force modes) exactly
            segs[i] = ll.compress_group(np.zeros(0, np.uint8), cfg)
        else:
            buckets.setdefault((s, None if host else _dev_key(blobs[i])),
                               []).append(i)
    if not buckets:
        return segs

    if host:
        stacked = {
            k: np.stack([np.asarray(blobs[i], dtype=np.uint8).reshape(-1)
                         for i in idxs])
            for k, idxs in buckets.items()}
    else:
        stacked = {
            k: jnp.stack([jnp.asarray(blobs[i], dtype=jnp.uint8).reshape(-1)
                          for i in idxs])
            for k, idxs in buckets.items()}
    _encode_buckets(stacked, buckets, segs, cfg)
    return segs


def encode_groups_stacked(stacks: Sequence[jax.Array],
                          cfg: ll.HybridConfig = ll.HybridConfig()
                          ) -> List[ll.Segment]:
    """``encode_groups`` for blobs that are ALREADY stacked on device.

    ``stacks`` are (B, S) uint8 device arrays — one group blob per row, as
    emitted by the fused write engine (``core.refactor_fused``): the chunk's
    single jitted program produces each same-size blob family as one stacked
    array, so this entry point never re-slices or re-stacks rows.  Same-size
    stacks are merged (one ``jnp.concatenate`` per size) so the kernel-batch
    count stays O(#distinct sizes), exactly as ``encode_groups``.

    Returns one ``lossless.Segment`` per row, flattened row-major across
    ``stacks`` — bit-identical to calling ``encode_groups`` on the individual
    rows, with the engine's same two host syncs."""
    with obs_trace.span("codec.encode"):
        return _encode_groups_stacked(stacks, cfg)


def _encode_groups_stacked(stacks: Sequence[jax.Array], cfg: ll.HybridConfig
                           ) -> List[ll.Segment]:
    sizes: List[int] = []
    for st in stacks:
        s = int(st.shape[1])
        ll._check_group_size(s)  # before any dispatch
        sizes.extend([s] * int(st.shape[0]))
    if not sizes:
        return []
    STATS.add(encode_calls=1, groups_encoded=len(sizes))

    host = _host_rows()
    segs: List[Optional[ll.Segment]] = [None] * len(sizes)
    buckets: Dict[tuple, List[int]] = {}
    parts: Dict[tuple, List] = {}
    base = 0
    for st in stacks:
        b, s = int(st.shape[0]), int(st.shape[1])
        if s == 0:
            for i in range(base, base + b):
                segs[i] = ll.compress_group(np.zeros(0, np.uint8), cfg)
        else:
            # host rows (CPU backend): numpy view, merge across devices —
            # a multi-chunk window spanning the whole mesh becomes ONE wide
            # bucket per size, not n_devices narrow ones (see _host_rows)
            k = (s, None if host else _dev_key(st))
            buckets.setdefault(k, []).extend(range(base, base + b))
            parts.setdefault(k, []).append(
                np.asarray(st, np.uint8) if host else jnp.asarray(st,
                                                                  jnp.uint8))
        base += b
    if not buckets:
        return segs

    cat = np.concatenate if host else jnp.concatenate
    stacked = {k: (p[0] if len(p) == 1 else cat(p))
               for k, p in parts.items()}
    _encode_buckets(stacked, buckets, segs, cfg)
    return segs


def _encode_buckets(stacked: Dict[tuple, jax.Array],
                    buckets: Dict[tuple, List[int]],
                    segs: List[Optional[ll.Segment]],
                    cfg: ll.HybridConfig) -> None:
    """Shared stages 1-3 of the batched encoder: stats (sync #1), host-side
    Algorithm-2 selection, vmapped pack/scan (sync #2).  Fills ``segs`` at
    the indices listed in ``buckets``.  Bucket keys are ``(group_size,
    device)`` — a multi-chunk batch spanning mesh devices runs one kernel
    batch per device (rows never move between devices), while both host
    syncs still cover EVERY bucket in one call each.  On the CPU backend
    the device key is always ``None`` (``_host_rows``): every mesh device
    is host memory, so the whole window merges into one wide numpy-stacked
    bucket per size and the pack/scan kernels run once on the default
    device.

    On the CPU backend stage 1 syncs the stacked rows themselves and runs
    the stats host-side (``_group_stats_host``): the XLA CPU histogram
    kernel loses ~10x to ``np.bincount``, dc payloads then come straight
    from the already-synced host rows, and codec row selection becomes an
    ``np.take`` + one upload instead of a device gather per codec.  On
    accelerators stage 1 stays the device kernel — only tiny stats cross
    the PCIe link.  Both paths keep the engine's two-syncs-per-call
    contract and are byte-identical (``hist_batches`` counts stats batch
    computations on either path).

    Each host stage runs under a span of its own, in order: ``codec.stats``
    (stats and sync #1), ``codec.select``, ``codec.pack`` (the pack/scan
    dispatches), ``codec.payload`` (sync #2) and ``codec.segments``, so a
    profiler trace can say which stage the device waits on.  They nest in
    the entry point's ``codec.encode``, which also holds the stacking
    before them and the release of the call's buffers as it returns."""
    # stage 1: all histograms + run counts, one batch per bucket, ONE sync
    rows_host: Optional[Dict[tuple, np.ndarray]] = None
    with obs_trace.span("codec.stats"):
        if jax.default_backend() == "cpu":
            rows_host = host_sync(stacked, label="codec.stats")
            stats_host = {}
            for k, rows in rows_host.items():
                STATS.add(hist_batches=1)
                stats_host[k] = _group_stats_host(rows)
        else:
            stats_dev = {}
            for k, st in stacked.items():
                STATS.add(hist_batches=1)
                stats_dev[k] = _group_stats_batch(st)
            stats_host = host_sync(stats_dev, label="codec.stats")

    # stage 2: Algorithm-2 selection + codebooks (host)
    methods: Dict[int, str] = {}
    books: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
    with obs_trace.span("codec.select"):
        for k, idxs in buckets.items():
            s = k[0]
            hists, nruns = stats_host[k]
            for j, i in enumerate(idxs):
                m, book = _select(s, hists[j].astype(np.int64),
                                  int(nruns[j]), cfg)
                methods[i] = m
                if book is not None:
                    books[i] = book

    # stage 3: dispatch one pack/scan per (bucket, codec), ONE payload sync
    pend: List[Tuple[str, int, List[int], object]] = []
    with obs_trace.span("codec.pack"):
        for k, idxs in buckets.items():
            s = k[0]
            st = stacked[k]
            pos = {i: j for j, i in enumerate(idxs)}
            h = [i for i in idxs if methods[i] == "huffman"]
            r = [i for i in idxs if methods[i] == "rle"]
            d = [i for i in idxs if methods[i] == "dc"]

            def rows_for(sel_idx: List[int]) -> jax.Array:
                # codec row selection: host take + upload when the rows are
                # already host-side (CPU stats path), device gather otherwise
                sel = [pos[i] for i in sel_idx]
                if rows_host is not None:
                    return jax.device_put(rows_host[k][np.asarray(sel)])
                return st[jnp.asarray(sel, jnp.int32)]

            if h:
                lens_tab = jax.device_put(
                    np.stack([books[i][0] for i in h]).astype(np.uint32))
                codes_tab = jax.device_put(np.stack([books[i][1] for i in h]))
                STATS.add(huffman_pack_batches=1)
                pend.append(("huffman", s, h,
                             _huffman_pack_batch(rows_for(h), lens_tab,
                                                 codes_tab)))
            if r:
                STATS.add(rle_scan_batches=1)
                pend.append(("rle", s, r, _rle_scan_batch(rows_for(r))))
            if d:
                if rows_host is not None:
                    # dc payloads are the raw rows — already on host, no
                    # device round-trip; .copy() detaches from the big stack
                    for i in d:
                        segs[i] = ll.Segment(
                            "dc", s, {"raw": rows_host[k][pos[i]].copy()},
                            {"n_syms": s})
                else:
                    pend.append(("dc", s, d, rows_for(d)))
    with obs_trace.span("codec.payload"):
        mats = host_sync([p[3] for p in pend], label="codec.payload")

    with obs_trace.span("codec.segments"):
        for (kind, s, idxs, _), mat in zip(pend, mats):
            if kind == "huffman":
                words_b, bits_b, offs_b = mat
                for j, i in enumerate(idxs):
                    total_bits = int(bits_b[j])
                    n_words = (total_bits + 31) // 32 + 1
                    segs[i] = ll.Segment(
                        "huffman", s,
                        payload={"words": words_b[j, :n_words].copy(),
                                 "chunk_offs": np.array(offs_b[j],
                                                        dtype=np.uint32),
                                 "lengths": books[i][0]},
                        meta={"n_syms": s, "total_bits": total_bits})
            elif kind == "rle":
                vals_b, lens_b, nruns_b = mat
                for j, i in enumerate(idxs):
                    r = int(nruns_b[j])
                    segs[i] = ll.Segment(
                        "rle", s,
                        payload={"values": vals_b[j, :r].copy(),
                                 "lengths": lens_b[j, :r].astype(np.uint16)},
                        meta={"n_syms": s})
            else:
                for j, i in enumerate(idxs):
                    segs[i] = ll.Segment("dc", s, {"raw": mat[j].copy()},
                                         {"n_syms": s})

        # per-codec byte accounting (obs.metrics): bytes_in is the raw blob
        # size, bytes_out the stored payload — compression_ratio per codec
        # is bytes_in / bytes_out of the same series
        per_codec: Dict[str, List[int]] = {}
        for idxs in buckets.values():
            for i in idxs:
                seg = segs[i]
                acc = per_codec.setdefault(seg.method, [0, 0, 0])
                acc[0] += 1
                acc[1] += seg.n_bytes
                acc[2] += sum(a.nbytes for a in seg.payload.values())
        m = obs_metrics.get()
        for method, (n, bin_, bout) in per_codec.items():
            m.inc("codec.groups", n, codec=method)
            m.inc("codec.bytes_in", bin_, codec=method)
            m.inc("codec.bytes_out", bout, codec=method)


# ------------------------------------------------------------------- decode --

def decode_segments(segs: Sequence[ll.Segment]) -> List[np.ndarray]:
    """Decode many segments, batching same-shape Huffman/RLE decodes.

    Segments sharing (method, n_syms) are decoded through ONE vmapped
    ``_huffman_unpack``/``_rle_expand`` call (Huffman ``words`` are padded to
    the batch max — trailing zeros are exactly what the chunk decoder already
    assumes).  Returns uint8 blobs aligned with ``segs``; bit-identical to
    ``[lossless.decompress_group(s) for s in segs]``."""
    if not segs:
        return []
    STATS.add(decode_calls=1, groups_decoded=len(segs))
    outs: List[Optional[np.ndarray]] = [None] * len(segs)
    pending = []  # (indices, device batch) resolved by one host_sync

    def key(seg: ll.Segment):
        return (seg.method, int(seg.meta.get("n_syms", seg.n_bytes)))

    for (method, n), idxs in batch_jobs(segs, key).items():
        ll._check_group_size(n)  # corrupt metadata must not drive allocation
        if n == 0:
            for i in idxs:
                outs[i] = np.zeros(0, np.uint8)
            continue
        if method == "dc":
            for i in idxs:
                outs[i] = segs[i].payload["raw"]
            continue
        if method == "huffman":
            luts = [ll._build_decode_lut(
                segs[i].payload["lengths"],
                ll._codes_from_lengths(segs[i].payload["lengths"]))
                for i in idxs]
            # rows repeat the first row to the batch bucket; their outputs
            # are discarded
            rows = idxs + [idxs[0]] * (_bucket(len(idxs)) - len(idxs))
            words = _pad_rows_host(
                [segs[i].payload["words"] for i in rows],
                _bucket(max(segs[i].payload["words"].shape[0]
                            for i in idxs)))
            chunk_offs = np.stack([segs[i].payload["chunk_offs"]
                                   for i in rows])
            luts += [luts[0]] * (len(rows) - len(idxs))
            lut_sym = np.stack([l[0] for l in luts])
            lut_len = np.stack([l[1] for l in luts])
            STATS.add(huffman_unpack_batches=1)
            pending.append((idxs, _huffman_unpack_batch(
                *jax.device_put((words, chunk_offs, lut_sym, lut_len)), n)))
        elif method == "rle":
            rows = idxs + [idxs[0]] * (_bucket(len(idxs)) - len(idxs))
            rmax = _bucket(max(segs[i].payload["values"].shape[0]
                               for i in idxs))
            # zero-length padding runs never cover a symbol
            values = _pad_rows_host([segs[i].payload["values"]
                                     for i in rows], rmax)
            lengths = _pad_rows_host([segs[i].payload["lengths"].astype(
                np.int32) for i in rows], rmax)
            STATS.add(rle_expand_batches=1)
            pending.append((idxs, _rle_expand_batch(
                *jax.device_put((values, lengths)), n)))
        else:
            raise ValueError(f"cannot decode method {method!r}")

    if pending:
        mats = host_sync([p[1] for p in pending], label="codec.decode")
        for (idxs, _), mat in zip(pending, mats):
            for j, i in enumerate(idxs):
                outs[i] = np.asarray(mat[j], dtype=np.uint8)
    return outs
