"""Autotuner: cost-model-guided search with measured probe runs.

``tune(shape, ...)`` finds the write-path config for one problem class
(shape, dtype, levels, backend, n_devices):

  1. consult the on-disk cache (``repro.tune.cache``) — a warm cache returns
     the winner with NO search, NO probes, NO compilation (the CI autotune
     smoke job asserts exactly this on its second run);
  2. on a miss, enumerate the candidate space (bitplane design x lossless
     group size x kernel tiling on accelerator backends), score every
     candidate's fused program with the HLO roofline model
     (``repro.tune.cost``) — one lowering per distinct program, no
     execution;
  3. run a handful of measured probe writes (``probes`` best-scored
     candidates, the hard-coded default ALWAYS included) through the real
     ``refactor_array`` fused path, calibrate the model's scale from the
     default's probe, then probe-search the pure-scheduling knobs the
     program's HLO cannot see: ``dispatch_ahead`` through the real chunked
     pipelined WRITE (async per-device drain windows) and the read-side
     ``depth`` through the real chunked pipelined READ of the winner's own
     blobs (overlap look-ahead + per-device drain window);
  4. cache the measured winner keyed by backend fingerprint.

A candidate that fails to lower, compile or run is not eligible, but it is
never dropped in silence: every failure is logged, counted
(``STATS.candidates_failed``), returned in ``TuneResult.failures`` with its
stage and error, and recorded in the cache entry's metadata.

The measured-best-of-probes rule keeps the tuner safe: the default config is
always a probe, so a tuned config can only tie or beat it on the probe
workload — never regress it on the machine that tuned.

Quality knobs (``mag_bits``) are never searched: tuning changes how bytes
are produced, not which bytes the user asked to keep.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.tune import cache as tcache
from repro.tune.config import DEFAULT_CONFIG, RefactorConfig
from repro.tune.cost import CostModel

DESIGNS = ("register_block", "locality", "shuffle")
GROUP_SIZES = (2, 4, 8)
TILES = (4, 8, 16)
DISPATCH_AHEAD = (1, 2, 4)
DEPTHS = (1, 2, 4)  # read-side overlap look-ahead / drain window

logger = logging.getLogger("repro.tune")


@dataclasses.dataclass
class SearchStats:
    """Process-global tuner counters (thread-safe).  ``searches`` counts
    actual cost-model searches — a cache hit must NOT increment it."""
    searches: int = 0
    candidates_scored: int = 0
    candidates_failed: int = 0
    probes_run: int = 0

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


STATS = SearchStats()


@dataclasses.dataclass(frozen=True)
class CandidateFailure:
    """One candidate the search could not use, and why."""
    config: RefactorConfig
    stage: str      # 'score' | 'probe' | 'dispatch_ahead' | 'depth'
    error: str      # ``repr`` of the exception

    def to_json(self) -> Dict[str, Any]:
        return {"config": self.config.to_json(), "stage": self.stage,
                "error": self.error}


def _attempt(failures: List[CandidateFailure], cfg: RefactorConfig,
             stage: str, fn: Callable[[], Any]) -> Optional[Any]:
    """Run ``fn``; on failure record a ``CandidateFailure`` (logged and
    counted) and return ``None``."""
    try:
        return fn()
    except Exception as exc:  # noqa: BLE001 - recorded, logged, counted
        failures.append(CandidateFailure(cfg, stage, repr(exc)))
        STATS.add(candidates_failed=1)
        logger.warning("tune: %s of %s failed: %r", stage, cfg, exc)
        return None


@dataclasses.dataclass(frozen=True)
class TuneResult:
    config: RefactorConfig
    cache_hit: bool
    fingerprint: str
    problem: str
    # (config, model_seconds) for every scored candidate; empty on cache hit
    scores: Tuple[Tuple[RefactorConfig, float], ...] = ()
    # (config, measured_seconds) for every probe; empty on cache hit
    probes: Tuple[Tuple[RefactorConfig, float], ...] = ()
    # every candidate that failed to lower, compile or run; empty on hit
    failures: Tuple[CandidateFailure, ...] = ()
    tune_s: float = 0.0


def candidate_space(base: RefactorConfig, backend_resolved: str
                    ) -> List[RefactorConfig]:
    """Program-level candidates: design x group_size (+ kernel tiling on
    Pallas backends — the jnp reference path ignores tiles/unroll, so
    searching them on CPU would only burn compile time)."""
    out: List[RefactorConfig] = []
    tiles = TILES if backend_resolved.startswith("pallas") else (
        base.tiles_per_block,)
    unrolls = (("naive", "butterfly")
               if backend_resolved.startswith("pallas") else (base.unroll,))
    for design in DESIGNS:
        for gs in GROUP_SIZES:
            for t in tiles:
                for u in unrolls:
                    out.append(base.replace(design=design, group_size=gs,
                                            tiles_per_block=t, unroll=u))
    return out


def _probe_chunk(shape: Sequence[int], dtype: str) -> np.ndarray:
    """Deterministic smooth-plus-noise probe data: representative of the
    scientific fields the refactorer targets (compressible but not trivial),
    and identical across runs so cached winners are reproducible."""
    rng = np.random.default_rng(20240817)
    n = int(np.prod(shape, dtype=np.int64)) if len(shape) else 1
    t = np.linspace(0.0, 6.0, n, dtype=np.float64)
    x = np.sin(t) + 0.05 * rng.standard_normal(n)
    return x.astype(dtype).reshape(shape)


def _measure_write(x: np.ndarray, cfg: RefactorConfig,
                   levels: Optional[int], repeats: int = 2) -> float:
    """Measured seconds for one chunk through the fused write path with
    ``cfg`` (compile excluded: one warmup, then best-of-``repeats``)."""
    from repro.core import refactor as rf

    def once() -> float:
        t0 = time.perf_counter()
        r = rf.refactor_array(x, levels=levels, config=cfg, fused=True)
        # serialization is part of the write budget the tuner optimizes
        for _ in rf.iter_segments(r):
            pass
        return time.perf_counter() - t0

    once()  # warmup: trace + compile the candidate's program
    best = min(once() for _ in range(max(repeats, 1)))
    STATS.add(probes_run=1)
    return best


def _measure_pipeline_write(x: np.ndarray, cfg: RefactorConfig,
                            levels: Optional[int],
                            repeats: int = 2) -> float:
    """Measured seconds for a multi-chunk PIPELINED write with ``cfg`` —
    the probe that actually sees ``dispatch_ahead`` (per-device in-flight
    window + drain batch size), which a single-chunk program probe cannot.
    Compile excluded: one warmup, then best-of-``repeats``."""
    from repro.core import pipeline as pl

    def once() -> float:
        t0 = time.perf_counter()
        pipe = pl.ChunkedRefactorPipeline(levels=levels, pipelined=True,
                                          config=cfg, use_tune_cache=False)
        pipe.refactor(x)
        return time.perf_counter() - t0

    once()
    best = min(once() for _ in range(max(repeats, 1)))
    STATS.add(probes_run=1)
    return best


def _measure_pipeline_read(blobs: Sequence[bytes], cfg: RefactorConfig,
                           tol: float, repeats: int = 2) -> float:
    """Measured seconds for a multi-chunk PIPELINED read with ``cfg`` — the
    probe that actually sees ``depth`` (the overlap feeder's look-ahead AND
    the per-device drain window), which no single-chunk program probe can.
    A fresh pipeline per run: incremental readers are stateful, so reusing
    one would time the engine cache, not the decode.  Compile excluded: one
    warmup, then best-of-``repeats``."""
    from repro.core import pipeline as pl

    def once() -> float:
        t0 = time.perf_counter()
        pipe = pl.ChunkedReconstructPipeline(pipelined=True, config=cfg)
        pipe.reconstruct(blobs, tol)
        return time.perf_counter() - t0

    once()
    best = min(once() for _ in range(max(repeats, 1)))
    STATS.add(probes_run=1)
    return best


def _probe_blobs(best: RefactorConfig, n: int, levels: Optional[int],
                 dtype: str, n_chunks: int
                 ) -> Tuple[np.ndarray, List[bytes]]:
    """Refactor the read probe's data once with the winning config: the
    serialized chunk blobs every depth candidate reconstructs from."""
    from repro.core import pipeline as pl

    x = _probe_chunk((n_chunks * n,), dtype)
    blobs = pl.ChunkedRefactorPipeline(
        levels=levels, pipelined=True, config=best.replace(chunk_elems=n),
        use_tune_cache=False).refactor(x)
    return x, blobs


def _tune_read_depth(best: RefactorConfig, shape: Sequence[int],
                     dtype: str, levels: Optional[int],
                     failures: List[CandidateFailure],
                     n_chunks: int = 6
                     ) -> Tuple[RefactorConfig,
                                List[Tuple[RefactorConfig, float]]]:
    """Probe-search the read-side overlap ``depth`` through the real
    pipelined read path.

    Like ``dispatch_ahead`` on the write side, ``depth`` is pure scheduling
    (the reconstruction is bit-identical at any depth), so the HLO model is
    blind to it: refactor the probe data ONCE with the winning config, then
    reconstruct the same blobs at every candidate depth and keep the fastest
    measured one.  The adopted depth is recorded in the winner (and thus in
    the manifest ``plan``), so store readers replay it via
    ``VariableEntry.plan`` exactly as they replay the kernel tiling.
    Returns (winner, [(cfg, seconds) per depth probed])."""
    n = int(np.prod(shape, dtype=np.int64)) if len(shape) else 1
    fallback = (best if best.depth in DEPTHS
                else best.replace(depth=DEPTHS[1]))
    if n == 0:
        return fallback, []
    if levels is None:
        from repro.core import decompose as dc
        levels = dc.num_levels((n,))
    probe = _attempt(failures, best, "depth",
                     lambda: _probe_blobs(best, n, levels, dtype, n_chunks))
    if probe is None:
        return fallback, []
    x, blobs = probe
    # mid-curve tolerance: deep enough that every chunk fetches several
    # plane groups (the staged-drain schedule depth actually controls)
    tol = 1e-3 * float(np.ptp(x)) if np.ptp(x) > 0 else 1e-3
    timed: List[Tuple[RefactorConfig, float]] = []
    for dp in DEPTHS:
        cfg = best.replace(depth=dp, chunk_elems=n)
        t = _attempt(failures, cfg, "depth",
                     lambda: _measure_pipeline_read(blobs, cfg, tol))
        if t is not None:
            timed.append((cfg, t))
    if not timed:
        return fallback, []
    dp = min(timed, key=lambda cs: cs[1])[0].depth
    # probe chunking stays out of the winner: only the depth is adopted
    return best.replace(depth=dp), timed


def _tune_dispatch_ahead(best_prog: RefactorConfig, shape: Sequence[int],
                         dtype: str, levels: Optional[int],
                         failures: List[CandidateFailure],
                         n_chunks: int = 6
                         ) -> Tuple[RefactorConfig,
                                    List[Tuple[RefactorConfig, float]]]:
    """Probe-search the per-device in-flight window depth.

    ``dispatch_ahead`` is pure scheduling — the serialized bytes are
    identical at any depth — so the HLO cost model is blind to it and
    measurement is the only honest signal: run the winning program config
    through the real chunked pipeline (``n_chunks`` chunks of the probe
    shape, async window drains included) at every candidate depth and keep
    the fastest.  Returns (winner, [(cfg, seconds) per depth probed])."""
    n = int(np.prod(shape, dtype=np.int64)) if len(shape) else 1
    fallback = (best_prog if best_prog.dispatch_ahead in DISPATCH_AHEAD
                else best_prog.replace(dispatch_ahead=DISPATCH_AHEAD[1]))
    if n == 0:
        return fallback, []
    if levels is None:
        from repro.core import decompose as dc
        levels = dc.num_levels((n,))
    x = _probe_chunk((n_chunks * n,), dtype)
    timed: List[Tuple[RefactorConfig, float]] = []
    for da in DISPATCH_AHEAD:
        cfg = best_prog.replace(dispatch_ahead=da, chunk_elems=n)
        t = _attempt(failures, cfg, "dispatch_ahead",
                     lambda: _measure_pipeline_write(x, cfg, levels))
        if t is not None:
            timed.append((cfg, t))
    if not timed:
        return fallback, []
    da = min(timed, key=lambda cs: cs[1])[0].dispatch_ahead
    # probe chunking stays out of the winner: only the depth is adopted
    return best_prog.replace(dispatch_ahead=da), timed


def tune(shape: Sequence[int], dtype: str = "float32",
         levels: Optional[int] = None, backend: str = "auto",
         n_devices: int = 1, probes: int = 3,
         base: Optional[RefactorConfig] = None,
         cache_root: Optional[os.PathLike] = None,
         force: bool = False) -> TuneResult:
    """Find (or recall) the winning ``RefactorConfig`` for a problem class.

    Returns a ``TuneResult``; ``result.config`` is what ``DatasetWriter``
    records in the manifest.  ``force=True`` ignores a cached winner (but
    still stores the fresh one)."""
    from repro.kernels import ops as kops

    t0 = time.perf_counter()
    shape = tuple(int(d) for d in shape)
    fp = tcache.backend_fingerprint(backend, n_devices)
    problem = tcache.problem_key(shape, dtype, levels)
    if not force:
        hit = tcache.load(fp, problem, root=cache_root)
        if hit is not None:
            return TuneResult(config=hit, cache_hit=True, fingerprint=fp,
                              problem=problem,
                              tune_s=time.perf_counter() - t0)

    STATS.add(searches=1)
    base = (base if base is not None else DEFAULT_CONFIG).replace(
        backend=backend, mesh_devices=(n_devices if n_devices > 1 else None))
    cands = candidate_space(base, kops._resolve(backend))

    model = CostModel(shape, levels, dtype)
    failures: List[CandidateFailure] = []
    scored: List[Tuple[RefactorConfig, float]] = []
    for c in cands:
        # a candidate that fails to lower/compile is not eligible
        t = _attempt(failures, c, "score", lambda: model.score(c))
        if t is not None:
            scored.append((c, t))
    STATS.add(candidates_scored=len(scored))
    scored.sort(key=lambda cs: cs[1])

    # measured probes: the model's top-(probes) programs, default included —
    # the winner is the best MEASURED probe, so tuned >= default by
    # construction on this machine
    probe_set: List[RefactorConfig] = [base]
    for c, _ in scored:
        if len(probe_set) >= max(probes, 1) + 1:
            break
        if c not in probe_set:
            probe_set.append(c)

    x = _probe_chunk(shape, dtype)
    measured: List[Tuple[RefactorConfig, float]] = []
    for c in probe_set:
        t = _attempt(failures, c, "probe",
                     lambda: _measure_write(x, c, levels))
        if t is not None:
            measured.append((c, t))
    if not measured:            # pathological: keep the default, cache it
        measured = [(base, float("inf"))]
    model.calibrate(base, measured[0][1])
    best_prog = min(measured, key=lambda cs: cs[1])[0]

    # pipeline knob branch: dispatch_ahead changes host/device overlap and
    # the async drain batch size, not the program — the HLO model cannot
    # rank it, so probe it through the real chunked pipeline and keep the
    # fastest measured window depth.  If every program probe failed the
    # machine cannot be trusted to probe more: keep the default window.
    if np.isfinite(min(s for _, s in measured)):
        best, da_probes = _tune_dispatch_ahead(best_prog, shape, dtype,
                                               levels, failures)
        # read-side scheduling twin: probe `depth` through the real
        # pipelined read of the winner's own blobs (bit-identical at any
        # depth — only wall clock distinguishes the candidates)
        best, depth_probes = _tune_read_depth(best, shape, dtype, levels,
                                              failures)
    else:
        best = (best_prog if best_prog.dispatch_ahead in DISPATCH_AHEAD
                else best_prog.replace(dispatch_ahead=DISPATCH_AHEAD[1]))
        da_probes = []
        depth_probes = []

    tcache.store(
        fp, problem, best,
        meta={"scores": [[c.to_json(), s] for c, s in scored[:8]],
              "probes": [[c.to_json(), s] for c, s in measured],
              "dispatch_probes": [[c.dispatch_ahead, s]
                                  for c, s in da_probes],
              "depth_probes": [[c.depth, s] for c, s in depth_probes],
              "model_scale": model.scale,
              "n_candidates": len(cands),
              "failures": [f.to_json() for f in failures]},
        root=cache_root)
    return TuneResult(config=best, cache_hit=False, fingerprint=fp,
                      problem=problem, scores=tuple(scored),
                      probes=tuple(measured), failures=tuple(failures),
                      tune_s=time.perf_counter() - t0)
