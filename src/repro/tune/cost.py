"""Roofline cost model over the fused encode program's optimized HLO.

Follows the byteprofile-analysis pattern named in ROADMAP.md: instead of
exhaustively running every candidate config, lower the candidate's fused
one-dispatch program (``core.refactor_fused.fused_encode_plan``), extract
per-op FLOPs / HBM bytes / collective wire bytes from the optimized HLO with
the previously orphaned ``launch.hlo_analysis``, and score it against
hardware peaks::

    t_model = max(flops / peak_flops, bytes / hbm_bw) + wire / link_bw

Absolute peaks come from one table keyed by jax ``device_kind``
(``PEAKS``); ``benchmarks/roofline.py`` reads the same table, so the
calibration artifact and the cost model can never disagree.  A device that
is not in the table is an error, never a silent default.  Absolute accuracy
does not matter for the tuner: the model only *ranks* candidates, and the
few measured probe runs (``repro.tune.search``) both calibrate the scale and
decide the winner.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Dict, Optional, Sequence, Tuple

from repro.tune.config import RefactorConfig


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float     # FLOP/s
    hbm_bw: float    # HBM bytes/s
    link_bw: float   # chip-to-chip bytes/s


# jax ``device_kind`` of a TPU v5e chip
V5E = "TPU v5 lite"

PEAKS: Dict[str, Peaks] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
    # 819 GB/s, 1,600 Gbit/s (200 GB/s) inter-chip interconnect
    V5E: Peaks(197e12, 819e9, 200e9),
    # the host backend the CPU test suite runs on: order-of-magnitude rates
    # (no published source), kept only so the tuner runs in those tests
    "cpu": Peaks(1e11, 3e10, 1e10),
}


def nominal_peaks(device_kind: str) -> Peaks:
    """The ``PEAKS`` row of ``device_kind``; raises for an unknown device."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device kind {device_kind!r}: add its published "
            f"figures, with their source, to repro.tune.cost.PEAKS") from None


# where a machine's measured roofline calibration lives; overridable so CI
# jobs and tests can point the tuner at a specific artifact
ROOFLINE_ARTIFACT_ENV = "REPRO_ROOFLINE_JSON"
DEFAULT_ROOFLINE_ARTIFACT = os.path.join("out", "benchmarks",
                                         "roofline.json")


def calibrated_peaks(device_kind: str,
                     path: Optional[str] = None) -> Optional[Peaks]:
    """This machine's measured effective peaks from its roofline artifact.

    ``benchmarks/roofline.py`` probes the fused program and publishes a
    ``calibrated`` section — nominal peaks divided by the fitted model
    scale, i.e. the peak rates at which THIS machine actually moved the
    program's bytes/flops.  When the artifact exists and matches the
    device kind, the cost model starts from those instead of the nominal
    table, so candidate rankings reflect the machine.

    Returns ``None`` (nominal fallback) when the artifact is absent,
    unreadable, for another device kind, or carries non-finite/zero rates —
    a corrupt artifact must never poison the tuner."""
    path = path if path is not None else os.environ.get(
        ROOFLINE_ARTIFACT_ENV, DEFAULT_ROOFLINE_ARTIFACT)
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        cal = doc["calibrated"]
        if cal.get("device_kind") != device_kind:
            return None
        peaks = Peaks(float(cal["flops"]), float(cal["hbm_bw"]),
                      float(cal["link_bw"]))
    except (OSError, ValueError, KeyError, TypeError):
        return None
    vals = (peaks.flops, peaks.hbm_bw, peaks.link_bw)
    if not all(math.isfinite(v) and v > 0 for v in vals):
        return None
    return peaks


def device_peaks(device_kind: Optional[str] = None) -> Peaks:
    """Peaks for scoring on ``device_kind`` (default: this process's first
    device): the machine's calibrated roofline artifact when one is present
    (``calibrated_peaks``), else the nominal ``PEAKS`` row.  Raises for a
    device kind the table does not know."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    nominal = nominal_peaks(device_kind)
    cal = calibrated_peaks(device_kind)
    return cal if cal is not None else nominal


def fused_program_hlo(shape: Sequence[int], levels: Optional[int],
                      config: RefactorConfig, dtype: str = "float32") -> str:
    """Optimized HLO text of the candidate's fused one-dispatch program.

    Lowers against a ShapeDtypeStruct — no probe data, no execution — and
    compiles, so the text reflects what XLA will actually run (fusion
    boundaries included, which is what ``HloAnalysis`` counts)."""
    import jax
    import jax.numpy as jnp

    from repro.core import decompose as dc
    from repro.core import refactor as rf
    from repro.core import refactor_fused as rff

    shape = tuple(int(d) for d in shape)
    if levels is None:
        levels = dc.num_levels(shape)
    mag_bits = config.resolved_mag_bits()
    group_planes = tuple(rf._group_plane_split(mag_bits, config.group_size))
    plan = rff.fused_encode_plan(shape, levels, config.design, mag_bits,
                                 group_planes, config.backend,
                                 config.tiles_per_block, config.unroll)
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    return plan.run.lower(x).compile().as_text()


@dataclasses.dataclass(frozen=True)
class ProgramCost:
    """HLO-derived resource use of one candidate's fused program."""
    flops: float
    hbm_bytes: float
    wire_bytes: float

    def seconds(self, peaks: Peaks, scale: float = 1.0) -> float:
        """Roofline time estimate: bound by the slower of compute/memory,
        plus the collective term; ``scale`` is the probe calibration."""
        t = max(self.flops / peaks.flops, self.hbm_bytes / peaks.hbm_bw)
        return scale * (t + self.wire_bytes / peaks.link_bw)


def analyze_config(shape: Sequence[int], levels: Optional[int],
                   config: RefactorConfig,
                   dtype: str = "float32") -> ProgramCost:
    """FLOPs / bytes / wire of one candidate config's fused program."""
    from repro.launch.hlo_analysis import HloAnalysis

    ana = HloAnalysis(fused_program_hlo(shape, levels, config, dtype))
    return ProgramCost(flops=float(ana.flops), hbm_bytes=float(ana.bytes),
                       wire_bytes=float(sum(c.wire_bytes
                                            for c in ana.collectives)))


class CostModel:
    """Scores candidate configs; calibrates its scale from measured probes.

    ``score`` caches per program key — configs differing only in pipeline
    knobs (``dispatch_ahead``, thresholds) share one lowering."""

    def __init__(self, shape: Sequence[int], levels: Optional[int] = None,
                 dtype: str = "float32", peaks: Optional[Peaks] = None):
        self.shape = tuple(int(d) for d in shape)
        self.levels = levels
        self.dtype = dtype
        self.peaks = peaks if peaks is not None else device_peaks()
        self.scale = 1.0
        self._cache: Dict[Tuple, ProgramCost] = {}

    def cost(self, config: RefactorConfig) -> ProgramCost:
        key = config.program_key()
        if key not in self._cache:
            self._cache[key] = analyze_config(self.shape, self.levels,
                                              config, self.dtype)
        return self._cache[key]

    def score(self, config: RefactorConfig) -> float:
        """Predicted seconds for one chunk through the fused program."""
        return self.cost(config).seconds(self.peaks, self.scale)

    def calibrate(self, config: RefactorConfig, measured_s: float) -> float:
        """Fit ``scale`` so the model's prediction for ``config`` matches a
        measured probe; returns the new scale.  One probe is enough to move
        predictions from nominal-peak units into this machine's units."""
        predicted = self.cost(config).seconds(self.peaks, 1.0)
        if predicted > 0 and measured_s > 0:
            self.scale = measured_s / predicted
        return self.scale
