"""Roofline analysis of the fused write program (feeds the autotuner).

Per (shape x design) cell, lower the fused one-dispatch encode program
(``core.refactor_fused.fused_encode_plan``), extract per-op FLOPs / HBM
bytes / collective wire bytes from the optimized HLO
(``launch.hlo_analysis``), and score the terms against hardware peaks::

  compute term    = flops / peak_flops
  memory term     = hbm_bytes / hbm_bw
  collective term = wire_bytes / link_bw

The peaks come from ``repro.tune.cost.PEAKS``, keyed by ``device_kind``
(single source of truth: this artifact and the tuner's cost model can never
disagree; the v5e row is 197 TFLOP/s / 819 GB/s / 200 GB/s link).  Each cell also
runs one measured probe write, so the artifact records the model's
calibration quality (``model_fraction`` = calibrated prediction / measured)
— the honesty check behind ``docs/autotune.md``'s cost-model section.

Emits CSV rows and writes ``out/benchmarks/roofline.json`` (CI artifact,
budget-gated by ``benchmarks/check_regressions.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from benchmarks.common import row, write_json
from repro.tune import cost as tc
from repro.tune import search as ts
from repro.tune.config import DEFAULT_CONFIG

SHAPES = [(1 << 14,), (1 << 16,)]
LEVELS = 3


def roofline_cells(shapes=SHAPES, levels: int = LEVELS) -> List[Dict]:
    """One cell per (shape x bitplane design): HLO-derived roofline terms
    plus a measured probe of the same program."""
    peaks = tc.device_peaks()
    cells: List[Dict] = []
    for shape in shapes:
        model = tc.CostModel(shape, levels)
        x = ts._probe_chunk(shape, "float32")
        # calibrate the model scale on the default design's measured probe;
        # the other designs then test how well the model transfers
        default = DEFAULT_CONFIG
        t_default = ts._measure_write(x, default, levels)
        model.calibrate(default, t_default)
        for design in ts.DESIGNS:
            cfg = default.replace(design=design)
            c = model.cost(cfg)
            t_c = c.flops / peaks.flops
            t_m = c.hbm_bytes / peaks.hbm_bw
            t_x = c.wire_bytes / peaks.link_bw
            dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))
            measured = (t_default if design == default.design
                        else ts._measure_write(x, cfg, levels))
            predicted = model.score(cfg)
            cells.append({
                "shape": list(shape), "levels": levels, "design": design,
                "flops": c.flops, "hbm_bytes": c.hbm_bytes,
                "wire_bytes": c.wire_bytes,
                "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
                "dominant": dom[1], "bound_s": dom[0],
                "measured_s": measured, "predicted_s": predicted,
                "model_fraction": predicted / max(measured, 1e-12),
                "model_scale": model.scale,
            })
    return cells


def run() -> List[str]:
    import jax

    cells = roofline_cells()
    kind = jax.devices()[0].device_kind
    peaks = tc.device_peaks(kind)
    # calibrated section: nominal peaks / fitted scale = the rates this
    # machine actually sustained on the probes.  ``tune.cost.device_peaks``
    # reads it back on later runs, so the tuner's cost model starts from the
    # machine, not the spec sheet.  (Fixed point: effective = cost/measured
    # regardless of which peaks scored the probes, so re-running against an
    # existing artifact does not drift.)  Median scale across cells resists
    # one noisy probe.
    scales = sorted(c["model_scale"] for c in cells)
    scale = scales[len(scales) // 2] if scales else 1.0
    result = {
        "peaks": {"flops": peaks.flops, "hbm_bw": peaks.hbm_bw,
                  "link_bw": peaks.link_bw},
        "nominal_v5e": dataclasses.asdict(tc.PEAKS[tc.V5E]),
        "calibrated": {
            "device_kind": kind, "scale": scale,
            "flops": peaks.flops / scale, "hbm_bw": peaks.hbm_bw / scale,
            "link_bw": peaks.link_bw / scale,
        },
        "cells": cells,
        # CI acceptance: every cell's HLO was analyzed.  The memory term is
        # the load-bearing one — the encode chain is bitwise ops, so HLO
        # FLOP counts are legitimately zero on some cells.
        "all_cells_analyzed": all(c["hbm_bytes"] > 0 for c in cells),
    }
    write_json("roofline", result)
    lines = []
    for c in cells:
        n = c["shape"][0]
        lines.append(row(
            f"roofline_fused_{n}_{c['design']}", c["measured_s"],
            f"dom={c['dominant']};bound_us={c['bound_s'] * 1e6:.1f};"
            f"model_frac={c['model_fraction']:.2f}"))
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
