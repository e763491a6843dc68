"""Legacy roofline over the LLM dry-run artifacts (EXPERIMENTS.md tables).

Moved out of ``benchmarks/roofline.py`` when that module became the HP-MDR
fused-write roofline (peaks now live in ``repro.tune.cost``).  This module
keeps the (arch x shape x mesh) cell analysis that
``benchmarks/make_experiments.py`` renders from ``out/dryrun/*.json``.

Per cell:

  compute term    = flops_per_device / PEAK_FLOPS
  memory term     = hbm_bytes_per_device / HBM_BW
  collective term = wire_bytes_per_device / LINK_BW

MODEL_FLOPS (per device):
  train:   6 * N_active * tokens / chips      (fwd+bwd weight flops)
  prefill: 2 * N_active * tokens / chips
  decode:  2 * N_active * batch  / chips  + cache-read attention flops

The ratio MODEL_FLOPS / HLO_FLOPs exposes remat recompute and masked-block
attention waste.  The dominant term is the roofline bottleneck; the perf
loop (EXPERIMENTS.md §Perf) iterates on whichever dominates.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

from repro.tune.cost import PEAKS, V5E

PEAK_FLOPS, HBM_BW, LINK_BW = (PEAKS[V5E].flops, PEAKS[V5E].hbm_bw,
                                PEAKS[V5E].link_bw)

OUT_DIR = Path(__file__).resolve().parents[1] / "out" / "dryrun"

ARCHS = ["rwkv6-3b", "deepseek-67b", "h2o-danube-3-4b", "command-r-plus-104b",
         "qwen2-7b", "hubert-xlarge", "jamba-v0.1-52b", "deepseek-v2-236b",
         "deepseek-v3-671b", "llama-3.2-vision-90b"]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def model_flops_per_device(arch: str, shape_name: str, chips: int) -> float:
    from repro.configs.base import SHAPES as SH, get_config
    from repro.models.model import count_params
    cfg = get_config(arch)
    shape = SH[shape_name]
    n_act = count_params(cfg, active_only=True)
    if shape.kind == "train":
        return 6.0 * n_act * shape.global_batch * shape.seq_len / chips
    if shape.kind == "prefill":
        return 2.0 * n_act * shape.global_batch * shape.seq_len / chips
    # decode: weight flops for B tokens + attention cache dot-products
    flops = 2.0 * n_act * shape.global_batch
    if not (cfg.ssm and cfg.ssm.kind == "rwkv6"):
        L = min(cfg.attn_window or shape.seq_len, shape.seq_len)
        if cfg.mla:
            dh_k = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim
            dh_v = cfg.mla.kv_lora_rank
            n_attn_layers = cfg.n_layers
            flops += (2.0 * cfg.n_heads * (dh_k + dh_v) * L
                      * shape.global_batch * n_attn_layers)
        else:
            n_attn = cfg.n_layers
            if cfg.ssm and cfg.ssm.attn_period:
                n_attn = cfg.n_layers // cfg.ssm.attn_period
            flops += (2.0 * cfg.n_heads * 2 * cfg.head_dim * L
                      * shape.global_batch * n_attn)
    return flops / chips


def model_bytes_per_device(arch: str, shape_name: str, chips: int,
                           policy: Dict) -> float:
    """Minimum achievable HBM traffic per device per step (the memory-roofline
    numerator): every resident weight byte read once per (micro)batch pass,
    plus optimizer traffic for train, plus one cache read for decode."""
    from repro.configs.base import SHAPES as SH, get_config
    from repro.models.model import count_params
    cfg = get_config(arch)
    shape = SH[shape_name]
    n = count_params(cfg)
    pbytes = n * (2 if cfg.param_dtype == "bfloat16" else 4) / chips
    if shape.kind == "train":
        n_micro = max(policy.get("n_micro", 1), 1)
        opt_b = 2 if policy.get("opt_state_dtype") == "bfloat16" else 4
        # fwd + bwd weight reads per microbatch (+1 recompute with remat),
        # grad write/read + adam m,v read+write + param update
        return pbytes * (3 * n_micro + 2) + (n / chips) * opt_b * 4
    if shape.kind == "prefill":
        act = shape.global_batch * shape.seq_len * cfg.d_model * 2 / chips
        return pbytes + act * cfg.n_layers * 2
    # decode: weights once + one full cache read
    cache = 0.0
    if not (cfg.ssm and cfg.ssm.kind == "rwkv6"):
        L = policy.get("cache_len", shape.seq_len)
        n_attn = cfg.n_layers
        if cfg.ssm and cfg.ssm.attn_period:
            n_attn = cfg.n_layers // cfg.ssm.attn_period
        if cfg.mla:
            per_tok = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim
        else:
            per_tok = 2 * cfg.n_kv_heads * cfg.head_dim
        cache = shape.global_batch * L * per_tok * 2 * n_attn / chips
    state = 0.0
    if cfg.ssm:
        d = cfg.d_model
        if cfg.ssm.kind == "rwkv6":
            state = cfg.n_layers * shape.global_batch * (d // 64) * 64 * 64 * 4 / chips
        else:
            n_mamba = cfg.n_layers - (cfg.n_layers // max(cfg.ssm.attn_period, 1)
                                      if cfg.ssm.attn_period else 0)
            state = n_mamba * shape.global_batch * cfg.ssm.expand * d \
                * cfg.ssm.d_state * 4 / chips
    return pbytes + cache + state * 2


def load_cells(mesh: str = "single") -> List[Dict]:
    rows = []
    for a in ARCHS:
        for s in SHAPES:
            p = OUT_DIR / f"{a}__{s}__{mesh}.json"
            if not p.exists():
                continue
            r = json.loads(p.read_text())
            if r["status"] != "ok":
                continue
            chips = 512 if mesh == "multi" else 256
            t_c = r["flops_per_device"] / PEAK_FLOPS
            t_m = r["hbm_bytes_per_device"] / HBM_BW
            t_x = r["collectives"]["wire_bytes_per_device"] / LINK_BW
            dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))
            mf = model_flops_per_device(a, s, chips)
            mb = model_bytes_per_device(a, s, chips, r.get("policy", {}))
            # minimum achievable step time on ANY resource vs estimated time
            # on the dominant resource
            t_min = max(mf / PEAK_FLOPS, mb / HBM_BW)
            rows.append({
                "arch": a, "shape": s, "mesh": mesh, "chips": chips,
                "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
                "dominant": dom[1], "bound_s": dom[0],
                "model_flops": mf, "model_bytes": mb,
                "useful_ratio": mf / max(r["flops_per_device"], 1.0),
                "roofline_fraction": min(t_min / max(dom[0], 1e-30), 1.0),
                "memory_gb": {k: v / 1e9 for k, v in r["memory"].items()},
                "policy": r.get("policy", {}),
            })
    return rows


IMPROVEMENT_NOTES = {
    "compute": "cut remat recompute (checkpoint dots-only) or raise per-chip "
               "batch to amortize fixed work",
    "memory": "decode/SSM cells are HBM-bound by cache/state reads: quantize "
              "the KV cache (HP-MDR bitplane truncation) or batch more "
              "queries per cache pass",
    "collective": "shrink per-layer all-gathers: two-level FSDP gather "
                  "(pod-local), bitplane-compressed gradient all-gather "
                  "(grad_compress), or overlap via latency hiding",
}


def fmt_table(rows: List[Dict]) -> str:
    hdr = ("| arch | shape | compute s | memory s | collective s | dominant | "
           "MODEL_FLOPS/HLO | roofline frac |\n"
           "|---|---|---|---|---|---|---|---|\n")
    out = [hdr]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']:.1%} |\n")
    return "".join(out)


if __name__ == "__main__":
    rows = load_cells("single")
    print(fmt_table(rows))
