# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver: one module per paper table/figure.

  Fig 6/7   bitplane_designs        Fig 8    lossless_strategies
  Fig 9     pipeline_overlap        Fig 10   weak_scaling
  Fig 11    end_to_end              Tab 2/3 + Fig 12/13/14  qoi_benchmarks
  (ours)    grad_compress_bench     (ours)   roofline (fused-write HLO
            roofline + measured probes, peaks from repro.tune.cost)
  (ours)    store_serving (cold/warm cache, sessions, bytes-vs-tol; also
            writes out/benchmarks/store_serving.json)
  (ours)    serving_load (open-loop Zipf load generator against the serving
            tier: hit-path speedup, p50/p99, cache-hit + coalesced ratios;
            writes out/benchmarks/serving_load.json)
  (ours)    autotune_smoke (repro.tune search + cache-hit replay + store
            plan round-trip; writes out/benchmarks/autotune_smoke.json)

Usage: PYTHONPATH=src python -m benchmarks.run [--only MODULE] [--devices N]

``--devices N`` forwards a device count to every benchmark whose ``run``
accepts a ``devices`` keyword (the mesh-sharded ones, e.g. weak_scaling),
so the bench matrix covers 1 vs N host devices; benchmarks without the
knob run unchanged.
"""
import argparse
import inspect
import sys
import traceback

from repro import compile_cache
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

MODULES = [
    "bitplane_designs",
    "lossless_strategies",
    "pipeline_overlap",
    "refactor_benchmarks",
    "weak_scaling",
    "end_to_end",
    "qoi_benchmarks",
    "grad_compress_bench",
    "store_serving",
    "serving_load",
    "autotune_smoke",
    "roofline",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--devices", type=int, default=None,
                    help="device count forwarded to sharding-aware benchmarks")
    args = ap.parse_args()
    compile_cache.enable()
    mods = [args.only] if args.only else MODULES
    print("name,us_per_call,derived")
    failures = 0
    for name in mods:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            kw = {}
            if (args.devices is not None
                    and "devices" in inspect.signature(mod.run).parameters):
                kw["devices"] = args.devices
            # per-module tracing + metrics scope: each module's write_json
            # artifact carries ITS spans/counters only (common.write_json
            # attaches the snapshot and the Chrome trace file)
            with obs_metrics.scope(), obs_trace.tracing():
                for line in mod.run(**kw):
                    print(line)
            sys.stdout.flush()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},0,ERROR")
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
