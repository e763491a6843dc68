"""Shared by the benchmark's CPU tests: import paths and tiny cells."""
import json
import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(CHIP))
for p in (os.path.join(CHECKOUT, "src"), CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

WORKLOADS = ("nyx.write", "isabel.ladder", "nyx.qoi")
SEED = 2**31 + 12345
# each configuration's shapes cut to what a CPU test holds; nothing else
# of the configuration or the mix changes
TINY = {"nyx": {"shape": [16, 16, 16], "qoi_shape": [16, 16, 16],
                "chunk_elems": 1024},
        "isabel": {"shape": [5, 20, 30], "chunk_elems": 1024}}


# cells whose files are in the benchmark while their entries wait in
# PERF.md's open questions: the entries a later PR adds to BENCHMARK.json,
# kept here so that their traffic and readers stay tested
with open(os.path.join(CHIP, "tests", "data", "deferred.json")) as f:
    DEFERRED = json.load(f)


def full_bench(checkout: str = CHECKOUT) -> dict:
    """``BENCHMARK.json`` with the deferred entries it does not have yet."""
    bench = harness.load_benchmark(os.path.join(checkout, "BENCHMARK.json"))
    for key, entries in DEFERRED.items():
        have = {e["name"] for e in bench[key]}
        bench[key] = bench[key] + [e for e in entries if e["name"] not in have]
    return bench


def tiny_cell(workload: str, checkout: str = CHECKOUT) -> "harness.Cell":
    bench = full_bench(checkout)
    cell = harness.resolve(bench, workload, checkout)
    config = {w["name"]: w["config"] for w in bench["workloads"]}[workload]
    cell.config = dict(cell.config, **TINY.get(config, {}))
    return cell
