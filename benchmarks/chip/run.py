"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python benchmarks/chip/run.py --workload nyx.write --seed 7 \
        --seconds 30 --trace 0

The cell's configuration, traffic mix and per-layer metrics are files
under ``benchmarks/chip/`` named in ``BENCHMARK.json`` (see
``harness.py``).  The run makes its inputs from ``--seed`` on the device,
warms up, measures whole units of work until ``--seconds`` have passed,
and then holds what the window produced to the plain reference.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics; with ``--trace 1`` the
per-layer ones, read from a profiler trace of the window), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checks``, each compared
number beside its limit.  The same numbers end standard error.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_ok(want: int) -> bool:
    """True where JAX sees a TPU with at least ``want`` chips; says why not
    on standard error."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform {devs[0].platform!r}); refusing "
              f"to run", file=sys.stderr)
        return False
    if len(devs) < want:
        print(f"bench: the cell needs {want} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return False
    return True


def main(argv=None) -> int:
    args = parse(argv)
    import harness
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    harness.enable_compile_cache()
    if not chips_ok(cell.chips):
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    for name, c in result["checks"].items():
        print(f"bench: check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
