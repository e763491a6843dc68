"""The readings a cell's limits are set from, on the chip, in one process.

    python benchmarks/chip/control.py --workload isabel.ladder \
        --seconds 10 --seeds 101 102 103

For each seed: the cell's set-up and a window of ``--seconds``, then every
compared number twice, for what the program produced and for the control
(the plain reference computed in bfloat16, the precision below the
configuration's float32, in the program's place on the same inputs), each
side with the ``correct`` that the harness would give it.  One JSON line
per seed.  The benchmark's own runs (``run.py``) never run the
control.  Like ``run.py`` it refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import harness
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    harness.enable_compile_cache()
    if not run.chips_ok(cell.chips):
        return 2
    for seed in args.seeds:
        r = harness.run_readings(cell, seed, args.seconds)
        line = {side: dict({c.name: c.value for c in checks},
                           correct=harness.verdict(checks))
                for side, checks in r.items()}
        print(json.dumps({"workload": cell.name, "seed": seed, **line}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
