"""Quickstart: refactor a scientific field, retrieve progressively.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import refactor as rf
from repro.core import retrieve as rt
from repro.data.fields import gaussian_field
from repro import compile_cache


def main():
    x = gaussian_field((64, 64, 64), slope=-2.2, seed=0)
    print(f"field: {x.shape} {x.dtype}  ({x.nbytes / 1e6:.1f} MB)")

    refd = rf.refactor_array(x, "demo")
    print(f"refactored into {len(refd.pieces)} pieces "
          f"({refd.stored_bytes / 1e6:.2f} MB stored, "
          f"{x.nbytes / refd.stored_bytes:.2f}x)")

    reader = rt.ProgressiveReader(refd)
    print(f"{'tol':>9} {'bound':>10} {'actual':>10} {'cum. bytes':>11} {'bits/val':>9}")
    for tol in [1e-1, 1e-2, 1e-3, 1e-4, 1e-5, 1e-6]:
        xh, bound, _ = reader.retrieve(tol)
        err = np.abs(xh - x).max()
        br = 8 * reader.total_bytes_fetched / x.size
        print(f"{tol:9.0e} {bound:10.2e} {err:10.2e} "
              f"{reader.total_bytes_fetched:11d} {br:9.2f}")
    print("every fetch was incremental: only new plane groups were read.")


if __name__ == "__main__":
    compile_cache.enable()
    main()
