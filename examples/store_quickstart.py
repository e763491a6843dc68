"""Store quickstart: write a dataset, reopen cold, serve progressive
requests that fetch only delta byte ranges.

    PYTHONPATH=src python examples/store_quickstart.py [root]

Pass a directory to keep the store around; default is a temp dir.
"""
import shutil
import sys
import tempfile

import numpy as np

from repro.data.fields import gaussian_field
from repro.store import DatasetStore, DatasetWriter, RetrievalService
from repro import compile_cache


def main():
    keep = len(sys.argv) > 1
    root = sys.argv[1] if keep else tempfile.mkdtemp(prefix="repro_store_")
    try:
        _run(root)
    finally:
        if not keep:
            shutil.rmtree(root, ignore_errors=True)


def _run(root):
    x = gaussian_field((64, 64, 64), slope=-2.2, seed=0)

    with DatasetWriter(root, chunk_elems=1 << 17) as w:
        entry = w.write("density", x)
    print(f"wrote {root}: {len(entry.chunks)} chunks, "
          f"{entry.stored_bytes / 1e6:.2f} MB "
          f"(raw {x.nbytes / 1e6:.1f} MB)")

    store = DatasetStore.open(root)          # cold open: manifest only
    service = RetrievalService(store)
    session = service.open_session()
    print(f"{'tol':>9} {'bound':>10} {'actual':>10} {'delta B':>9} "
          f"{'total B':>9} {'% of store':>10}")
    for tol in [1e-1, 1e-2, 1e-3, 1e-4, 1e-5]:
        xh, bound, fetched = session.retrieve("density", tol)
        err = np.abs(xh - x).max()
        frac = 100.0 * session.bytes_fetched / store.stored_bytes
        print(f"{tol:9.0e} {bound:10.2e} {err:10.2e} {fetched:9d} "
              f"{session.bytes_fetched:9d} {frac:9.1f}%")
    st = service.stats()["backend"]
    print(f"backend: {st['fetches']} range reads, "
          f"{st['bytes_fetched'] / 1e6:.2f} MB moved, "
          f"hit rate {st['hit_rate']:.2f}")
    print("each request fetched only the delta plane groups (incremental).")


if __name__ == "__main__":
    compile_cache.enable()
    main()
