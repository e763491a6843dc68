"""QoI-controlled progressive retrieval (paper §6.2 / Alg 3) **through the
on-disk store**: write three velocity components with the dataset writer,
reopen cold, and retrieve to a guaranteed V_total = Vx^2+Vy^2+Vz^2 tolerance,
comparing the CP / MA / MAPE error-bound estimators.  Each session fetches
only the plane-group byte ranges its estimator asks for.

    PYTHONPATH=src python examples/qoi_retrieval.py
"""
import shutil
import tempfile

import numpy as np

from repro.core import qoi as qq
from repro.data.fields import velocity_field
from repro.store import DatasetStore, DatasetWriter, RetrievalService
from repro import compile_cache


def main():
    vs = list(velocity_field((48, 48, 48), seed=1))
    truth = sum(v ** 2 for v in vs)

    root = tempfile.mkdtemp(prefix="qoi_store_")
    try:
        _run(vs, truth, root)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _run(vs, truth, root):
    with DatasetWriter(root, chunk_elems=1 << 20) as w:
        for v, n in zip(vs, ["vx", "vy", "vz"]):
            w.write(n, v)

    store = DatasetStore.open(root)  # cold: metadata only, no payloads yet
    service = RetrievalService(store)
    print(f"store: {store.stored_bytes / 1e6:.2f} MB on disk, "
          f"variables {store.variables}")

    print(f"{'method':>10} {'tau':>9} {'bitrate':>8} {'iters':>6} "
          f"{'estimated':>10} {'actual':>10} {'MB fetched':>10} guarantee")
    for method, kw in [("cp", {}), ("ma", {}), ("mape", {"c": 10.0})]:
        session = service.open_session()  # one session per estimator
        for tau in [1e-2, 1e-4]:          # tightening tau reuses the session
            res = session.retrieve_qoi(["vx", "vy", "vz"], qq.V_TOTAL, tau,
                                       method=method, **kw)
            actual = np.abs(sum(v ** 2 for v in res.values) - truth).max()
            ok = actual <= res.tau_estimated <= tau
            print(f"{method:>10} {tau:9.0e} {res.bitrate:8.2f} "
                  f"{res.iterations:6d} {res.tau_estimated:10.2e} "
                  f"{actual:10.2e} {session.bytes_fetched / 1e6:10.2f} "
                  f"{'OK' if ok else 'VIOLATED'}")
    st = service.stats()["backend"]
    if st:
        print(f"backend: {st['bytes_fetched'] / 1e6:.2f} MB from storage, "
              f"cache hit rate {st['hit_rate']:.2f} across sessions")


if __name__ == "__main__":
    compile_cache.enable()
    main()
