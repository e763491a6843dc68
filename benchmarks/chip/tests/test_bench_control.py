"""The check that decides ``correct`` fails what it must: the control (the
plain reference in bfloat16 in the program's place) and each fault of the
timed path that a cell can have, planted underneath a whole run."""
import dataclasses
import time

import numpy as np
import pytest

from bench_tiny import SEED, WORKLOADS, tiny_cell
import harness
from repro.store import service as store_service
from repro.store import writer as store_writer


@pytest.mark.parametrize("workload", WORKLOADS)
def test_the_control_fails_where_the_program_holds(workload, tmp_path):
    r = harness.run_readings(tiny_cell(workload), SEED, 0.3,
                             out_dir=str(tmp_path))
    assert harness.verdict(r["program"]) is True, r
    assert harness.verdict(r["control"]) is False, r
    assert max(c.value for c in r["control"]) > 3.0, r


def _half(x):
    """Half of the answer left out: its second half zeroed."""
    y = np.array(x, dtype=np.float32, copy=True)
    y.reshape(-1)[y.size // 2:] = 0.0
    return y


def _altered(x):
    """One value of the answer altered where it is produced."""
    y = np.array(x, dtype=np.float32, copy=True)
    y.reshape(-1)[y.size // 3] += 0.5
    return y


def _plant(monkeypatch, workload, fault):
    if workload == "nyx.write":
        orig_write = store_writer.DatasetWriter.write
        monkeypatch.setattr(store_writer.DatasetWriter, "write",
                            lambda self, name, x: orig_write(
                                self, name, fault(x)))
        return
    if workload == "isabel.ladder":
        orig = store_service.Session.retrieve

        def retrieve(self, var, tol, relative=False):
            xh, bound, fetched = orig(self, var, tol, relative)
            if fault is None:        # the state is returned unchanged
                first = self.__dict__.setdefault("_first", {})
                xh = first.setdefault(var, xh)
                return xh, bound, fetched
            return fault(xh), bound, fetched
        monkeypatch.setattr(store_service.Session, "retrieve", retrieve)
        return
    orig_qoi = store_service.Session.retrieve_qoi

    def retrieve_qoi(self, variables, q, tau, method="mape", **kw):
        res = orig_qoi(self, variables, q, tau, method=method, **kw)
        if fault is None:
            first = self.__dict__.setdefault("_first", res.values)
            return dataclasses.replace(res, values=first)
        return dataclasses.replace(res, values=[fault(v) for v in res.values])
    monkeypatch.setattr(store_service.Session, "retrieve_qoi", retrieve_qoi)


CASES = [("nyx.write", "half"), ("nyx.write", "altered"),
         ("isabel.ladder", "unchanged"), ("isabel.ladder", "half"),
         ("isabel.ladder", "altered"), ("nyx.qoi", "unchanged"),
         ("nyx.qoi", "half"), ("nyx.qoi", "altered")]
FAULTS = {"unchanged": None, "half": _half, "altered": _altered}


@pytest.mark.parametrize("workload,fault", CASES)
def test_a_fault_under_the_timed_path_reads_not_correct(
        workload, fault, monkeypatch, tmp_path):
    _plant(monkeypatch, workload, FAULTS[fault])
    res = harness.run_cell(tiny_cell(workload), SEED, 0.3, False,
                           time.perf_counter(), out_dir=str(tmp_path))
    assert res["failed"] == 0
    assert res["correct"] is False, res["checks"]
