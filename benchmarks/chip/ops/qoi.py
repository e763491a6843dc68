"""``qoi``: set-up writes the mix's variables at the configuration's
``qoi_shape``; one unit is one cold QoI request, tightened over ``taus``."""
from __future__ import annotations

import os
from typing import Dict, List, Sequence

import numpy as np

from generator import BaseOp, Check, bf16, ratio


class Op(BaseOp):

    def setup(self, seed: int) -> None:
        self.names = list(self.mix["variables"])
        self.data = self.make_fields(seed, self.config["qoi_shape"],
                                     self.names)
        self.store_root = os.path.join(self.root, "store")
        self.write_store(self.store_root, self.data)

    def qoi(self, vs: Sequence[np.ndarray]) -> np.ndarray:
        kind = self.mix["qoi"]
        if kind != "sum_squares":
            raise ValueError(f"unknown QoI {kind!r}")
        return sum(np.asarray(v, np.float64) ** 2 for v in vs)

    def unit(self, i: int) -> dict:
        from repro.core import qoi as qq
        q = qq.QoI(self.mix["qoi"])
        store, svc = self.open_service(self.store_root)
        try:
            session = svc.open_session()
            steps = []
            for tau in self.mix["taus"]:
                res = session.retrieve_qoi(self.names, q, float(tau),
                                           method=self.mix["method"])
                steps.append({"tau": float(tau), "values": res.values,
                              "estimated": float(res.tau_estimated),
                              "iterations": int(res.iterations)})
            fetched = int(store.stats().bytes_fetched)
        finally:
            store.close()
        rec = {"i": i, "steps": steps, "fetched_bytes": fetched}
        self.records.append(rec)
        return rec

    def counters(self) -> Dict[str, float]:
        return {"units": len(self.records),
                "fetched_bytes": sum(r["fetched_bytes"]
                                     for r in self.records),
                "iterations": sum(s["iterations"] for r in self.records
                                  for s in r["steps"])}

    def e2e(self, window_s: float) -> Dict[str, float]:
        return {"qoi_s": window_s / len(self.records)}

    def answers(self, control: bool = False) -> List[dict]:
        ctrl = [bf16(self.data[n]) for n in self.names] if control else None
        out = []
        for rec in self.records:
            for s in rec["steps"]:
                values, est = s["values"], s["estimated"]
                if control:
                    values, est = ctrl, s["tau"]
                out.append({"values": values, "estimated": est,
                            "tau": s["tau"]})
        return out

    def check(self, answers: List[dict]) -> List[Check]:
        truth = self.qoi([self.data[n] for n in self.names])
        err_tau = err_est = est_tau = 0.0
        for a in answers:
            err = float(np.max(np.abs(self.qoi(a["values"]) - truth)))
            err_tau = max(err_tau, ratio(err, a["tau"]))
            err_est = max(err_est, ratio(err, a["estimated"]))
            est_tau = max(est_tau, ratio(a["estimated"], a["tau"]))
        return [Check("qoi_err_over_tau", err_tau, 1.0),
                Check("qoi_err_over_est", err_est, 1.0),
                Check("qoi_est_over_tau", est_tau, 1.0)]
