"""``ladder``: set-up writes the configuration's fields into one store; one
unit is one cold walk of the tolerance ladder on one field, in turn."""
from __future__ import annotations

import os
from typing import Dict, List

from generator import BaseOp, Check, bf16, bound_checks, value_range


class Op(BaseOp):

    def setup(self, seed: int) -> None:
        self.data = self.make_fields(seed, self.config["shape"])
        self.names = list(self.data)
        self.store_root = os.path.join(self.root, "store")
        self.write_store(self.store_root, self.data)

    def unit(self, i: int) -> dict:
        name = self.names[i % len(self.names)]
        store, svc = self.open_service(self.store_root)
        try:
            session = svc.open_session()
            steps = []
            for tol in self.mix["tolerances"]:
                xh, bound, _ = session.retrieve(name, float(tol),
                                                relative=True)
                steps.append((float(tol), xh, float(bound)))
            fetched = int(store.stats().bytes_fetched)
        finally:
            store.close()
        rec = {"i": i, "name": name, "steps": steps, "fetched_bytes": fetched}
        self.records.append(rec)
        return rec

    def counters(self) -> Dict[str, float]:
        return {"units": len(self.records), "fetched_bytes": sum(
            r["fetched_bytes"] for r in self.records)}

    def e2e(self, window_s: float) -> Dict[str, float]:
        return {"ladder_s": window_s / len(self.records)}

    def answers(self, control: bool = False) -> List[dict]:
        out = []
        for rec in self.records:
            x = self.data[rec["name"]]
            xc = bf16(x) if control else None
            for tol, xh, bound in rec["steps"]:
                if control:
                    xh, bound = xc, tol * value_range(x)
                out.append({"x": x, "xh": xh, "bound": bound, "tol": tol})
        return out

    def check(self, answers: List[dict]) -> List[Check]:
        return bound_checks(answers)
