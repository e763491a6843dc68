"""``write``: one unit writes one field of the configuration, in turn, into
a fresh store through ``DatasetWriter``; the check reads every store the
window wrote back at ``check_tolerance`` and holds it to the field."""
from __future__ import annotations

import os
import shutil
from typing import Dict, List

from generator import BaseOp, Check, bf16, bound_checks, value_range


class Op(BaseOp):

    def setup(self, seed: int) -> None:
        self.data = self.make_fields(seed, self.config["shape"])
        self.names = list(self.data)
        from repro.core import lossless_batch as lb
        self._lb = lb

    def unit(self, i: int) -> dict:
        name = self.names[i % len(self.names)]
        root = os.path.join(self.root, f"w{i}")
        shutil.rmtree(root, ignore_errors=True)
        with self.writer(root) as w:
            entry = w.write(name, self.data[name])
        rec = {"i": i, "name": name, "root": root,
               "raw_bytes": int(self.data[name].nbytes),
               "chunks": len(entry.chunks)}
        self.records.append(rec)
        return rec

    def begin_window(self) -> None:
        for r in self.records:
            shutil.rmtree(r["root"], ignore_errors=True)
        super().begin_window()
        self._syncs0 = self._lb.STATS.host_syncs

    def counters(self) -> Dict[str, float]:
        chunks = sum(r["chunks"] for r in self.records)
        return {"chunks": chunks, "units": len(self.records),
                "codec_host_syncs": self._lb.STATS.host_syncs - self._syncs0}

    def e2e(self, window_s: float) -> Dict[str, float]:
        raw = sum(r["raw_bytes"] for r in self.records)
        return {"write_GBps": raw / 1e9 / window_s}

    def answers(self, control: bool = False) -> List[dict]:
        tol = float(self.mix["check_tolerance"])
        out = []
        for rec in self.records:
            x = self.data[rec["name"]]
            if control:
                xh, bound = bf16(x), tol * value_range(x)
            else:
                store, svc = self.open_service(rec["root"])
                try:
                    xh, bound, _ = svc.open_session().retrieve(
                        rec["name"], tol, relative=True)
                finally:
                    store.close()
            out.append({"x": x, "xh": xh, "bound": float(bound), "tol": tol})
        return out

    def check(self, answers: List[dict]) -> List[Check]:
        return bound_checks(answers)
