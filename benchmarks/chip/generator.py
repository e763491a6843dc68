"""The one generator that turns a traffic mix (``traffic/<mix>.json``) and a
configuration (``configs/<config>.json``) into set-up, units of work and
the check of their outputs.

A mix names its operation in ``"op"``; the rest of the file is that
operation's parameters.  An operation is a file of its own,
``ops/<op>.py``, whose class ``Op`` (a ``BaseOp``) is found by that name,
so a new kind of traffic is a new file and no edit:

* ``write``  -- one unit writes one field of the configuration, in turn,
  into a fresh store through ``DatasetWriter`` (manifest finalized).
* ``ladder`` -- set-up writes the configuration's fields into one store;
  one unit opens it afresh (``DatasetStore.open`` + ``RetrievalService``,
  so every cache starts cold) and one session retrieves one field, in
  turn, at each relative tolerance of ``"tolerances"``.
* ``qoi``    -- set-up writes the mix's ``"variables"`` at the
  configuration's ``"qoi_shape"``; one unit opens the store afresh and one
  session retrieves the QoI at each ``"taus"`` with ``"method"``.

The plain reference is the field itself, made by ``fields.py`` from the
seed and kept on the host: a retrieval at relative tolerance ``tol`` must
return ``max|x - x^| <= bound <= tol * (max x - min x)``, and a QoI
retrieval ``max|Q - Q^| <= tau_estimated <= tau``, with ``Q`` worked out
in float64.  These are the guarantees the configuration states, so each
compared number is a ratio whose limit is 1.

The program is reached only through ``DatasetWriter``, ``DatasetStore.open``
and ``RetrievalService`` / ``Session``; what it counts is read from its
counters (``lossless_batch.STATS``, the store backend's ``BackendStats``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import shutil
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import fields as fld


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class Phase:
    """Prints how long a set-up phase took (standard error)."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        say(f"set-up {self.name}: {time.perf_counter() - self.t0:.3f} s")
        return False


def load_file(path: str, prefix: str):
    """The module in the file ``path``, loaded by its path."""
    name = prefix + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Check:
    """One compared number: its worst value over the answers, and its limit
    (the check holds where ``value <= limit``)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def bf16(x: np.ndarray) -> np.ndarray:
    """The reference in the next precision below float32 (the control)."""
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def value_range(x: np.ndarray) -> float:
    return float(np.max(x)) - float(np.min(x))


def max_abs_diff(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def ratio(num: float, den: float) -> float:
    return num / den if den > 0 else (0.0 if num == 0 else float("inf"))


class BaseOp:
    """Base of the operations: subclasses fill ``setup``, ``unit``,
    ``answers``, ``check`` and ``e2e``.  The harness warms up by running the
    window's own units for as long as the window, then calls
    ``begin_window``, which forgets them."""

    def __init__(self, config: dict, mix: dict, root: str):
        self.config = config
        self.mix = mix
        self.root = root
        self.records: List[dict] = []

    # -- helpers ---------------------------------------------------------
    def field_specs(self, names: Optional[Sequence[str]] = None
                    ) -> List[Tuple[str, float]]:
        spec = self.config["fields"]
        names = list(spec) if names is None else list(names)
        return [(n, float(spec[n])) for n in names]

    def make_fields(self, seed: int, shape: Sequence[int],
                    names: Optional[Sequence[str]] = None
                    ) -> Dict[str, np.ndarray]:
        """The fields, made on the device in one call, copied to the host."""
        specs = self.field_specs(names)
        with Phase(f"make {len(specs)} fields of {list(shape)}"):
            dev = fld.generate(seed, shape, [s for _, s in specs])
        with Phase("copy the fields to the host"):
            host = np.asarray(dev)
        del dev
        return {n: host[i] for i, (n, _) in enumerate(specs)}

    def writer(self, root: str):
        from repro.store import DatasetWriter
        return DatasetWriter(root, chunk_elems=int(self.config["chunk_elems"]),
                             use_tune_cache=False)

    def write_store(self, root: str, data: Dict[str, np.ndarray]) -> None:
        shutil.rmtree(root, ignore_errors=True)
        with Phase(f"write the {len(data)} fields the traffic reads"), \
                self.writer(root) as w:
            for name, x in data.items():
                w.write(name, x)

    def open_service(self, root: str):
        from repro.store import DatasetStore, RetrievalService
        store = DatasetStore.open(root)
        return store, RetrievalService(store)

    # -- interface ---------------------------------------------------------
    def setup(self, seed: int) -> None:
        raise NotImplementedError

    def unit(self, i: int) -> dict:
        raise NotImplementedError

    def begin_window(self) -> None:
        """Called once set-up and its warm-up units are done, before the
        first timed unit: the warm-up's units are forgotten."""
        self.records.clear()

    def counters(self) -> Dict[str, float]:
        """Program counters of the window's units (for per-layer readers)."""
        return {}

    def answers(self, control: bool = False) -> List[dict]:
        """What the window produced, in the form ``check`` compares; with
        ``control`` the reference in bfloat16 takes the program's place."""
        raise NotImplementedError

    def check(self, answers: List[dict]) -> List[Check]:
        raise NotImplementedError

    def e2e(self, window_s: float) -> Dict[str, float]:
        raise NotImplementedError


def bound_checks(answers: Sequence[dict]) -> List[Check]:
    """Retrievals at relative tolerances: ``max|x - x^| <= bound <=
    tol * range``, each side as the worst ratio over every answer."""
    err_tol = err_bound = bound_tol = 0.0
    for a in answers:
        err = max_abs_diff(a["xh"], a["x"])
        allowed = a["tol"] * value_range(a["x"])
        err_tol = max(err_tol, ratio(err, allowed))
        err_bound = max(err_bound, ratio(err, a["bound"]))
        bound_tol = max(bound_tol, ratio(a["bound"], allowed))
    return [Check("err_over_tol", err_tol, 1.0),
            Check("err_over_bound", err_bound, 1.0),
            Check("bound_over_tol", bound_tol, 1.0)]


def make_op(path: str, config: dict, mix: dict, root: str) -> BaseOp:
    """The operation in the file ``path`` (``ops/<op>.py``), set to run the
    mix on the configuration with its stores under ``root``."""
    return load_file(path, "bench_op_").Op(config, mix, root)
