"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state.  The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
before any jax import; everything else sees the real device count.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def auto_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with Auto axes.  ``jax.make_mesh`` builds Explicit
    axes by default; the model code's sharding rules
    (``distributed.sharding.constraint``, implicit gathers) assume Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) ('data','model') single pod; (2,16,16) ('pod','data','model')
    across two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1):
    """Small mesh over however many (host) devices are available — used by
    smoke tests, elastic-restore tests and the weak-scaling benchmark."""
    shape = tuple(x for x in (pod, data, model))
    axes = ("pod", "data", "model")
    if pod == 1:
        shape, axes = (data, model), ("data", "model")
    return auto_mesh(shape, axes)
