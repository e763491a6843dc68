"""Mesh context + sharding rules.

Physical mesh axes: ``('data', 'model')`` single-pod, ``('pod', 'data',
'model')`` multi-pod.  Data parallelism (and ZeRO-3 parameter sharding)
spans ('pod','data'); tensor/expert parallelism spans 'model'.

Model code calls :func:`acts` / :func:`constraint` with *logical* specs and
the helpers translate to whatever axes the current mesh actually has, so the
same model runs on a 1x1 smoke-test mesh, a 16x16 pod, or a 2x16x16 slice.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def set_current_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh


def get_current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    prev = get_current_mesh()
    set_current_mesh(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        set_current_mesh(prev)


def _filter_axes(axes: Union[None, str, Sequence[str]], mesh: Mesh):
    """Keep only axes present in the mesh; collapse empty tuples to None."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    kept = tuple(a for a in axes if a in mesh.axis_names)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def spec(*dims) -> P:
    """PartitionSpec from logical per-dim axis requests, filtered by mesh.

    Each dim is None, an axis name, or a tuple of axis names.  'dp' expands
    to ('pod','data').  Without a current mesh, returns P() placeholders
    (constraints become no-ops)."""
    mesh = get_current_mesh()
    out = []
    for d in dims:
        if d == "dp":
            d = ("pod", "data")
        if mesh is None:
            out.append(None)
        else:
            out.append(_filter_axes(d, mesh))
    return P(*out)


def constraint(x: jax.Array, *dims) -> jax.Array:
    """with_sharding_constraint if a mesh is active, else identity."""
    mesh = get_current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec(*dims)))


def named(pspec: P) -> Optional[NamedSharding]:
    mesh = get_current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, pspec)


def dp_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_current_mesh()
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def tp_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_current_mesh()
    return mesh.shape.get("model", 1)
