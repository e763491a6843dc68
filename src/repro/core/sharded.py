"""Mesh-sharded execution layer for the refactor and retrieval workflows.

HP-MDR targets multi-GPU nodes, and the scalable multigrid refactoring
line of work shows refactoring scales near-linearly when each device owns
a shard of the domain: chunks are independent (each is refactored with its
own decomposition, alignment, and lossless state), so the natural data
axis is the *chunk* axis.  This module owns the chunk -> device placement
policy and the per-device execution of the existing single-device engines:

``ShardedRefactorPlan`` (write side)
    Splits a variable's chunks round-robin across the devices of a 1-d
    ``'chunk'`` mesh (``make_chunk_mesh``; any ``Mesh`` is accepted — its
    device array is flattened into chunk-axis order).  Each chunk's whole
    encode chain still runs through the cached one-dispatch program of
    ``refactor_fused.fused_encode_plan``; committing the chunk's input to
    its owning device (``jax.device_put``) makes the jitted program execute
    there, so every device holds its own queue of collective-free
    dispatches, all in flight concurrently (``dispatch_ahead`` deep per
    device under the chunked pipeline).  ``finish_many`` resolves ANY
    batch of dispatched chunks — a full per-device window, not one round —
    with one ``lossless_batch.host_sync`` for the batch's tiny scalar
    metadata (per-piece exponents, amax, range) plus one stacked codec
    pass (``refactor_fused.finish_encode_many``): the amortized scalar
    gather count per chunk is ``1 / batch`` (< 1 whenever two or more
    chunks are in flight).

``ShardedReconstructEngine`` (read side)
    Places each chunk's incremental reconstruction state
    (``reconstruct.IncrementalReconstructor``) on the chunk's owning
    device and drains staged plane groups with per-device
    ``reconstruct.batch_apply_pending`` — decode buckets never mix
    devices, so every delta decode runs where its engine state lives.

Bit-exactness contract: placement never changes values.  A mesh of one
device is exactly today's path (same jitted programs, same device), and a
mesh of N host devices compiles the *same jaxpr* per device, so the
serialized output is byte-identical to the single-device oracle regardless
of device count — property-tested in tests/test_sharded.py and enforced
end-to-end by the store oracle test (single-device vs sharded writer
producing byte-identical segment files).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro.core import lossless as ll
from repro.core import lossless_batch as lb
from repro.core import reconstruct as rc
from repro.core import refactor as rf
from repro.core import refactor_fused as rff
from repro.obs import trace as obs_trace
from repro import tune as tn

from jax.sharding import Mesh

MeshLike = Union[None, int, Mesh]

CHUNK_AXIS = "chunk"


# ------------------------------------------------------------------- stats --

@dataclasses.dataclass
class ShardedStats:
    """Counters for the sharded layer (thread-safe, process-global).

    ``dispatches_by_device`` maps device ordinal (position in the chunk-axis
    device order) to fused dispatches issued there — round-robin placement
    shows up as a flat histogram.  ``rounds`` counts batched finishes (one
    cross-device scalar gather each); ``chunks_finished`` the chunks they
    resolved — their ratio is the amortized scalar-gathers-per-chunk number
    the async scheduler drives below 1 (counter-tested in
    tests/test_sharded.py)."""
    rounds: int = 0
    drains: int = 0
    chunks_finished: int = 0

    def __post_init__(self):
        self._lock = threading.Lock()
        self.dispatches_by_device: Dict[int, int] = {}

    def add_dispatch(self, ordinal: int) -> None:
        with self._lock:
            self.dispatches_by_device[ordinal] = (
                self.dispatches_by_device.get(ordinal, 0) + 1)

    def add(self, **kw: int) -> None:
        with self._lock:
            for k, v in kw.items():
                setattr(self, k, getattr(self, k) + v)

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {"rounds": self.rounds, "drains": self.drains,
                    "chunks_finished": self.chunks_finished,
                    "dispatches_by_device": dict(self.dispatches_by_device)}

    def reset(self) -> None:
        with self._lock:
            self.rounds = 0
            self.drains = 0
            self.chunks_finished = 0
            self.dispatches_by_device = {}


STATS = ShardedStats()


# -------------------------------------------------------------------- mesh --

def make_chunk_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-d ``('chunk',)`` mesh over the first ``n_devices`` local devices.

    ``None`` takes every available device.  This is the write/read stack's
    data axis: chunk ``ci`` lives on device ``ci % n`` of this mesh."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices < 1:
            raise ValueError(f"n_devices must be >= 1, got {n_devices}")
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devs)} available")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (CHUNK_AXIS,))


def resolve_mesh(mesh: MeshLike) -> Optional[Mesh]:
    """Normalize the ``mesh=`` knob: None / device count / ``Mesh``."""
    if mesh is None or isinstance(mesh, Mesh):
        return mesh
    if isinstance(mesh, int):
        return make_chunk_mesh(mesh)
    raise TypeError(f"mesh must be None, an int, or a Mesh, got {type(mesh)!r}")


def chunk_devices(mesh: Optional[Mesh]) -> List[Optional[jax.Device]]:
    """Chunk-axis device order of ``mesh`` (flattened for multi-axis meshes).

    ``None`` mesh -> ``[None]``: a single *uncommitted* slot, so the
    single-device path stays exactly today's ``jax.device_put(x)``."""
    if mesh is None:
        return [None]
    return list(mesh.devices.reshape(-1))


def _put(x, device: Optional[jax.Device]):
    """``device_put`` to a committed device, or today's uncommitted put."""
    return jax.device_put(x) if device is None else jax.device_put(x, device)


# -------------------------------------------------------------- write side --

class ShardedRefactorPlan:
    """Chunk -> device placement + per-shard fused dispatch (write side).

    Stateless apart from counters: ``place``/``dispatch`` may be called from
    any thread (the chunked pipeline's prefetcher places, the main thread
    dispatches).  All chunks of one variable share the cached
    ``fused_encode_plan`` programs — each device compiles the same jaxpr, so
    outputs are bitwise independent of placement."""

    def __init__(self, mesh: MeshLike,
                 levels: Optional[int] = None,
                 design: Optional[str] = None,
                 mag_bits: Optional[int] = None,
                 hybrid: Optional[ll.HybridConfig] = None,
                 backend: Optional[str] = None,
                 config: Optional[tn.RefactorConfig] = None):
        force = hybrid.force if hybrid is not None else None
        cfg = tn.as_config(config, design=design, mag_bits=mag_bits,
                           hybrid=hybrid, backend=backend)
        self.config = cfg
        self.mesh = resolve_mesh(mesh if mesh is not None
                                 else cfg.mesh_devices)
        self.devices = chunk_devices(self.mesh)
        self.levels = levels
        self.design = cfg.design
        self.mag_bits = cfg.mag_bits
        self.hybrid = cfg.hybrid(force=force)
        self.backend = cfg.backend

    @property
    def n_shards(self) -> int:
        return len(self.devices)

    def shard_for(self, ci: int) -> int:
        """Round-robin chunk -> shard ordinal (the manifest's record)."""
        return ci % self.n_shards

    def device_for(self, ci: int) -> Optional[jax.Device]:
        return self.devices[self.shard_for(ci)]

    def place(self, ci: int, host_chunk) -> jax.Array:
        """Commit chunk ``ci``'s input to its owning device (H2D copy)."""
        obs_trace.event(obs_trace.EV_DEVICE_PUT, chunk=ci,
                        device=self.shard_for(ci))
        return _put(host_chunk, self.device_for(ci))

    def dispatch(self, ci: int, chunk, name: str = "var",
                 donate: bool = False) -> rff.PendingChunk:
        """One collective-free fused dispatch on chunk ``ci``'s device.

        ``chunk`` may be a host array (placed here) or an already-placed
        device array from ``place``.  ``donate=True`` forwards the encode
        input for buffer donation (``refactor_fused.dispatch_encode``) —
        only pass it for buffers this layer's caller owns exclusively, e.g.
        the pipeline's placed copies.  Under tracing the span carries the
        owning device ordinal, so the Chrome-trace export renders one track
        per device (queue-drain idle gaps become visible)."""
        if not isinstance(chunk, jax.Array):
            chunk = self.place(ci, chunk)
        STATS.add_dispatch(self.shard_for(ci))
        with obs_trace.span("sharded.dispatch", chunk=ci,
                            device=self.shard_for(ci)):
            return rff.dispatch_encode(chunk, name=name, levels=self.levels,
                                       hybrid=self.hybrid, config=self.config,
                                       donate=donate)

    def dispatch_round(self, chunks: Sequence[Tuple[int, np.ndarray]],
                       name: str = "var") -> List[rff.PendingChunk]:
        """Dispatch one round: each (ci, host_chunk) to its owning device.

        Dispatches are async and collective-free, so a round of N chunks on
        N devices runs concurrently — the multi-device analogue of the
        single-device dispatch-ahead window."""
        return [self.dispatch(ci, chunk, name=f"{name}.{ci}")
                for ci, chunk in chunks]

    def finish_many(self, pendings: Sequence[rff.PendingChunk]
                    ) -> List[rf.Refactored]:
        """Resolve a batch of dispatched chunks — any number, any device mix:
        ONE host sync gathers every chunk's scalar metadata (exponents /
        amax / range) across devices, and ONE stacked lossless pass encodes
        every chunk's blob rows (``refactor_fused.finish_encode_many``), so
        a batch of B chunks costs 3 host syncs — not 3B.  Results come back
        in input order, byte-identical to finishing chunk by chunk."""
        pendings = list(pendings)
        if not pendings:
            return []
        STATS.add(rounds=1, chunks_finished=len(pendings))
        with obs_trace.span("sharded.finish_many", chunks=len(pendings)):
            return rff.finish_encode_many(pendings)

    def finish_round(self, pendings: Sequence[rff.PendingChunk]
                     ) -> List[rf.Refactored]:
        """Back-compat alias: a round is just a batch of one chunk per
        device — ``finish_many`` handles any batch shape."""
        return self.finish_many(pendings)

    def refactor_chunks(self, chunks: Sequence[np.ndarray], name: str = "var"
                        ) -> List[rf.Refactored]:
        """Convenience: dispatch up to one window (one chunk per device)
        ahead, finishing each window with one batched gather, returning
        results in chunk order."""
        out: List[rf.Refactored] = []
        n = self.n_shards
        for base in range(0, len(chunks), n):
            rnd = [(base + j, c)
                   for j, c in enumerate(chunks[base:base + n])]
            out.extend(self.finish_many(self.dispatch_round(rnd, name=name)))
        return out


# --------------------------------------------------------------- read side --

class ShardedReconstructEngine:
    """Chunk -> device placement for incremental reconstruction state.

    ``engine_for`` builds a ``reconstruct.IncrementalReconstructor`` pinned
    to the chunk's owning device; ``drain`` decodes the staged plane groups
    of many engines with one ``batch_apply_pending`` pass *per device*, so
    decode buckets never mix devices and every kernel launch runs where its
    engine state lives.  ``shards`` (the manifest's recorded chunk -> shard
    map) overrides round-robin placement, taken modulo the mesh size so a
    store written on N devices reads fine on M."""

    def __init__(self, mesh: MeshLike,
                 shards: Optional[Sequence[int]] = None):
        self.mesh = resolve_mesh(mesh)
        self.devices = chunk_devices(self.mesh)
        self.shards = list(shards) if shards is not None else None

    @property
    def n_shards(self) -> int:
        return len(self.devices)

    def shard_for(self, ci: int) -> int:
        if self.shards is not None and ci < len(self.shards):
            return self.shards[ci] % self.n_shards
        return ci % self.n_shards

    def device_for(self, ci: int) -> Optional[jax.Device]:
        return self.devices[self.shard_for(ci)]

    def engine_for(self, ci: int, ref: rf.Refactored, backend: str = "auto"
                   ) -> rc.IncrementalReconstructor:
        return rc.IncrementalReconstructor(ref, backend=backend,
                                           device=self.device_for(ci))

    @staticmethod
    def drain(engines: Sequence[rc.IncrementalReconstructor]) -> None:
        """Decode many engines' staged plane groups, per device.

        ``reconstruct.batch_apply_pending``'s bucket key includes each
        engine's owning device, so one call already yields per-device
        decode batches — shards never mix in a stacked launch, and every
        kernel runs where its engine state lives."""
        rc.batch_apply_pending(list(engines))
        STATS.add(drains=1)
