"""Paper Fig 10: weak-scaling of the REAL sharded write path across devices.

Each (host) device owns a round-robin shard of the chunks and runs the full
fused refactor chain — decompose -> quantize -> bitplane encode -> lossless
-> serialize — through ``ChunkedRefactorPipeline(mesh=...)``, exactly the
path ``store.DatasetWriter`` drives (not just the raw bitplane kernel).
Per-device workload is fixed (``CHUNKS_PER_DEV`` chunks of ``CHUNK_ELEMS``),
so ideal weak scaling keeps wall time flat as devices grow.

Host devices timeshare the container's few physical cores, so two numbers
are reported per device count n:

  ``weak_efficiency``     = t_1dev / t_n over the async-pipelined path —
                            the paper's weak-scaling metric (ideal 1.0,
                            only reachable while n <= cores; the paper
                            reports 89-95% on real GPUs; on a 1-core host
                            the ideal collapses to 1/n and is NOT gated);
  ``serialized_speedup``  = serial_wall / async_wall at the SAME device
                            count: the measured win of the async per-device
                            queues (batched drains: one scalar gather +
                            one stacked codec pass per window of
                            ``dispatch_ahead * n`` chunks) over the
                            round-barrier serial mode that finishes every
                            chunk with its own 3 host syncs;
  ``sync_amortization``   = serial syncs-per-chunk / async syncs-per-chunk,
                            from the codec engine's counters: the
                            scheduling layer's batching factor, exactly
                            ``dispatch_ahead * n`` when every drain window
                            fills (8.0 at 4 devices).  Counter-based, so it
                            is deterministic and host-core-independent —
                            this is the >= 2x async-vs-serialized gate.

On a 1-core host the WALL ratio is capped well below the sync
amortization: both modes run identical device compute (dispatch, codec
kernels, Algorithm-2 selection, serialization) on the same core, and only
the per-finish host overhead (~0.2 ms/chunk of the ~1.5 ms/chunk total)
is amortizable, bounding serial/async near 1.4 regardless of window
depth.  On real multi-GPU hosts the wall ratio approaches the
amortization factor because the batched drain also uncovers cross-device
compute overlap the round-barrier forfeits.

Writes ``out/benchmarks/weak_scaling.json`` with per-device-count
throughput and efficiency (the CI bench artifact).  ``run(devices=N)``
narrows the matrix to {1, N} (the ``benchmarks.run --devices`` knob).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path
from typing import List, Optional

from benchmarks.common import row, write_json

# 8 Ki-elem chunks, 16 per device: enough chunks that every drain window
# fills several times over, and a per-chunk host-overhead fraction large
# enough that the serial-vs-async wall ratio is stable run to run (at
# 64 Ki-elem chunks the shared compute drowns the ~0.2 ms/chunk amortizable
# overhead and the ratio wanders across 1.0)
CHUNK_ELEMS = 1 << 13
CHUNKS_PER_DEV = 16
DISPATCH_AHEAD = 2

_SCRIPT = rf"""
import json, time
import numpy as np, jax
from repro.core import lossless_batch as lb
from repro.core import pipeline as pl
from repro.core import sharded as shd

from repro.data.fields import gaussian_field

n_dev = len(jax.devices())
chunk_elems = {CHUNK_ELEMS}
n = n_dev * {CHUNKS_PER_DEV} * chunk_elems
# smooth spectral field, like the other write benchmarks: compressible but
# not trivial (pure iid noise stores at ratio < 1 at this chunk size, which
# would gate compression on data no refactorer targets)
x = gaussian_field((n,), slope=-2.0, seed=0)
mesh = shd.make_chunk_mesh(n_dev)

def write(pipelined):
    # dispatch_ahead pinned + tune cache off: the artifact must measure THIS
    # window depth, not whatever a stale cache on the CI host tuned last week
    pipe = pl.ChunkedRefactorPipeline(chunk_elems=chunk_elems, levels=2,
                                      mesh=mesh, pipelined=pipelined,
                                      dispatch_ahead={DISPATCH_AHEAD},
                                      use_tune_cache=False)
    pipe.refactor(x, name="v")
    return pipe

def timed(pipelined):
    write(pipelined)  # warm the jit caches (compile amortized in practice)
    lb.STATS.reset()
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        pipe = write(pipelined)
        ts.append(time.perf_counter() - t0)
    syncs = lb.STATS.snapshot()["host_syncs"] / 3  # 3 identical timed runs
    return sorted(ts)[1], syncs / pipe.stats.chunks, pipe

serial_dt, serial_spc, _ = timed(pipelined=False)
dt, async_spc, pipe = timed(pipelined=True)

print("RESULT " + json.dumps({{
    "devices": n_dev, "wall_s": dt, "serial_wall_s": serial_dt,
    "chunks": pipe.stats.chunks,
    "serial_syncs_per_chunk": serial_spc,
    "async_syncs_per_chunk": async_spc,
    "sync_amortization": serial_spc / async_spc,
    "bytes_in": pipe.stats.bytes_in, "bytes_out": pipe.stats.bytes_out,
    "compression_ratio": pipe.stats.bytes_in / max(pipe.stats.bytes_out, 1),
    "gbps": pipe.stats.bytes_in / dt / 1e9}}))
"""


def _one(n_dev: int, repo: Path) -> Optional[dict]:
    # a CPU virtual-device rehearsal: pinned to the CPU backend so the child
    # never reaches for an accelerator the parent may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = str(repo / "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    out = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")]
    if r.returncode != 0 or not out:
        sys.stderr.write(r.stderr[-2000:])
        return None
    return json.loads(out[0][len("RESULT "):])


def run(devices: Optional[int] = None) -> List[str]:
    counts = [1, 2, 4, 8] if devices is None else sorted({1, int(devices)})
    repo = Path(__file__).resolve().parents[1]
    lines, results, base = [], [], None
    for n in counts:
        res = _one(n, repo)
        if res is None:
            lines.append(row(f"weak_scaling_{n}dev", 0.0, "FAILED"))
            continue
        if n == 1:
            base = res["wall_s"]
        # serialized_speedup is same-count serial/async: always computable.
        # weak_efficiency needs the 1-device async baseline; if that run
        # FAILED, later rows report no_baseline instead of a bogus ratio.
        res["serialized_speedup"] = res["serial_wall_s"] / res["wall_s"]
        if base is None:
            res["weak_efficiency"] = None
            derived = (f"{res['gbps']:.4f}GBps;"
                       f"serialized_speedup={res['serialized_speedup']:.2f};"
                       f"sync_amortization={res['sync_amortization']:.1f};"
                       "no_baseline")
        else:
            res["weak_efficiency"] = base / res["wall_s"]
            derived = (f"{res['gbps']:.4f}GBps;"
                       f"weak_efficiency={res['weak_efficiency']:.2f};"
                       f"serialized_speedup={res['serialized_speedup']:.2f};"
                       f"sync_amortization={res['sync_amortization']:.1f};"
                       f"compression={res['compression_ratio']:.3f}")
        results.append(res)
        lines.append(row(f"weak_scaling_{n}dev", res["wall_s"], derived))
    write_json("weak_scaling", {
        "bench": "weak_scaling", "path": "ChunkedRefactorPipeline(mesh=...)",
        "chunk_elems": CHUNK_ELEMS, "chunks_per_device": CHUNKS_PER_DEV,
        "dispatch_ahead": DISPATCH_AHEAD,
        "host_cores": os.cpu_count(),
        "results": results})
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
