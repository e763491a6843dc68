"""Beyond-paper: progressive gradient compression — collective wire bytes of
the compressed allreduce vs plain psum, from lowered HLO on 8 host devices
(subprocess), plus encode throughput on this host."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import timeit, row
from repro.distributed.grad_compress import ef_quantize

_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.grad_compress import make_compressed_allreduce
from repro.launch.hlo_analysis import HloAnalysis
from repro.launch.mesh import auto_mesh
mesh = auto_mesh((8,), ("data",))
n = 1 << 22
xs = jax.ShapeDtypeStruct((8, n), jnp.float32)
sh = NamedSharding(mesh, P("data", None))
with mesh:
    cp = jax.jit(lambda x: jnp.mean(x, axis=0), in_shardings=(sh,),
                 out_shardings=NamedSharding(mesh, P())).lower(xs).compile()
    for planes in [4, 8, 12]:
        cc = jax.jit(make_compressed_allreduce(mesh, "data", planes=planes),
                     in_shardings=(sh,)).lower(xs).compile()
        wc = HloAnalysis(cc.as_text()).summary()["collective_wire_bytes_per_device"]
        print(f"RESULT comp{planes} {wc:.0f}")
    wp = HloAnalysis(cp.as_text()).summary()["collective_wire_bytes_per_device"]
    print(f"RESULT plain {wp:.0f}")
"""


def run() -> list:
    lines = []
    repo = Path(__file__).resolve().parents[1]
    # a CPU virtual-device rehearsal: pinned to the CPU backend so the child
    # never reaches for an accelerator the parent may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(repo / "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    vals = {}
    for l in r.stdout.splitlines():
        if l.startswith("RESULT"):
            _, k, v = l.split()
            vals[k] = float(v)
    if "plain" in vals:
        for k, v in vals.items():
            if k == "plain":
                lines.append(row("gradcomp_wire_plain_psum", 0.0, f"{v:.0f}B"))
            else:
                lines.append(row(f"gradcomp_wire_{k}", 0.0,
                                 f"{v:.0f}B;{v / vals['plain']:.2%}_of_plain"))
    else:
        lines.append(row("gradcomp_wire", 0.0, "FAILED:" + r.stderr[-200:]))
    # encode throughput (error-feedback quantize path)
    g = jnp.asarray(np.random.default_rng(0).normal(size=1 << 22).astype(np.float32))
    res = jnp.zeros_like(g)
    f = jax.jit(lambda a, b: ef_quantize(a, b, 8))
    jax.block_until_ready(f(g, res))
    t = timeit(lambda: jax.block_until_ready(f(g, res)))
    lines.append(row("gradcomp_ef_quantize_4M", t,
                     f"{g.nbytes / 1e9 / t:.3f}GBps"))
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
