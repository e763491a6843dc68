"""Compile the main path's kernels for a TPU v5e without the chip.

The topology is *described* (``v5e:2x2``), nothing runs: each test lowers
and compiles a program at a real width for one described chip, in this
test's own process, and checks that the Pallas kernel made it into the
compiled program (``tpu_custom_call``).  What the chip's compiler refuses
here — layouts, unsupported reductions, compile-time blowups — costs no
chip time.  Interpret-mode tests (tests/test_bitplane.py) cannot see any of
it.

The topology is described only inside the module fixture below, never while
a module is imported: only one process at a time may load the TPU library.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.core import lossless_batch as lb
from repro.core import refactor as rf
from repro.core import refactor_fused as rff
from repro.core import decompose as dc
from repro.kernels import bitplane as bp
from repro.kernels import ops as kops
from repro.tune import DEFAULT_CONFIG
from repro.tune.search import TILES

N = 1 << 20          # the pipeline's default chunk: 1 Mi elements
PLANES = 24
DESIGNS = ("register_block", "locality", "shuffle")
TILE_COUNTS = (1,) + tuple(TILES)   # the default and the tuner's space


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    # described-device compiles land in the persistent cache but can never
    # be read back without a chip: keep the cache off around them
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("design", DESIGNS)
@pytest.mark.parametrize("tiles", TILE_COUNTS)
def test_encode_compiles_for_v5e(one_chip, design, tiles):
    x = jax.ShapeDtypeStruct((N,), jnp.uint32, sharding=one_chip)
    text = _compiled_text(
        lambda m: bp.encode_pallas(m, PLANES, design, tiles_per_block=tiles),
        x)
    assert "tpu_custom_call" in text
    assert f"bitplane_encode_{design}" in text   # the kernel's trace name


@pytest.mark.parametrize("design", DESIGNS)
@pytest.mark.parametrize("tiles", TILE_COUNTS)
def test_decode_compiles_for_v5e(one_chip, design, tiles):
    p = jax.ShapeDtypeStruct((8, N // 32), jnp.uint32, sharding=one_chip)
    text = _compiled_text(
        lambda q: bp.decode_pallas(q, PLANES, N, design,
                                   tiles_per_block=tiles), p)
    assert "tpu_custom_call" in text
    kernel = "register_block" if design == "register_block" else "locality"
    assert f"bitplane_decode_{kernel}" in text


def test_batched_decode_compiles_for_v5e(one_chip):
    """The serving path's vmapped decode at a realistic batch: 8 chunks'
    4-plane groups in one launch, at a plane offset (a traced shift)."""
    B, n = 8, N
    p = jax.ShapeDtypeStruct((B, 4, n // 32), jnp.uint32, sharding=one_chip)
    text = _compiled_text(
        lambda q: kops.decode_bitplanes_offset_batch(
            q, PLANES, n, 8, "register_block", "pallas"), p)
    assert "tpu_custom_call" in text


def test_fused_write_program_compiles_for_v5e(one_chip):
    """The whole one-dispatch write program for a full default chunk."""
    cfg = DEFAULT_CONFIG
    mag_bits = cfg.resolved_mag_bits()
    plan = rff.fused_encode_plan(
        (N,), dc.num_levels((N,)), cfg.design, mag_bits,
        tuple(rf._group_plane_split(mag_bits, cfg.group_size)), "pallas",
        cfg.tiles_per_block, cfg.unroll)
    x = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=one_chip)
    text = plan.run.lower(x).compile().as_text()
    assert text.count("tpu_custom_call") >= 2   # magnitudes + signs
    assert " gather(" not in text               # decompose: static slices


def test_codec_stats_accelerator_branch_compiles_for_v5e(one_chip,
                                                          monkeypatch):
    """``_group_stats_batch`` off the CPU takes its scatter-add histogram
    branch; steer ``default_backend`` here so that branch is the one
    compiled for the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    stats = jax.jit(lb._group_stats_batch.__wrapped__)
    s = jax.ShapeDtypeStruct((64, 1 << 15), jnp.uint8, sharding=one_chip)
    lowered = stats.lower(s)
    text = lowered.as_text()
    assert "stablehlo.scatter" in text and "stablehlo.sort" not in text
    lowered.compile()
