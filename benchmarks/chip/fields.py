"""Seeded spectral proxy fields, made on the device in one jitted call.

A ``jax.numpy`` port of the repository's ``gaussian_field`` (spectral
synthesis: a real Gaussian random field whose power spectrum goes as
``k^slope``, DC mode zeroed, Hermitian-symmetric through ``irfftn``,
normalised so that ``max|x| == 1``).  The noise comes from ``jax.random``
instead of NumPy's generator, so the values differ from the NumPy original
while the spectrum and the normalisation are the same.  Kept here so that
the benchmark's inputs cannot move with the program.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (two 32-bit halves of it mod 2^64)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def _field(key: jax.Array, shape: Tuple[int, ...], slope: jax.Array
           ) -> jax.Array:
    freqs = ([jnp.fft.fftfreq(s) for s in shape[:-1]]
             + [jnp.fft.rfftfreq(shape[-1])])
    k2 = jnp.zeros(tuple(len(f) for f in freqs), jnp.float32)
    for i, f in enumerate(freqs):
        idx = [None] * len(freqs)
        idx[i] = slice(None)
        k2 = k2 + jnp.square(f.astype(jnp.float32))[tuple(idx)]
    # power ~ k^slope -> amplitude k^(slope/2) = (k^2)^(slope/4)
    amp = jnp.where(k2 > 0, jnp.power(jnp.where(k2 > 0, k2, 1.0),
                                      slope / 4.0), 0.0)
    kr, ki = jax.random.split(key)
    noise = jax.lax.complex(jax.random.normal(kr, k2.shape, jnp.float32),
                            jax.random.normal(ki, k2.shape, jnp.float32))
    x = jnp.fft.irfftn(amp * noise, s=shape, axes=tuple(range(len(shape))))
    return (x / (jnp.max(jnp.abs(x)) + 1e-30)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("shape",))
def make_fields(key: jax.Array, shape: Tuple[int, ...],
                slopes: jax.Array) -> jax.Array:
    """``(len(slopes), *shape)``: field ``i`` from ``fold_in(key, i)`` with
    spectral slope ``slopes[i]``, made one after another (``lax.map``), so
    the device holds one field's spectrum at a time."""
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(slopes.shape[0]))
    return jax.lax.map(lambda ks: _field(ks[0], shape, ks[1]), (keys, slopes))


def generate(seed: int, shape: Sequence[int], slopes: Sequence[float]
             ) -> jax.Array:
    """The fields of one run, stacked, on the default device, ready."""
    out = make_fields(seed_key(seed), tuple(int(s) for s in shape),
                      jnp.asarray(slopes, jnp.float32))
    return jax.block_until_ready(out)
