"""The yardstick's own parts: the device field generator, the compile
meter and the window's memory reading."""
import numpy as np
import pytest

import bench_tiny
import fields
import harness
from compile_meter import CompileMeter


def _gen(seed, shape=(24, 20, 16), slopes=(-1.8, -5.0 / 3.0)):
    return np.asarray(fields.generate(seed, shape, slopes))


def test_fields_are_deterministic_per_seed_and_differ_across_seeds():
    big = 2**31 + 987654321          # more than 32 signed bits hold
    a, b, c = _gen(big), _gen(big), _gen(big + 1)
    assert a.dtype == np.float32 and a.shape == (2, 24, 20, 16)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert not np.array_equal(a[0], a[1])


def test_fields_are_normalised_like_gaussian_field():
    from repro.data.fields import gaussian_field
    x = _gen(5, (32, 32, 32), (-1.8,))[0]
    ref = gaussian_field((32, 32, 32), -1.8, 5)
    # max|x| == 1, mean ~0 (DC mode zeroed), as the NumPy original
    assert np.abs(x).max() == pytest.approx(1.0, abs=1e-6)
    assert abs(float(x.mean())) < 1e-5
    # same spectrum: the smoothness (neighbour correlation) agrees
    def corr(y):
        return float(np.corrcoef(y[:-1].ravel(), y[1:].ravel())[0, 1])
    assert corr(x) == pytest.approx(corr(ref), abs=0.05)
    assert float(x.std()) == pytest.approx(float(ref.std()), rel=0.35)


def test_steeper_slope_is_smoother():
    rough, smooth = _gen(3, (32, 32, 32), (-1.0, -3.0))
    d = [float(np.abs(np.diff(f, axis=0)).mean()) for f in (rough, smooth)]
    assert d[1] < d[0]


def test_compile_meter_counts_a_new_program():
    import jax
    import jax.numpy as jnp
    x = jnp.arange(7.0).block_until_ready()
    m = CompileMeter()
    c0, _, _ = m.reading()
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    c1, s1, _ = m.reading()
    assert c1 == c0 + 1 and s1 > 0


LOAD_AGAIN = """
import sys
sys.path.insert(0, sys.argv[2])
import jax, jax.numpy as jnp
from compile_meter import CompileMeter
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
x = jnp.arange(5.0).block_until_ready()
m = CompileMeter()
jax.jit(lambda x: x * 7 - 2)(x).block_until_ready()
jax.clear_caches()
jax.jit(lambda x: x * 7 - 2)(x).block_until_ready()
print(m.reading()[0], m.reading()[2])
"""


def test_compile_meter_counts_a_cache_load_as_no_compile(tmp_path):
    """A program loaded from the persistent cache fires JAX's compile
    duration event too; the meter counts it as a load only."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", LOAD_AGAIN, str(tmp_path), bench_tiny.CHIP],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split() == ["1", "1"]       # one compile, one load


class _Device:
    """A chip whose memory statistics the test sets."""

    def __init__(self, in_use, peak):
        self.stats = {"bytes_in_use": in_use, "peak_bytes_in_use": peak}

    def memory_stats(self):
        return dict(self.stats)


def _hold(dev, in_use, peak=None):
    """Inside a watch: hold ``in_use`` bytes long enough to be sampled."""
    import time
    dev.stats["bytes_in_use"] = in_use
    if peak is not None:
        dev.stats["peak_bytes_in_use"] = peak
    time.sleep(20 * harness.MemoryWatch.EVERY_S)
    dev.stats["bytes_in_use"] = 100


@pytest.mark.parametrize("window_peak,expected", [
    (5000, 5000),        # the window raised the peak: exact
    (None, 700),         # set-up's peak stands: the window's high mark
])
def test_memory_watch_reads_what_the_window_holds(window_peak, expected):
    quiet, busy = _Device(100, 3000), _Device(100, 3000)
    with harness.MemoryWatch([quiet, busy]) as mem:
        _hold(busy, 700 if window_peak is None else window_peak,
              window_peak)
    assert mem.peak == expected


def test_memory_watch_without_statistics_reads_zero():
    class Bare:
        def memory_stats(self):
            return None
    with harness.MemoryWatch([Bare()]) as mem:
        pass
    assert mem.peak == 0
