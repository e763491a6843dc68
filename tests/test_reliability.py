"""Store reliability layer: error taxonomy, checksums, retry/backoff/breaker,
fault injection determinism, caching-backend failure propagation."""
import json
import os
import threading
import time

import pytest

from repro.store import backend as bk
from repro.store import reliability as rl


# ------------------------------------------------------------------ helpers --

class FakeClock:
    """Deterministic monotonic clock + sleep for retry tests (no real waits)."""

    def __init__(self):
        self.t = 0.0
        self.sleeps = []

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.sleeps.append(s)
        self.t += s


class ScriptedInner:
    """Inner backend that raises scripted exceptions before succeeding."""

    def __init__(self, data=b"payload", failures=()):
        self.data = data
        self.failures = list(failures)
        self.calls = 0

    def read(self, key, offset, size):
        self.calls += 1
        if self.failures:
            raise self.failures.pop(0)
        return self.data

    def size(self, key):
        self.calls += 1
        if self.failures:
            raise self.failures.pop(0)
        return len(self.data)

    def prefetch(self, key, offset, size):
        pass

    def close(self):
        pass


def retrying(inner, **policy_kw):
    clock = FakeClock()
    policy = rl.RetryPolicy(**policy_kw) if policy_kw else rl.RetryPolicy()
    b = rl.RetryingBackend(inner, policy, clock=clock, sleep=clock.sleep,
                           rng=__import__("random").Random(0))
    return b, clock


# ----------------------------------------------------------------- taxonomy --

def test_error_taxonomy_classification():
    assert rl.classify(rl.TransientFetchError("x")) == "transient"
    assert rl.classify(TimeoutError()) == "transient"
    assert rl.classify(ConnectionError()) == "transient"
    assert rl.classify(OSError(5, "EIO")) == "transient"
    assert rl.classify(rl.CorruptSegmentError("x")) == "corrupt"
    assert rl.classify(rl.TruncatedReadError("x")) == "corrupt"
    assert rl.classify(rl.FatalStoreError("x")) == "fatal"
    assert rl.classify(FileNotFoundError()) == "fatal"
    assert rl.classify(KeyError("k")) == "fatal"
    assert rl.classify(RuntimeError()) == "fatal"


def test_corrupt_is_valueerror_and_all_are_store_errors():
    # pre-checksum callers that catch ValueError on decode keep working
    assert issubclass(rl.CorruptSegmentError, ValueError)
    assert issubclass(rl.TruncatedReadError, rl.CorruptSegmentError)
    for t in (rl.TransientFetchError, rl.CorruptSegmentError,
              rl.TruncatedReadError, rl.FatalStoreError,
              rl.UnreachableSegmentError):
        assert issubclass(t, rl.StoreIOError)


def test_checksum_verify():
    blob = b"some segment bytes"
    c = rl.checksum(blob)
    rl.verify_checksum(blob, c)  # no raise
    with pytest.raises(rl.CorruptSegmentError):
        rl.verify_checksum(blob + b"x", c)
    with pytest.raises(rl.CorruptSegmentError):
        rl.verify_checksum(blob, c ^ 1)


def test_manifest_body_checksum_survives_json_roundtrip():
    body = {"v": {"shape": [3, 4], "amax": 0.25, "chunks": [[0, 10, "huff"]]}}
    c = rl.manifest_body_checksum(body)
    reparsed = json.loads(json.dumps({"variables": body, "crc32": c}))
    assert rl.manifest_body_checksum(reparsed["variables"]) == c


# -------------------------------------------------------------------- retry --

def test_retry_transient_then_success():
    inner = ScriptedInner(failures=[rl.TransientFetchError("flake"),
                                    TimeoutError()])
    b, clock = retrying(inner, attempts=4, base_delay_s=0.1, max_delay_s=1.0)
    assert b.read("k", 0, 7) == b"payload"
    assert inner.calls == 3
    assert b.stats.retries == 2
    assert b.stats.transient_errors == 2
    assert len(clock.sleeps) == 2
    # bounded exponential backoff with full jitter: attempt k's delay is in
    # [base/2, base] * 2^(k-1), capped
    assert 0.05 <= clock.sleeps[0] <= 0.1
    assert 0.1 <= clock.sleeps[1] <= 0.2


def test_retry_never_retries_corruption_or_fatal():
    for exc, kind in [(rl.CorruptSegmentError("rot"), "corrupt"),
                      (FileNotFoundError("gone"), "fatal")]:
        inner = ScriptedInner(failures=[exc])
        b, clock = retrying(inner, attempts=5)
        with pytest.raises(type(exc)):
            b.read("k", 0, 7)
        assert inner.calls == 1  # no second attempt
        assert clock.sleeps == []


def test_retry_exhaustion_raises_unreachable_with_cause():
    inner = ScriptedInner(failures=[rl.TransientFetchError(f"f{i}")
                                    for i in range(10)])
    b, _ = retrying(inner, attempts=3, base_delay_s=0.01)
    with pytest.raises(rl.UnreachableSegmentError) as ei:
        b.read("k", 0, 7)
    assert inner.calls == 3
    assert isinstance(ei.value.__cause__, rl.TransientFetchError)
    assert b.stats.exhausted == 1


def test_retry_deadline_cuts_attempts_short():
    inner = ScriptedInner(failures=[rl.TransientFetchError(f"f{i}")
                                    for i in range(100)])
    # base delay 10s vs 1s deadline: the first backoff would blow the
    # deadline, so only ONE attempt runs before UnreachableSegmentError
    b, clock = retrying(inner, attempts=50, base_delay_s=10.0,
                        max_delay_s=10.0, deadline_s=1.0)
    with pytest.raises(rl.UnreachableSegmentError):
        b.read("k", 0, 7)
    assert inner.calls == 1
    assert clock.sleeps == []


def test_circuit_breaker_opens_fast_fails_and_half_opens():
    inner = ScriptedInner(failures=[rl.TransientFetchError(f"f{i}")
                                    for i in range(100)])
    b, clock = retrying(inner, attempts=1, breaker_threshold=3,
                        breaker_reset_s=5.0)
    for _ in range(3):  # trip the breaker: 3 consecutive exhausted reads
        with pytest.raises(rl.UnreachableSegmentError):
            b.read("k", 0, 7)
    calls = inner.calls
    with pytest.raises(rl.UnreachableSegmentError):  # fast fail: no traffic
        b.read("k", 0, 7)
    assert inner.calls == calls
    assert b.stats.breaker_fast_fails == 1
    assert b.stats.breaker_opens == 1
    # other keys are unaffected: their reads still reach the inner backend
    calls = inner.calls
    with pytest.raises(rl.UnreachableSegmentError):
        b.read("other", 0, 7)  # inner is still scripted to fail
    assert inner.calls == calls + 1
    # after the reset window one probe read half-opens the circuit
    clock.t += 10.0
    inner.failures = []
    assert b.read("k", 0, 7) == b"payload"
    assert b.read("k", 0, 7) == b"payload"  # closed again


def test_retry_size_retried_prefetch_passthrough():
    inner = ScriptedInner(failures=[TimeoutError()])
    b, _ = retrying(inner)
    assert b.size("k") == 7
    b.prefetch("k", 0, 7)  # hint only: never retried, never raises
    b.close()


# --------------------------------------------------------- fault injection --

def _fault_reads(seed, n=400, **kw):
    inner = bk.InMemoryBackend({"seg": bytes(range(256)) * 16})
    fb = rl.FaultInjectionBackend(inner, rl.FaultConfig(seed=seed, **kw))
    out = []
    for i in range(n):
        off = (i * 13) % 1024
        try:
            out.append(fb.read("seg", off, 64))
        except rl.StoreIOError as e:
            out.append(type(e).__name__)
    return out, fb.stats


def test_fault_injection_deterministic_across_instances():
    a, sa = _fault_reads(seed=42, transient=0.2, corrupt=0.1)
    b, sb = _fault_reads(seed=42, transient=0.2, corrupt=0.1)
    assert a == b
    assert sa.snapshot() == sb.snapshot()
    assert sa.transient_injected > 0 and sa.corrupt_injected > 0
    c, _ = _fault_reads(seed=43, transient=0.2, corrupt=0.1)
    assert a != c  # a different seed draws a different fault pattern


def test_fault_injection_corruption_is_sticky_single_bitflip():
    inner = bk.InMemoryBackend({"seg": os.urandom(4096)})
    fb = rl.FaultInjectionBackend(inner, rl.FaultConfig(corrupt=1.0, seed=7))
    clean = inner.read("seg", 128, 256)
    r1 = fb.read("seg", 128, 256)
    r2 = fb.read("seg", 128, 256)  # a retry sees the SAME rot
    assert r1 == r2 and r1 != clean
    diff = [(i, a ^ b) for i, (a, b) in enumerate(zip(clean, r1)) if a != b]
    assert len(diff) == 1 and bin(diff[0][1]).count("1") == 1


def test_fault_injection_truncation_and_protect():
    inner = bk.InMemoryBackend({"seg": os.urandom(1024),
                                "manifest.json": b"{}" * 100})
    fb = rl.FaultInjectionBackend(
        inner, rl.FaultConfig(truncate=1.0, transient=1.0, seed=3,
                              protect=("manifest",)))
    # protected key: no transient, no truncation, byte-identical
    assert fb.read("manifest.json", 0, 50) == inner.read("manifest.json", 0, 50)
    with pytest.raises(rl.TransientFetchError):
        fb.read("seg", 0, 100)


def test_fault_injection_slow_read_sleeps():
    inner = bk.InMemoryBackend({"seg": b"x" * 64})
    fb = rl.FaultInjectionBackend(
        inner, rl.FaultConfig(slow=1.0, slow_s=0.01, seed=1))
    t0 = time.perf_counter()
    assert fb.read("seg", 0, 64) == b"x" * 64
    assert time.perf_counter() - t0 >= 0.009
    assert fb.stats.slow_injected == 1


def test_chaos_from_env_parsing():
    inner = bk.InMemoryBackend({"k": b"data"})
    assert rl.chaos_from_env(inner, env="") is inner  # unset -> identity
    wrapped = rl.chaos_from_env(inner, env="transient=0.25,seed=9,attempts=3")
    assert isinstance(wrapped, rl.RetryingBackend)
    assert isinstance(wrapped.inner, rl.FaultInjectionBackend)
    assert wrapped.inner.faults.transient == 0.25
    assert wrapped.inner.faults.seed == 9
    assert wrapped.policy.attempts == 3
    assert wrapped.read("k", 0, 4) == b"data"  # retries absorb the faults


def test_chaos_env_composes_with_retries_to_serve_identically():
    payload = os.urandom(2048)
    inner = bk.InMemoryBackend({"seg": payload})
    wrapped = rl.chaos_from_env(inner, env="transient=0.3,seed=11,attempts=8")
    for i in range(64):
        off = (i * 37) % 1024
        assert wrapped.read("seg", off, 128) == payload[off:off + 128]


# -------------------------------------------- caching backend failure paths --

class _BlockingFlaky:
    """Inner backend: first read blocks until released, then raises; later
    reads succeed.  Exercises the coalescing-under-failure path."""

    caches = False

    def __init__(self):
        self.release = threading.Event()
        self.entered = threading.Event()
        self.calls = 0
        self.fail_first = True

    def read(self, key, offset, size):
        self.calls += 1
        first = self.calls == 1
        if first:
            self.entered.set()
            self.release.wait(timeout=5.0)
            if self.fail_first:
                raise rl.TransientFetchError("flaky first read")
        return b"d" * size

    def size(self, key):
        return 1 << 20

    def prefetch(self, key, offset, size):
        pass

    def close(self):
        pass


def test_caching_backend_propagates_error_to_all_coalesced_waiters():
    inner = _BlockingFlaky()
    cb = bk.CachingBackend(inner, workers=0)
    results = []

    def reader():
        try:
            results.append(cb.read("k", 0, 8))
        except rl.TransientFetchError as e:
            results.append(type(e).__name__)

    t_owner = threading.Thread(target=reader)
    t_owner.start()
    assert inner.entered.wait(timeout=5.0)
    waiters = [threading.Thread(target=reader) for _ in range(4)]
    for t in waiters:
        t.start()
    time.sleep(0.05)  # let the waiters coalesce on the in-flight entry
    inner.release.set()
    for t in [t_owner] + waiters:
        t.join(timeout=5.0)
    # every coalesced reader saw the SAME typed error, exactly one inner read
    # happened for the failed round...
    assert results.count("TransientFetchError") >= 1
    # ...and the entry was cleared: a fresh read succeeds with a new fetch
    assert cb.read("k", 0, 8) == b"d" * 8
    assert ("k", 0, 8) not in cb._inflight


def test_caching_backend_prefetch_worker_survives_inner_failure():
    inner = _BlockingFlaky()
    inner.release.set()  # don't block; first read still raises
    cb = bk.CachingBackend(inner, workers=1)
    cb.prefetch("k", 0, 8)  # this fetch RAISES inside the worker
    deadline = time.time() + 5.0
    while cb._inflight and time.time() < deadline:
        time.sleep(0.01)
    # worker thread must still be alive and serving the queue afterwards
    cb.prefetch("k", 64, 8)
    while (("k", 64, 8) not in cb._cache) and time.time() < deadline:
        time.sleep(0.01)
    assert cb._cache.get(("k", 64, 8)) == b"d" * 8
    assert any(w.is_alive() for w in cb._workers)
    cb.close()


def test_caching_backend_counts_swallowed_fetch_errors():
    """The prefetch worker and the service's cache warming swallow inner
    read failures; ``fetch_errors`` still counts every one of them."""
    inner = _BlockingFlaky()
    inner.release.set()  # don't block; first read still raises
    cb = bk.CachingBackend(inner, workers=1)
    cb.prefetch("k", 0, 8)  # raises inside the worker, swallowed there
    deadline = time.time() + 5.0
    while cb.stats.fetch_errors == 0 and time.time() < deadline:
        time.sleep(0.01)
    assert cb.stats.snapshot()["fetch_errors"] == 1
    assert cb.read("k", 0, 8) == b"d" * 8  # later reads succeed, no count
    assert cb.stats.snapshot()["fetch_errors"] == 1
    cb.close()


def test_local_file_backend_truncated_read_is_typed(tmp_path):
    p = tmp_path / "seg"
    p.write_bytes(b"0123456789")
    b = bk.LocalFileBackend(str(tmp_path))
    assert b.read("seg", 2, 5) == b"23456"
    with pytest.raises(rl.TruncatedReadError):
        b.read("seg", 5, 10)  # range runs past EOF
    b.close()


def test_in_memory_backend_truncated_read_is_typed():
    b = bk.InMemoryBackend({"seg": b"0123"})
    with pytest.raises(rl.TruncatedReadError):
        b.read("seg", 2, 10)
