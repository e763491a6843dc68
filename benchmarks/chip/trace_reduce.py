"""From a profiler trace to per-layer numbers.

``Recorder`` traces the measured window: JAX's profiler with the Python
tracer off, the program's ``repro.obs`` spans bridged into the same trace
(``tracing(jax_profiler=True)`` turns each span into a
``jax.profiler.TraceAnnotation``), and the benchmark's own ``bench.window``
and ``bench.unit`` spans around the window and each unit.

``reduce`` reads the ``.xplane.pb`` through ``jax.profiler.ProfileData``:

* the window is the ``bench.window`` span on the host;
* a device's busy time is the union, inside the window, of the intervals
  in which one of its programs ran (the ``XLA Modules`` line of a device
  plane such as ``/device:TPU:0``), averaged over the devices that ran
  anything; device time is also summed per program (the module's name
  without its fingerprint);
* every stretch of the window in which no program ran on a device is an
  idle gap, labelled by the innermost span (``repro.obs`` or ``bench``)
  open on the host at its midpoint.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
UNIT_SPAN = "bench.unit"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
MODULES_LINE = "XLA Modules"
FINGERPRINT = re.compile(r"\(\d+\)$")
# the program's spans and the benchmark's: dotted lower-case names, unlike
# the runtime's own trace events (``PjitFunction(f)``, ``ParseArguments``)
SPAN = re.compile(r"^[a-z][a-z0-9_]*\.[a-z0-9_.]+$")
TOP = 10

Interval = Tuple[int, int]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                    # mean over the devices that ran ops
    n_devices: int
    op_s: Dict[str, float]           # device seconds by program
    gaps_s: Dict[str, float]         # idle seconds by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> Dict[str, List[List[object]]]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def gaps(cover: Sequence[Interval], window: Interval) -> List[Interval]:
    """The stretches of ``window`` that ``cover`` (sorted, disjoint) leaves."""
    out, t = [], window[0]
    for a, b in cover:
        if a > t:
            out.append((t, min(a, window[1])))
        t = max(t, b)
        if t >= window[1]:
            break
    if t < window[1]:
        out.append((t, window[1]))
    return [g for g in out if g[1] > g[0]]


def _clip(a: int, b: int, w: Interval) -> Optional[Interval]:
    a, b = max(a, w[0]), min(b, w[1])
    return (a, b) if b > a else None


def label_points(spans: Sequence[Tuple[int, int, str]],
                 points: Sequence[int]) -> List[str]:
    """For each time in ``points``, the innermost (shortest) span open at
    it, or ``"none"``: one sweep over spans sorted by start."""
    order = sorted(range(len(points)), key=lambda i: points[i])
    spans = sorted(spans)
    out = ["none"] * len(points)
    active: List[Tuple[int, int, str]] = []
    k = 0
    for i in order:
        t = points[i]
        while k < len(spans) and spans[k][0] <= t:
            active.append(spans[k])
            k += 1
        active = [s for s in active if s[1] > t]
        if active:
            out[i] = min(active, key=lambda s: s[1] - s[0])[2]
    return out


def reduce(pd) -> Summary:
    """Reduce a ``jax.profiler.ProfileData`` to a ``Summary``."""
    window: Optional[Interval] = None
    spans: List[Tuple[int, int, str]] = []
    device_planes = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if not SPAN.match(name):
                    continue
                a, b = int(ev.start_ns), int(ev.end_ns)
                if name == WINDOW_SPAN:
                    window = (a, b) if window is None else window
                else:
                    spans.append((a, b, name))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")

    op_s: Dict[str, float] = {}
    busy = []
    cover_all: List[Interval] = []
    for plane in device_planes:
        ivs = []
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                iv = _clip(int(ev.start_ns), int(ev.end_ns), window)
                if iv is None:
                    continue
                ivs.append(iv)
                key = FINGERPRINT.sub("", ev.name)
                op_s[key] = op_s.get(key, 0.0) + (iv[1] - iv[0]) / 1e9
        if ivs:
            cover = union(ivs)
            busy.append(sum(b - a for a, b in cover) / 1e9)
            cover_all += cover
    idle: Dict[str, float] = {}
    holes = gaps(union(cover_all), window)
    labels = label_points(spans, [(a + b) // 2 for a, b in holes])
    for (a, b), label in zip(holes, labels):
        idle[label] = idle.get(label, 0.0) + (b - a) / 1e9
    return Summary(window_s=(window[1] - window[0]) / 1e9,
                   busy_s=sum(busy) / len(busy) if busy else 0.0,
                   n_devices=len(busy), op_s=op_s, gaps_s=idle)


def load(path: str) -> Summary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path))


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """A benchmark span in the profiler's trace (free when no trace runs)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class Recorder:
    """Traces part of a window into ``directory`` and reduces it: ``start``
    before the first traced unit, ``stop`` after the last."""

    def __init__(self, directory: str):
        self.directory = directory
        self._stack: Optional[contextlib.ExitStack] = None

    def start(self) -> None:
        import jax
        from repro.obs import trace as obs_trace
        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self._stack = contextlib.ExitStack()
        self._stack.enter_context(obs_trace.tracing(jax_profiler=True))
        self._stack.enter_context(annotate(WINDOW_SPAN))

    def stop(self) -> None:
        import jax
        if self._stack is None:
            return
        try:
            self._stack.close()
        finally:
            self._stack = None
            jax.profiler.stop_trace()

    def path(self) -> str:
        found = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.directory}")
        return sorted(found)[-1]

    def reduce(self) -> Summary:
        return load(self.path())
