"""Capture of a profiler trace, and its reduction to what the metrics read.

The reduction takes the ``.xplane.pb`` the JAX profiler writes and keeps:

* device operations: the events of the ``XLA Ops`` line of every TPU plane,
  clipped to the traced window and named by their HLO instruction
  (``encode_fused.1``, ``fusion.114``, ``while.37``; the event's own name is
  the whole instruction text). Loops nest: a ``while`` event spans the
  operations of its body;
* the harness's spans: host events named ``bench.*`` (one
  ``jax.profiler.TraceAnnotation`` around each engine call), on the same
  clock as the device events;
* the host thread those spans sit on, to name what the host was doing in
  each gap of the device.

Busy time is the union of the device operations' intervals; idle is the
window less that union. A kernel's time is the sum of its events' durations.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

Interval = Tuple[float, float]


def op_name(text: str) -> str:
    """``%fusion.114 = bf16[...] fusion(...)`` -> ``fusion.114``."""
    return text.split(" = ", 1)[0].lstrip("%")


def self_times(evs: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Per operation name, its events' durations less the nested events
    they contain (a loop's own time, not its body's)."""
    acc: Dict[str, float] = {}
    stack: List[list] = []
    for name, s, e in sorted(evs, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            n, s0, e0, kids = stack.pop()
            acc[n] = acc.get(n, 0.0) + (e0 - s0) - kids
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    for n, s0, e0, kids in stack:
        acc[n] = acc.get(n, 0.0) + (e0 - s0) - kids
    return acc


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of the part of ``merged`` (disjoint, sorted) inside [lo, hi]."""
    i = max(bisect.bisect_right(merged, (lo, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < hi:
        s, e = merged[i]
        total += max(0.0, min(e, hi) - max(s, lo))
        i += 1
    return total


@dataclasses.dataclass
class Reduced:
    """One traced window: device ops per chip, harness spans, host events.

    Times are nanoseconds on the profiler's clock."""
    window: Interval
    ops: Dict[str, List[Tuple[str, float, float]]]   # plane -> (name, s, e)
    spans: List[Tuple[str, float, float]]            # bench.* host spans
    host: List[Tuple[str, float, float]]             # the spans' thread

    def __post_init__(self):
        self._busy = {p: merge((s, e) for _, s, e in evs)
                      for p, evs in self.ops.items()}

    @property
    def chips(self) -> int:
        return len(self.ops)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        if not self._busy:
            return 0.0
        return sum(overlap(b, *self.window) for b in self._busy.values()) \
            / len(self._busy) * 1e-9

    def busy_in(self, names: Iterable[str]) -> float:
        """Busy seconds inside the harness spans called ``names`` (given
        without the ``bench.`` prefix), averaged over the chips."""
        want = {SPAN_PREFIX + n for n in names}
        spans = merge((s, e) for n, s, e in self.spans if n in want)
        if not self._busy:
            return 0.0
        return sum(overlap(b, s, e) for b in self._busy.values()
                   for s, e in spans) / len(self._busy) * 1e-9

    def op_time(self, match: Callable[[str], bool]) -> Tuple[float, int]:
        """(seconds, events) of the operations whose name ``match``es,
        summed over events and averaged over the chips."""
        t = n = 0
        for evs in self.ops.values():
            for name, s, e in evs:
                if match(name):
                    t += e - s
                    n += 1
        k = max(len(self.ops), 1)
        return t / k * 1e-9, n // k

    def stage(self, t: float) -> str:
        """The innermost harness span at ``t`` (without ``bench.``)."""
        return min(((e - s, n) for n, s, e in self.spans if s <= t < e),
                   default=(0, SPAN_PREFIX + "none"))[1][len(SPAN_PREFIX):]

    def top_ops(self, k: int = 10) -> List[list]:
        """The ``k`` operations that took most device time of their own
        (nested operations subtracted), each named ``<stage>:<op>`` by the
        harness span its first event started in."""
        acc: Dict[str, float] = {}
        where: Dict[str, str] = {}
        for evs in self.ops.values():
            for name, t in self_times(evs).items():
                acc[name] = acc.get(name, 0.0) + t
            for name, s, _ in evs:
                where.setdefault(name, self.stage(s))
        n = max(len(self.ops), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[f"{where[name]}:{name}", t / n * 1e-9] for name, t in top]

    def gaps(self) -> List[Interval]:
        """The idle stretches of the first chip inside the window."""
        if not self._busy:
            return [self.window]
        busy = next(iter(self._busy.values()))
        lo, hi = self.window
        out, cur = [], lo
        for s, e in busy:
            if e <= lo or s >= hi:
                continue
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if cur < hi:
            out.append((cur, hi))
        return out

    def label(self, t: float) -> str:
        """What the host was doing at ``t``: the innermost harness span and
        the innermost other event on its thread."""
        span = self.stage(t)
        inner = min(((e - s, n) for n, s, e in self.host
                     if s <= t < e and not n.startswith(SPAN_PREFIX)),
                    default=(0, ""))[1]
        return f"{span}/{inner}" if inner else span

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The ``k`` longest idle gaps, by what the host was doing."""
        top = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:k]
        return [[self.label((s + e) / 2), (e - s) * 1e-9] for s, e in top]


def reduce(data) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Reduced`."""
    spans: List[Tuple[str, float, float]] = []
    host: List[Tuple[str, float, float]] = []
    ops: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
            ops[plane.name] = evs
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            mine = [ev for ev in evs if ev[0].startswith(SPAN_PREFIX)]
            if mine:
                spans.extend(mine)
                host.extend(evs)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    window = (min(s for s, _ in windows), max(e for _, e in windows))
    lo, hi = window
    ops = {p: [(n, max(s, lo), min(e, hi)) for n, s, e in evs
               if e > lo and s < hi] for p, evs in ops.items()}
    return Reduced(window=window, ops=ops, spans=spans, host=host)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path))


def options():
    """Profiler options: device and host events, no Python call tracing."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def matcher(*kernels: str) -> Callable[[str], bool]:
    """An operation-name predicate: the HLO instruction is one of
    ``kernels`` (``encode_fused`` matches ``encode_fused`` and
    ``encode_fused.3``). A Pallas kernel's instruction takes the name of
    its jitted entry point."""
    return lambda name: name.split(".", 1)[0] in kernels


def summary(data, limit: int = 40) -> Dict[str, object]:
    """Plane and line names with their busiest event names, for reading a
    trace by hand (``bench/trim_trace.py`` prints it)."""
    out: Dict[str, object] = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            acc: Dict[str, List[float]] = {}
            for e in line.events:
                a = acc.setdefault(e.name, [0, 0.0])
                a[0] += 1
                a[1] += e.duration_ns
            top = sorted(acc.items(), key=lambda kv: -kv[1][1])[:limit]
            lines[line.name] = [[n, c, t * 1e-9] for n, (c, t) in top]
        out[plane.name] = lines
    return out

