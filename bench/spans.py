"""Device idle time inside the program's own host spans, and its counters.

The program opens ``sz.*`` spans (``jax.profiler.TraceAnnotation``) on the
thread that calls the engine, the thread that holds the harness's spans, so
``trace.reduce`` keeps them among ``Reduced.host``, on the device events'
clock. The device is idle inside a set of spans for their merged length less
the part of it in which some operation ran: nested or repeated spans count
once. A trace without the spans, or a run whose engine lacks a counter,
reads ``None``.
"""

from __future__ import annotations

from typing import Optional

from bench.trace import Reduced, merge, overlap


def count(red: Reduced, name: str) -> int:
    """How many spans called ``name`` the harness's thread recorded."""
    return sum(n == name for n, _, _ in red.host)


def idle_s(red: Reduced, name: str) -> Optional[float]:
    """Seconds of device idle inside the spans called ``name``, averaged
    over the chips; None where the trace holds no such span."""
    spans = merge((s, e) for n, s, e in red.host if n == name)
    if not spans or not red.ops:
        return None
    idle = 0.0
    for evs in red.ops.values():
        busy = merge((s, e) for _, s, e in evs)
        idle += sum((e - s) - overlap(busy, s, e) for s, e in spans)
    return idle / len(red.ops) * 1e-9


def idle_ms_per(red: Reduced, name: str, per: str) -> Optional[float]:
    """Device idle inside the spans ``name``, in ms per span ``per``."""
    t, n = idle_s(red, name), count(red, per)
    if t is None or not n:
        return None
    return t / n * 1e3


def ratio(counters: dict, num: str, den: str) -> Optional[float]:
    """``counters[num] / counters[den]``; None where either is missing or
    the denominator is 0."""
    if num not in counters or not counters.get(den):
        return None
    return counters[num] / counters[den]
