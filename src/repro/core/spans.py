"""The program's own spans and host-read counts, on the profiler's clock.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` named
``"sz." + name``: a host event on the calling thread, on the same clock as
the device's operations, so the device time inside it (busy or idle) can be
read from one trace. Identifiers (a batch ordinal, a leaf key, a step) go in
as keyword stats, never into the name. With the profiler off a span costs
the annotation's disabled path and nothing else.

Spans belong in eager host code only: inside a jitted or ``shard_map``-traced
function they would time the tracing, once, and not the work.

``host_read(x, what, stats, as_)`` performs one device-to-host read of ``x``
inside a ``sz.host_read`` span and adds 1 to ``stats.host_reads``. It adds
no read: each call site is a read the program already made.

    span                 where
    sz.prefill           DisaggregatedEngine.prefill (batch)
    sz.transfer          DisaggregatedEngine.transfer (batch)
    sz.transfer.encode   one leaf's encode down the capacity schedule (key)
    sz.transfer.decode   one leaf's decode on the receiving side (key)
    sz.resident.admit    pool build and admission of the received streams
    sz.decode            DisaggregatedEngine.decode (batch)
    sz.resident.step     one resident decode step's dispatch (step)
    sz.resident.flush    the flush of full tail pages after a step
    sz.host_read         a device-to-host read (what)
"""

from __future__ import annotations

import jax
import numpy as np

PREFIX = "sz."


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span ``sz.<name>`` with ``ids`` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


def host_read(x, what: str, stats, as_=np.asarray):
    """``as_(x)`` (``np.asarray``, ``bool``, ``int`` or ``float``) inside a
    ``sz.host_read`` span, counted on ``stats.host_reads`` when ``x`` is a
    device array (a host value, as a host-side backend returns, is no
    read). ``stats`` may be None: the read is then only spanned."""
    if not isinstance(x, jax.Array):
        return as_(x)
    with span("host_read", what=what):
        out = as_(x)
    if stats is not None:
        stats.host_reads += 1
    return out
