"""resident.flush_idle_ms: device idle inside the program's
``sz.resident.flush`` spans (the host flush of full tail pages after each
resident decode step: the cache-length and page-table reads, and the
re-encode on page boundaries), in ms per resident step of the traced batch.
Moves resident_tpot_ms."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per(ctx.trace, "sz.resident.flush",
                             "sz.resident.step")
