"""transfer.idle_ms: device idle inside the program's ``sz.transfer`` spans
(``DisaggregatedEngine.transfer``: encode, host reads, ship, decode), in ms
per traced transfer. The idle the transfer's host code leaves on the chip.
Moves ttft_p90_ms."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per(ctx.trace, "sz.transfer", "sz.transfer")
