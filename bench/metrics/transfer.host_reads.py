"""transfer.host_reads: device-to-host reads per ``engine.transfer`` call,
counted by the program (``EngineStats.transfer_host_reads /
transfer_calls``) over the whole run: per encoded leaf the capacity check and
the wire-byte count, the cache length, and the pool's admission reads where
the decode side keeps the cache compressed. Moves ttft_p90_ms."""

from bench import spans


def read(ctx):
    return spans.ratio(ctx.counters, "transfer_host_reads", "transfer_calls")
