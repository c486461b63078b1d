"""engine.transfer_ms: host time of ``engine.transfer`` (span
``bench.transfer``) with a raw-resident decode side: encode, ship and decode
of the cache. Mean per batch over the window's batches that ran without the
profiler. Moves ttft_p90_ms."""


def read(ctx):
    if ctx.mix["resident"] != "raw":
        return None
    plain = [b for b in ctx.batches if not b.traced] or ctx.batches
    return sum(b.times["transfer"] for b in plain) / len(plain) * 1e3
