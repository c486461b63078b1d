"""engine.prefill_ms: host time of ``engine.prefill`` (span ``bench.prefill``,
ended by ``block_until_ready``), mean per batch over the window's batches
that ran without the profiler. Moves ttft_p90_ms."""

STAGE = "prefill"


def read(ctx):
    plain = [b for b in ctx.batches if not b.traced] or ctx.batches
    return sum(b.times[STAGE] for b in plain) / len(plain) * 1e3
