"""engine.resident_admit_ms: host time of ``engine.transfer`` with a
compressed-resident decode side (span ``bench.transfer``): encode, ship and
admission of the streams into the paged pool. Mean per batch over the
window's batches that ran without the profiler. Moves output_tokens_per_s."""


def read(ctx):
    if ctx.mix["resident"] != "compressed":
        return None
    plain = [b for b in ctx.batches if not b.traced] or ctx.batches
    return sum(b.times["transfer"] for b in plain) / len(plain) * 1e3
