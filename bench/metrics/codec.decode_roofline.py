"""codec.decode_roofline: share of the HBM roofline reached by the fused
SplitZip decode kernel in the traced batches (the decode side turning the
received streams back into the raw cache). Bytes from
``counts.decode_bytes`` over every cache leaf once per batch; time: the
device durations of the kernel's events. Moves ttft_p90_ms."""

from bench import counts
from bench.trace import matcher

KERNEL = matcher("decode_fused")


def read(ctx):
    t, _ = ctx.trace.op_time(KERNEL)
    if not t or ctx.mix["resident"] != "raw":
        return None
    traced = sum(b.traced for b in ctx.batches)
    nbytes = traced * sum(counts.decode_bytes(n, chunk=ctx.chunk, cap=ctx.cap)
                          for n in ctx.cache_elems)
    return 100.0 * nbytes / ctx.peak["hbm_bytes_per_s"] / t
