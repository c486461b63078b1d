"""codec.encode_roofline: share of the HBM roofline reached by the fused
SplitZip encode kernel in the traced batches. Bytes: every element of every
cache leaf encoded once per batch (``counts.encode_bytes``); time: the
device durations of the kernel's events. Moves ttft_p90_ms."""

from bench import counts
from bench.trace import matcher

KERNEL = matcher("encode_fused")


def read(ctx):
    t, _ = ctx.trace.op_time(KERNEL)
    if not t:
        return None
    traced = sum(b.traced for b in ctx.batches)
    nbytes = traced * sum(counts.encode_bytes(n, chunk=ctx.chunk, cap=ctx.cap)
                          for n in ctx.cache_elems)
    return 100.0 * nbytes / ctx.peak["hbm_bytes_per_s"] / t
