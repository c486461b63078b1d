"""mfu.prefill: useful prefill FLOPs of the traced batches (the family's
``prefill_flops``: products, causal attention once per pair, the head at
the last position) over the device's busy time inside the prefill and
transfer spans times the chip's bf16 peak: the whole TTFT path's share of
the peak. Moves ttft_p90_ms."""


def read(ctx):
    t = ctx.trace.busy_in(["prefill", "transfer"])
    if not t:
        return None
    traced = sum(b.traced for b in ctx.batches)
    flops = traced * ctx.fam.prefill_flops(ctx.conf, ctx.gen.batch,
                                           ctx.gen.prompt_tokens)
    return 100.0 * flops / (t * ctx.peak["bf16_flops_per_s"])
