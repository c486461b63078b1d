"""moe.expert_roofline: share of its roofline reached by the grouped expert
kernel (the family's ``expert_kernel``, two calls per MoE layer and token
block: gate and up, then down) in the traced batch. FLOPs and bytes are the
family's ``expert_kernel_step`` for a batch's routed pairs and touched
experts, the program's counters (``EngineStats.moe_pairs``,
``moe_experts_touched``) over the run's batches (one prefill each); time:
the device durations of the kernel's events. Nothing to read where the
program has no such kernel or counters. Moves tpot_ms."""

from bench import counts
from bench.trace import matcher


def read(ctx):
    kernel = getattr(ctx.fam, "expert_kernel", None)
    batches = ctx.counters.get("prefill_calls", 0)
    if kernel is None or "moe_pairs" not in ctx.counters or not batches:
        return None
    t, _ = ctx.trace.op_time(matcher(kernel))
    if not t:
        return None
    traced = sum(b.traced for b in ctx.batches)
    flops, nbytes = ctx.fam.expert_kernel_step(
        ctx.conf, ctx.counters["moe_pairs"] / batches * traced,
        ctx.counters["moe_experts_touched"] / batches * traced)
    least, _ = counts.roofline_s(flops, nbytes, ctx.peak)
    return 100.0 * least / t
