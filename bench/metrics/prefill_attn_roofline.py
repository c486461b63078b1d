"""prefill_attn_roofline: share of its roofline reached by the fused prefill
attention kernel (``flash_attention``) in the traced batches.

FLOPs: the family's own causal attention count at unpadded widths. Every
family's ``prefill_flops`` is linear in the sequence plus a term per
(query, key <= query) pair, so its second difference in the sequence is the
per-pair term (batch and layers included); times S(S+1)/2 pairs. Bytes: q +
k + v + o in bf16, each layer: 2·B·S·(H + Hkv)·(d + dv), where the per-pair
term of one row and layer is 2·H·(d + dv). Time: the device durations of the
kernel's events. Nothing to read where the kernel did not run. Moves
ttft_p90_ms."""

from bench import counts
from bench.trace import matcher

KERNEL = "flash_attention"


def pair_flops(fam, conf, batch: int, seq: int) -> float:
    """FLOPs of one (query, key) pair over the batch and the layers."""
    f = lambda s: fam.prefill_flops(conf, batch, s)
    return f(seq + 1) - 2 * f(seq) + f(seq - 1)


def prefill_attention(fam, conf, batch: int, seq: int):
    """(FLOPs, bytes) of one prefill's attention, all layers."""
    per_pair = pair_flops(fam, conf, batch, seq)
    flops = per_pair * seq * (seq + 1) / 2
    heads_ratio = conf["num_key_value_heads"] / conf["num_attention_heads"]
    nbytes = seq * per_pair * (1 + heads_ratio)
    return flops, nbytes


def read(ctx):
    t, _ = ctx.trace.op_time(matcher(KERNEL))
    if not t:
        return None
    traced = sum(b.traced for b in ctx.batches)
    flops, nbytes = prefill_attention(ctx.fam, ctx.conf, ctx.gen.batch,
                                      ctx.gen.prompt_tokens)
    least, _ = counts.roofline_s(traced * flops, traced * nbytes, ctx.peak)
    return 100.0 * least / t
