"""mfu.decode: the decode steps' least time over their device time, in the
traced batches. A step's least time is the larger of its FLOPs over the bf16
peak and its bytes over the HBM bandwidth (``counts.roofline_s``); bytes are
the weights read once and the live cache (raw, or the compressed pages and
raw tails of a resident pool). Device time: busy time inside the decode
spans. The step's share of the chip's peak that bounds it (memory at these
sizes). Moves tpot_ms."""

from bench import counts


def cache_bytes(ctx, ctx_tokens):
    """Live cache bytes of a step: raw, or pages and tails when resident."""
    g, c, gen = ctx.geom, ctx.conf, ctx.gen
    if g is None:
        return gen.batch * ctx_tokens * ctx.fam.kv_bytes_per_token(c)
    full, tail = divmod(ctx_tokens - 1, g.tokens_per_page)
    per_row = sum(full * counts.page_bytes(lg.page_elems, lg.escape_cap)
                  + (tail + 1) * lg.m * 2 for lg in g.leaves)
    return gen.batch * c["num_hidden_layers"] * per_row


def read(ctx):
    t = ctx.trace.busy_in(["decode"])
    if not t:
        return None
    c, gen, fam = ctx.conf, ctx.gen, ctx.fam
    least = 0.0
    traced = sum(b.traced for b in ctx.batches)
    for k in range(gen.new_tokens):
        ctx_tokens = gen.prompt_tokens + k + 1
        flops, _ = fam.decode_step(c, gen.batch, ctx_tokens)
        nbytes = fam.weight_bytes(c) + cache_bytes(ctx, ctx_tokens)
        least += counts.roofline_s(flops, nbytes, ctx.peak)[0]
    return 100.0 * traced * least / t
