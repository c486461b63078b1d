"""paged_attn_roofline: share of its roofline reached by the paged attention
kernel over compressed pages (``paged_gqa_attention``, one call per layer
and decode step) in the traced batches. FLOPs and bytes come from the pages
in use at each step (``counts.paged_attention_step``): a row with cache
length c reads c // tokens_per_page full pages of K and V. Time: the device
durations of the kernel's events. Moves tpot_ms."""

from bench import counts
from bench.trace import matcher

KERNEL = matcher("paged_gqa_attention")


def read(ctx):
    g = ctx.geom
    t, events = ctx.trace.op_time(KERNEL)
    if g is None or not t or ctx.conf["family"] != "gqa":
        return None
    c, gen = ctx.conf, ctx.gen
    kv_page = sum(counts.page_bytes(lg.page_elems, lg.escape_cap)
                  for lg in g.leaves)
    layers = c["num_hidden_layers"]
    steps = events // layers                  # steps the kernel ran
    per_batch = gen.new_tokens
    flops = nbytes = 0.0
    for k in range(steps):
        f, b = counts.paged_attention_step(
            rows=gen.batch, heads=c["num_attention_heads"],
            head_dim=c["head_dim"], dv=c["head_dim"],
            full_pages=[(gen.prompt_tokens + k % per_batch)
                        // g.tokens_per_page] * gen.batch,
            tokens_per_page=g.tokens_per_page, page_bytes_kv=kv_page)
        flops += f * layers
        nbytes += b * layers
    least, _ = counts.roofline_s(flops, nbytes, ctx.peak)
    return 100.0 * least / t
