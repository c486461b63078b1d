"""paged_attn_roofline: share of its roofline reached by the paged attention
kernel over compressed pages (one call per layer and decode step) in the
traced batches. The kernel's name and widths are the family's
(``paged_kernel`` and ``paged_dims`` in ``bench/reference/<family>.py``).
FLOPs and bytes come from the pages in use at each step
(``counts.paged_attention_step``): a row with cache length c reads
c // tokens_per_page full pages of every paged leaf. Time: the device
durations of the kernel's events. Moves resident_tpot_ms."""

from bench import counts
from bench.trace import matcher


def read(ctx):
    g, fam = ctx.geom, ctx.fam
    kernel = getattr(fam, "paged_kernel", None)
    if g is None or kernel is None:
        return None
    t, events = ctx.trace.op_time(matcher(kernel))
    if not t:
        return None
    c, gen = ctx.conf, ctx.gen
    heads, q_dim, v_dim = fam.paged_dims(c)
    page = sum(counts.page_bytes(lg.page_elems, lg.escape_cap)
               for lg in g.leaves)
    layers = c["num_hidden_layers"]
    steps = events // layers                  # steps the kernel ran
    per_batch = gen.new_tokens
    flops = nbytes = 0.0
    for k in range(steps):
        f, b = counts.paged_attention_step(
            rows=gen.batch, heads=heads, head_dim=q_dim, dv=v_dim,
            full_pages=[(gen.prompt_tokens + k % per_batch)
                        // g.tokens_per_page] * gen.batch,
            tokens_per_page=g.tokens_per_page, page_bytes_kv=page)
        flops += f * layers
        nbytes += b * layers
    least, _ = counts.roofline_s(flops, nbytes, ctx.peak)
    return 100.0 * least / t
