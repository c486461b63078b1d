"""moe.pairs_per_expert: routed (token, held expert) pairs per held expert,
per MoE layer and per decode step, over the run, from the program's
counters (``EngineStats.moe_decode_pairs`` over the decode steps,
``decode_tokens`` / batch). How far the cell's expert load is from a
deployment's, where other chips' tokens would reach these experts too.
Nothing to read where the program has no such counter. Moves tpot_ms."""


def read(ctx):
    c = ctx.conf
    pairs = ctx.counters.get("moe_decode_pairs")
    steps = ctx.counters.get("decode_tokens", 0) / ctx.gen.batch
    if pairs is None or not steps:
        return None
    moe_layers = c["num_hidden_layers"] - c.get("first_k_dense_replace", 0)
    return pairs / (steps * moe_layers * c["n_routed_experts"])
