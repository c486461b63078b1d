"""The mixture-of-experts layer at a chip's share of the experts.

At tiny sizes on the CPU: the grouped-matmul kernel (interpret mode) against
its XLA product; the router against a plain loop; the layer against its
dense reference; the held shares against the uncut layer; the dense prefix
and the routing counts through the engine.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, ShapeConfig, get_config
from repro.kernels.grouped_matmul import grouped_matmul_ref, moe_grouped_matmul
from repro.models import model as M
from repro.models import moe as MOE

D = 64
SOFTMAX = MoEConfig(num_experts=8, top_k=2, d_ff_expert=32)
# DeepSeek-V3's router at a small size: 16 experts in 4 groups, the best 2
# groups kept, 4 experts a token, one shared expert
SIGMOID = MoEConfig(num_experts=16, top_k=4, d_ff_expert=32, n_shared=1,
                    scoring="sigmoid", n_group=4, topk_group=2,
                    routed_scale=2.5)
CONFIGS = {"softmax": SOFTMAX, "sigmoid": SIGMOID}


def _x(t=24, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, t // 2, D),
                             jnp.float32).astype(jnp.bfloat16)


@pytest.mark.parametrize("glu", [False, True])
@pytest.mark.parametrize("sizes", [(3, 0, 20, 9), (0, 0, 0, 0), (1, 0, 0, 1),
                                   (40, 0, 0, 0)],
                         ids=["mixed", "none", "ends", "one"])
def test_kernel_matches_xla_product(sizes, glu):
    """Exact up to the bf16 rounding of the output: both accumulate in f32
    over the same products (one ulp of slack)."""
    rng = np.random.default_rng(1)
    m, k, n = 40, 128, 256
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(4, k, n)) / np.sqrt(k), jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    got = moe_grouped_matmul(lhs, rhs, gs, glu=glu, interpret=True)
    want = grouped_matmul_ref(lhs, rhs, gs, glu=glu)
    rows = int(gs.sum())
    np.testing.assert_allclose(np.asarray(got[:rows], np.float32),
                               np.asarray(want[:rows], np.float32),
                               rtol=2 ** -7, atol=2 ** -7)


def _route_loop(p, x, cfg):
    """The router as a plain per-token numpy loop."""
    x = np.asarray(x, np.float64).reshape(-1, D)
    logits = x @ np.asarray(p["router"], np.float64)
    bias = np.asarray(p.get("router_bias", np.zeros(cfg.num_experts)))
    out = []
    for row in logits:
        if cfg.scoring == "sigmoid":
            s = 1 / (1 + np.exp(-row))
        else:
            s = np.exp(row - row.max()) / np.exp(row - row.max()).sum()
        c = s + bias
        per = cfg.num_experts // cfg.n_group
        groups = sorted(range(cfg.n_group), key=lambda g: -sum(
            sorted(c[g * per:(g + 1) * per])[-2:]))[:cfg.topk_group]
        allowed = [e for e in range(cfg.num_experts) if e // per in groups]
        top = sorted(allowed, key=lambda e: -c[e])[:cfg.top_k]
        w = s[top] / s[top].sum() * cfg.routed_scale
        out.append(dict(zip(top, w)))
    return out


@pytest.mark.parametrize("scoring", sorted(CONFIGS))
def test_router_matches_a_plain_loop(scoring):
    cfg = CONFIGS[scoring]
    p = MOE.init_moe(jax.random.PRNGKey(2), D, cfg)
    if "router_bias" in p:      # a bias wide enough to move the choice
        p["router_bias"] = jax.random.normal(jax.random.PRNGKey(9), (16,)) * 0.1
    x = _x()
    w, idx, _ = MOE.route(p, x.reshape(-1, D), cfg)
    for want, ws, es in zip(_route_loop(p, x, cfg), np.asarray(w),
                            np.asarray(idx)):
        assert set(es.tolist()) == set(want)
        for e, v in zip(es, ws):
            assert v == pytest.approx(want[e], rel=1e-5)


@pytest.mark.parametrize("scoring", sorted(CONFIGS))
@pytest.mark.parametrize("share", [(None, 0), (4, 4)], ids=["all", "held4"])
def test_layer_matches_dense_reference(scoring, share):
    """The sorted, kernel-computed layer against every held expert on every
    token, masked: the same bf16 products summed in another order (bf16
    output: within 2% of the largest output)."""
    held, off = share
    cfg = dataclasses.replace(CONFIGS[scoring], num_held=held,
                              expert_offset=off)
    p = MOE.init_moe(jax.random.PRNGKey(3), D, cfg)
    x = _x(t=48)
    got, stats = MOE.moe_ffn(p, x, cfg)
    want = MOE.moe_ffn_dense_ref(p, x, cfg)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= 0.02 * np.abs(want).max()
    _, idx, _ = MOE.route(p, x.reshape(-1, D), cfg)
    local = np.asarray(idx) - off
    mine = (local >= 0) & (local < cfg.held)
    assert int(stats["pairs"]) == int(mine.sum())
    assert int(stats["touched"]) == len(set(local[mine].tolist()))


def test_token_blocks_give_the_one_block_result(monkeypatch):
    """A prefill cut into token blocks (bounded buffers) computes what one
    block does, and counts a block's touched experts once a block."""
    cfg = SIGMOID
    p = MOE.init_moe(jax.random.PRNGKey(4), D, cfg)
    x = _x(t=64)
    whole, st1 = MOE.moe_ffn(p, x, cfg)
    monkeypatch.setattr(MOE, "TEMP_BYTES", 16 * MOE.token_bytes(D, cfg))
    assert MOE.block_tokens(64, D, cfg) == 16
    blocked, st4 = MOE.moe_ffn(p, x, cfg)
    np.testing.assert_array_equal(np.asarray(whole, np.float32),
                                  np.asarray(blocked, np.float32))
    assert int(st4["pairs"]) == int(st1["pairs"])
    assert int(st4["touched"]) >= int(st1["touched"])


def test_shares_sum_to_the_uncut_layer():
    """Four chips' shares of 16 experts: their routed parts, with the shared
    expert counted once, add up to the layer that holds every expert."""
    cfg = SIGMOID
    key = jax.random.PRNGKey(5)
    x = _x(t=48)
    uncut = MOE.moe_ffn_dense_ref(MOE.init_moe(key, D, cfg), x, cfg)
    total = 0.0
    for off in range(0, 16, 4):
        share = dataclasses.replace(cfg, num_held=4, expert_offset=off)
        p = MOE.init_moe(key, D, share)
        got, _ = MOE.moe_ffn(p, x, share)
        total = total + got.astype(jnp.float32)
    shared = M.L.mlp(p["shared"], x).astype(jnp.float32)
    total = total - 3 * shared
    want = np.asarray(uncut, np.float32)
    # four bf16 roundings of partial sums against one of the whole
    assert np.abs(np.asarray(total) - want).max() <= 0.03 * np.abs(want).max()


def _tiny_deepseek():
    return ArchConfig(
        name="tiny-ds", family="moe", num_layers=3, d_model=D, num_heads=4,
        num_kv_heads=4, d_ff=96, vocab_size=128, head_dim=24,
        mla=MLAConfig(q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=24),
        moe=dataclasses.replace(SIGMOID, num_held=4, first_k_dense=1))


def test_dense_prefix_params_and_param_count():
    cfg = _tiny_deepseek()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.leaves(params["dense_layers"])[0].shape[0] == 1
    assert params["layers"]["ffn"]["w_gate_up"].shape == (2, 4, D, 64)
    assert params["dense_layers"]["ffn"]["w_gate"].shape == (1, D, 96)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    norms = 3 * (2 * D + 32 + 32) + D           # layer norms, final norm
    assert cfg.param_count() == n - norms


@pytest.mark.parametrize("arch", ["qwen3-moe-30b-a3b", "tiny-ds"])
def test_engine_counts_routed_pairs(arch):
    """The jitted prefill and decode count their routed pairs on the device,
    in the states they return; reading ``stats`` reads them once, prefill
    and decode together and the decode's part on its own."""
    from repro.core.codebook import DEFAULT_BF16_CODEBOOK
    from repro.serving.decode import make_decode_fn
    from repro.serving.engine import DisaggregatedEngine
    from repro.serving.prefill import make_prefill_fn
    cfg = (_tiny_deepseek() if arch == "tiny-ds"
           else get_config(arch).reduced())
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    batch = M.make_inputs(cfg, ShapeConfig("s", 12, 2, "train"), seq=12)
    prompt = {"tokens": batch["tokens"]}
    eng = DisaggregatedEngine(cfg, params, DEFAULT_BF16_CODEBOOK)
    pre = eng.prefill(prompt, max_seq=16)
    eng.decode(pre.first_token, eng.transfer(pre.state), 3)
    want = make_prefill_fn(cfg, 16)(params, prompt)
    _, final = make_decode_fn(cfg, 3)(params, want.first_token, want.state)
    pre_counts = {k: int(v) for k, v in want.state.moe.items()}
    # the final state holds the prefill's counts and the decode's on top
    dec = {k: int(v) - pre_counts[k] for k, v in final.moe.items()}
    pairs, dec_pairs = pre_counts["moe_pairs"], dec["moe_pairs"]
    st = eng.stats
    assert st.moe_decode_pairs == dec_pairs > 0
    assert st.moe_decode_experts_touched == dec["moe_experts_touched"]
    assert st.moe_pairs == pairs + dec_pairs
    assert st.moe_experts_touched == pre_counts["moe_experts_touched"] \
        + st.moe_decode_experts_touched
    # 3 steps of 2 tokens through the MoE layers, a pair per held pick
    moe_layers = cfg.num_layers - cfg.moe.first_k_dense
    assert dec_pairs <= 3 * 2 * moe_layers * min(cfg.moe.top_k, cfg.moe.held)
    assert eng.stats.moe_pairs == st.moe_pairs      # read once


def test_counts_only_where_the_state_keeps_them():
    """A model without MoE keeps no counts; an MoE state built by hand (no
    counts) decodes the same tokens and keeps none; a transfer passes the
    counts on."""
    from repro.core.codebook import DEFAULT_BF16_CODEBOOK
    from repro.serving.decode import decode_loop
    from repro.serving.engine import DisaggregatedEngine
    dense = get_config("smollm-135m").reduced()
    p = M.init_params(dense, jax.random.PRNGKey(1))
    toks = M.make_inputs(dense, ShapeConfig("s", 8, 2, "train"),
                         seq=8)["tokens"]
    _, st = M.prefill(p, {"tokens": toks}, dense, max_seq=12)
    assert st.moe is None
    assert M.decode_step(p, toks[:, :1], st, dense)[1].moe is None

    cfg = _tiny_deepseek()
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    toks = M.make_inputs(cfg, ShapeConfig("s", 8, 2, "train"),
                         seq=8)["tokens"]
    _, st = M.prefill(params, {"tokens": toks}, cfg, max_seq=12)
    assert int(st.moe["moe_pairs"]) > 0
    eng = DisaggregatedEngine(cfg, params, DEFAULT_BF16_CODEBOOK)
    assert eng.transfer(st).moe is st.moe
    first = toks[:, -1]
    counted, end = decode_loop(params, first, st, cfg, 3)
    bare, end_bare = decode_loop(
        params, first, dataclasses.replace(st, moe=None), cfg, 3)
    assert np.array_equal(np.asarray(counted), np.asarray(bare))
    assert end_bare.moe is None
    assert int(end.moe["moe_pairs"]) > int(st.moe["moe_pairs"])


def test_dense_prefix_and_moe_stack_share_one_cache():
    """The latent cache is one leaf set over both stacks: a compressed-
    resident pool admits it whole, and decoding over its pages (page tables
    and tails cut between the stacks) follows the raw decode's logits."""
    from repro.launch.serve import calibrate_on_model
    from repro.models.kvpool import ResidentState
    from repro.serving.engine import DisaggregatedEngine
    cfg = _tiny_deepseek()
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    prompt = {"tokens": M.make_inputs(cfg, ShapeConfig("s", 16, 2, "train"),
                                      seq=16)["tokens"]}
    eng = DisaggregatedEngine(cfg, params, calibrate_on_model(cfg, params),
                              resident="compressed")
    tp = eng.resident_tokens_per_page()
    pre = eng.prefill(prompt, max_seq=-(-24 // tp) * tp)
    assert pre.state.cache["ckv"].shape[0] == cfg.num_layers
    st_res = eng.transfer(pre.state)
    assert isinstance(st_res, ResidentState)
    st_raw = pre.state
    rng = np.random.default_rng(6)
    for step in range(4):
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 1)), jnp.int32)
        a, st_raw = M.decode_step(params, tok, st_raw, cfg)
        b, st_res = M.resident_decode_step(params, tok, st_res, cfg)
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # the paged kernel accumulates in f32 where the raw decode rounds
        # probabilities to bf16: 1-2% of the largest logit here; a layer
        # reading another layer's pages would be off by the whole scale
        assert np.abs(a - b).max() < 0.05 * np.abs(a).max(), step
        st_res = eng._pool.flush_full_tails(st_res)
