"""Plain float32 reference of a pre-norm MLA mixture-of-experts transformer
(DeepSeek-V3 layout), at one chip's share of the routed experts.

Attention is multi-head latent attention in its expanded form, as in
``mla.py``, with the value head ``v_head_dim`` wide and YaRN RoPE on the rope
parts (DeepSeek-V3's ``yarn_get_mscale`` and correction range): the rotary
frequencies are blended between theta^(-2i/d) and that over ``factor``,
across the dimensions that turn between ``beta_fast`` and ``beta_slow`` times
in ``original_max_position_embeddings`` positions; the softmax scale is
(nope + rope)^-1/2 times mscale^2, mscale = 0.1 ln(factor) + 1.

The first ``first_k_dense_replace`` layers have a dense SwiGLU MLP. The
others have the routed experts held here, ``n_routed_experts`` of
``router_experts`` from ``held_expert_offset``, and the shared experts:
  s = sigmoid(h W_router) over every expert; the choice adds the correction
  bias b; each of the ``n_group`` groups scores the sum of its two best
  choices, the ``topk_group`` best groups stay; the ``num_experts_per_tok``
  best choices inside them are the token's experts; their weights are their
  s, divided by the sum and times ``routed_scaling_factor``;
  y = sum over held experts e of weight_e * SwiGLU_e(h) + SwiGLU_shared(h),
computed as a loop over the held experts, every token through each, with
the weight zero where the token did not pick it. Experts held elsewhere add
nothing, as in the program.

Weights follow the program's recipe, drawn from ``seeds.key(seed,
"weights")``: the embedding, the head and the layer keys as in ``gqa.py``;
a layer key splits in two: the first six ways into W_qa (d^-1/2), W_qb
(q_lora^-1/2), W_kva (d^-1/2), W_kvb (kv_lora^-1/2) and W_o ((H v)^-1/2); the
second, for a dense layer, three ways into W_gate, W_up (d^-1/2) and W_down
(d_ff^-1/2); for an MoE layer five ways into the router (d^-1/2, float32),
the experts' [W_gate | W_up] (d^-1/2) and W_down (f^-1/2), each expert e from
``fold_in(part, e)``, the correction bias (float32, N(0, std^2), std the
configuration's ``assumed.e_score_correction_bias_std``) and the shared
experts (three ways, as a dense MLP of width f * n_shared).
``layer_weights`` draws both FFN kinds and says which the layer has: the
common loop draws every layer through one traced function of the layer's
index.

Also the counts of the served programs and of the expert kernel. The routed
experts' work depends on the routing: it counts only where the caller gives
the routed pairs and touched experts (the program's counters), else none of
it, so a count never exceeds what the program must do.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import gqa
from bench.reference.common import causal_attention, draw, mm, rms_norm

embed = gqa.embed
head = gqa.head
expert_kernel = "moe_grouped_matmul"


def _sizes(c):
    return (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["intermediate_size"])


def _moe_sizes(c):
    """(held, router width, per token, expert width, shared, offset)."""
    return (c["n_routed_experts"], c["router_experts"],
            c["num_experts_per_tok"], c["moe_intermediate_size"],
            c["n_shared_experts"], c.get("held_expert_offset", 0))


# -- YaRN ---------------------------------------------------------------------

def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(c) -> np.ndarray:
    rp, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, rp, 2, dtype=np.float64) / rp)
    rs = c.get("rope_scaling")
    if not rs:
        return extra
    f, orig = rs["factor"], rs["original_max_position_embeddings"]

    def dim(rot):
        return rp * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(dim(rs["beta_fast"])), 0)
    hi = min(math.ceil(dim(rs["beta_slow"])), rp - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(rp // 2) - lo) / (hi - lo), 0, 1)
    return extra / f * ramp + extra * (1 - ramp)


def softmax_scale(c) -> float:
    s = 1.0 / math.sqrt(c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
    rs = c.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        s *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def rope(x, pos, c):
    """Rotate the two halves of the last axis; x (T, H, D), pos (T,)."""
    rs = c.get("rope_scaling") or {}
    m = (_mscale(rs["factor"], rs.get("mscale", 1))
         / _mscale(rs["factor"], rs.get("mscale_all_dim", 1))) if rs else 1.0
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv_freq(c),
                                                         jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :] * m, jnp.sin(ang)[:, None, :] * m
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# -- weights ------------------------------------------------------------------

def _mlp_weights(key, d, ff):
    m = jax.random.split(key, 3)
    return {"w_gate": draw(m[0], (d, ff), d ** -0.5),
            "w_up": draw(m[1], (d, ff), d ** -0.5),
            "w_down": draw(m[2], (ff, d), ff ** -0.5)}


def layer_weights(c, wkey, i):
    d, h, qr, r, nope, rp, v, ff = _sizes(c)
    held, e, _, f, shared, off = _moe_sizes(c)
    ks = jax.random.split(wkey, 8)
    lk = jax.random.split(ks[3], c["num_hidden_layers"])[i]
    k_attn, k_ffn = jax.random.split(lk)
    a = jax.random.split(k_attn, 6)
    m = jax.random.split(k_ffn, 5)
    s = d ** -0.5
    experts = [(draw(jax.random.fold_in(m[1], x), (d, 2 * f), s),
                draw(jax.random.fold_in(m[2], x), (f, d), f ** -0.5))
               for x in range(off, off + held)]
    return {
        "wq_a": draw(a[0], (d, qr), s),
        "wq_b": draw(a[1], (qr, h, nope + rp), qr ** -0.5),
        "wkv_a": draw(a[2], (d, r + rp), s),
        "wkv_b": draw(a[3], (r, h, nope + v), r ** -0.5),
        "wo": draw(a[4], (h, v, d), (h * v) ** -0.5),
        "is_moe": i >= c["first_k_dense_replace"],
        "mlp": _mlp_weights(k_ffn, d, ff),
        "router": jax.random.normal(m[0], (d, e)) * s,
        "bias": jax.random.normal(m[3], (e,))
        * float(c["assumed"]["e_score_correction_bias_std"]),
        "experts": [{"w_gate": gu[:, :f], "w_up": gu[:, f:], "w_down": dn}
                    for gu, dn in experts],
        "shared": _mlp_weights(m[4], d, f * shared),
    }


# -- the forward ----------------------------------------------------------------

def _swiglu(w, x, mode):
    g = mm("td,df->tf", x, w["w_gate"], mode)
    u = mm("td,df->tf", x, w["w_up"], mode)
    return mm("tf,fd->td", jax.nn.silu(g) * u, w["w_down"], mode)


def attention(c, w, x, mode):
    """The MLA block's output for one row: x (T, d) float32."""
    d, h, qr, r, nope, rp, v, ff = _sizes(c)
    eps = c["rms_norm_eps"]
    t = x.shape[0]
    pos = jnp.arange(t)
    hn = rms_norm(x, 1.0, eps)
    q = mm("tr,rhk->thk", rms_norm(mm("td,dr->tr", hn, w["wq_a"], mode), 1.0,
                                   eps), w["wq_b"], mode)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, c)], -1)
    kv = mm("td,dr->tr", hn, w["wkv_a"], mode)
    latent = rms_norm(kv[:, :r], 1.0, eps)
    k_rope = rope(kv[:, None, r:], pos, c)                      # (T, 1, rp)
    kvb = mm("tr,rhk->thk", latent, w["wkv_b"], mode)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_rope, (t, h, rp))], -1)
    o = causal_attention(q, k, kvb[..., nope:], softmax_scale(c), mode)
    return mm("thv,hvd->td", o, w["wo"], mode)


def routing(c, w, h, mode):
    """(T, held) weight of each held expert for each token (0 where the
    token did not pick it)."""
    held, e, k, _, _, off = _moe_sizes(c)
    g, kg = c["n_group"], c["topk_group"]
    s = jax.nn.sigmoid(mm("td,de->te", h, w["router"], mode))
    choice = s + w["bias"]
    best_two = jnp.sort(choice.reshape(-1, g, e // g), axis=-1)[..., -2:]
    group_rank = jnp.argsort(-best_two.sum(-1), axis=-1)        # (T, G)
    kept = jnp.zeros(group_rank.shape, bool).at[
        jnp.arange(h.shape[0])[:, None], group_rank[:, :kg]].set(True)
    choice = jnp.where(jnp.repeat(kept, e // g, axis=1), choice, -jnp.inf)
    top = jnp.argsort(-choice, axis=-1)[:, :k]
    wt = jnp.take_along_axis(s, top, axis=-1)
    wt = wt / wt.sum(-1, keepdims=True) * c["routed_scaling_factor"]
    return jnp.stack([jnp.sum(jnp.where(top == off + x, wt, 0.0), -1)
                      for x in range(held)], -1)


def moe(c, w, h, mode):
    coef = routing(c, w, h, mode)
    y = _swiglu(w["shared"], h, mode)
    for x, ew in enumerate(w["experts"]):
        y = y + coef[:, x:x + 1] * _swiglu(ew, h, mode)
    return y


def layer(c, w, x, *, mode):
    """One layer over one row: x (T, d) float32."""
    x = x + attention(c, w, x, mode)
    h2 = rms_norm(x, 1.0, c["rms_norm_eps"])
    return x + jax.lax.cond(w["is_moe"], lambda: moe(c, w, h2, mode),
                            lambda: _swiglu(w["mlp"], h2, mode))


# -- counts -------------------------------------------------------------------

def attn_params(c) -> int:
    d, h, qr, r, nope, rp, v, _ = _sizes(c)
    return (d * qr + qr * h * (nope + rp) + d * (r + rp)
            + r * h * (nope + v) + h * v * d)


def expert_params(c) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_params(c, dense: bool = False) -> int:
    """Parameters of a dense-prefix layer, or of an MoE layer as held here
    (its experts, the shared experts, the router and its bias)."""
    d = c["hidden_size"]
    if dense:
        return attn_params(c) + 3 * d * c["intermediate_size"]
    held, e, _, _, shared, _ = _moe_sizes(c)
    return attn_params(c) + (held + shared) * expert_params(c) + d * e + e


def _layers(c):
    k = c["first_k_dense_replace"]
    return k, c["num_hidden_layers"] - k


def weight_bytes(c, touched: float = 0) -> float:
    """Bytes a decode step must read: every dense-prefix layer, each MoE
    layer's attention, shared experts and float32 router, the routed experts
    the step touched (``touched``, summed over MoE layers), the norms and
    the head. Without ``touched`` no routed expert counts."""
    d, _, qr, r = _sizes(c)[:4]
    held, e, _, _, shared, _ = _moe_sizes(c)
    k, n_moe = _layers(c)
    norms = 2 * d + qr + r
    bf16 = (k * (layer_params(c, dense=True) + norms)
            + n_moe * (attn_params(c) + shared * expert_params(c) + norms)
            + touched * expert_params(c) + d * c["vocab_size"] + d)
    return 2 * bf16 + 4 * n_moe * (d * e + e)


def kv_bytes_per_token(c) -> int:
    """Raw bf16 latent-cache bytes of one token over all layers."""
    return 2 * c["num_hidden_layers"] * (c["kv_lora_rank"]
                                         + c["qk_rope_head_dim"])


def _token_flops(c) -> float:
    """Products of one token through the layers, without attention scores
    and routed experts (the attention in its expanded form)."""
    d = c["hidden_size"]
    _, e, _, _, shared, _ = _moe_sizes(c)
    k, n_moe = _layers(c)
    return 2 * (k * layer_params(c, dense=True)
                + n_moe * (attn_params(c) + shared * expert_params(c) + d * e))


def prefill_flops(c, batch: int, seq: int, pairs: float = 0) -> float:
    """Useful FLOPs of a prefill: the layers' products, causal attention once
    per (query, key <= query) pair, the head at the last position, and the
    routed experts' products for ``pairs`` routed (token, held expert)
    pairs (over the batch and the MoE layers; none without them)."""
    d, h, qr, r, nope, rp, v, ff = _sizes(c)
    causal = seq * (seq + 1) / 2
    per_row = (_token_flops(c) * seq
               + c["num_hidden_layers"] * 2 * h * (nope + rp + v) * causal
               + 2 * d * c["vocab_size"])
    return batch * per_row + pairs * 2 * expert_params(c)


def attention_flops(c, rows: int, tokens: int) -> float:
    """Scores over latent + rope and the latent context, one layer."""
    h, r, rp = (c["num_attention_heads"], c["kv_lora_rank"],
                c["qk_rope_head_dim"])
    return rows * 2 * h * (r + rp + r) * tokens


def decode_step(c, batch: int, ctx: int, pairs: float = 0,
                touched: float = 0):
    """(FLOPs, bytes) of an absorbed-form decode step over ``ctx`` latent
    tokens a row: the query, latent and output products, W_kvb absorbed into
    query and output, scores, the FFNs and the head; the weights it must read
    once (``weight_bytes``) and the live latent cache. The routed experts
    count through ``pairs`` and ``touched`` (the step's, over the MoE
    layers), and not at all without them."""
    d, h, qr, r, nope, rp, v, ff = _sizes(c)
    _, e, _, _, shared, _ = _moe_sizes(c)
    k, n_moe = _layers(c)
    attn = 2 * (d * qr + qr * h * (nope + rp) + d * (r + rp)
                + h * nope * r + h * r * v + h * v * d) \
        + attention_flops(c, 1, ctx)
    per_row = (c["num_hidden_layers"] * attn
               + 2 * k * 3 * d * ff
               + 2 * n_moe * (shared * expert_params(c) + d * e)
               + 2 * d * c["vocab_size"])
    flops = batch * per_row + pairs * 2 * expert_params(c)
    return flops, weight_bytes(c, touched) + batch * ctx * kv_bytes_per_token(c)


def expert_kernel_step(c, pairs: float, touched: float):
    """(FLOPs, bytes) the grouped expert products need for ``pairs`` routed
    pairs over ``touched`` (expert, call) weight loads: each pair's gate, up
    and down products; each touched expert's weights read once a call, each
    pair's row read and its hidden written (gate and up), that hidden read
    and the pair's output row written (down), all bf16."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    return (pairs * 2 * expert_params(c),
            touched * 2 * expert_params(c) + pairs * 4 * (d + f))


# -- the paged kernel of the compressed-resident decode ---------------------

paged_kernel = "paged_mla_attention"


def paged_dims(c):
    """(heads, query width, value width) of the paged kernel in its absorbed
    form: scores over the latent and rope pages, context over the latent."""
    r = c["kv_lora_rank"]
    return c["num_attention_heads"], r + c["qk_rope_head_dim"], r
