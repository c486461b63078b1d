"""Mixture-of-experts FFN at one chip's share of the experts, dropless.

The router spans every expert the model has (``MoEConfig.num_experts``) and
picks ``top_k`` per token; this chip holds ``MoEConfig.held`` of them, from
``expert_offset``, and computes their part of the result for the tokens routed
to them, plus the shared experts every token passes through:

    y(h) = sum over the token's picks i that are held here: w_i E_i(h)
           + shared(h)

with E_i and ``shared`` SwiGLU MLPs. The layer that holds every expert is the
uncut layer; the parts that all the shares compute add up to it, with the
shared experts counted once. No token is dropped.

Routing (:func:`route`): scores are the softmax (Qwen3-MoE) or the sigmoid
(DeepSeek-V3) of the router's logits. A per-expert correction bias is added
for the choice only. With ``n_group > 1`` the experts fall into groups, each
scored by the sum of its two best biased scores, and only the ``topk_group``
best groups stay in the running (DeepSeek's ``noaux_tc``). The weights of the
``top_k`` picks are their unbiased scores, renormalized to sum to one, times
``routed_scale``.

Expert compute runs over the routed (token, held expert) pairs only: sorted
by expert, gathered, and multiplied in two grouped matmuls
(``kernels/grouped_matmul.py``: SwiGLU's gate and up products, then down).
Its buffers are sized for the most pairs a block of tokens can route here
(each token picks an expert at most once), so a long prefill runs in blocks
of tokens whose buffers stay under ``TEMP_BYTES``. Inside a scan over layers
the held experts' weights stay the whole stack (``STACKED``), and the layer
reads its own through ``p["layer"]`` (:func:`per_layer`).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.distributed.sharding import constrain
from repro.kernels.grouped_matmul import moe_grouped_matmul
from repro.models import layers as L
from repro.models import scanctl

# bytes of the expert buffers of one block of tokens, 1.5 GiB (gathered rows,
# their hidden activations, the products and the f32 combine)
TEMP_BYTES = 3 << 29
HIGHEST = jax.lax.Precision.HIGHEST
# the held experts' weights: the kernel reads them from the layer stack
STACKED = ("w_gate_up", "w_down")


def init_moe(key, d_model: int, cfg: MoEConfig):
    """Router over every expert (with a sigmoid router, its correction bias
    drawn N(0, ``bias_std``^2)); the held experts' weights, each drawn from
    its own key (``fold_in`` of the expert's id), so a share's weights are
    the uncut layer's for the experts it holds."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    e, f = cfg.num_experts, cfg.d_ff_expert
    s_in = d_model ** -0.5
    ids = cfg.expert_offset + jnp.arange(cfg.held)

    def expert(k, shape, scale):
        return jax.vmap(lambda i: (jax.random.normal(
            jax.random.fold_in(k, i), shape) * scale).astype(jnp.bfloat16))(ids)

    p = {
        "router": (jax.random.normal(k1, (d_model, e)) * s_in).astype(jnp.float32),
        "w_gate_up": expert(k2, (d_model, 2 * f), s_in),
        "w_down": expert(k3, (f, d_model), f ** -0.5),
    }
    if cfg.scoring == "sigmoid":
        p["router_bias"] = jax.random.normal(k4, (e,)) * cfg.bias_std
    if cfg.n_shared:
        p["shared"] = L.init_mlp(k5, d_model, f * cfg.n_shared)
    return p


def per_layer(stack):
    """Split a stack of MoE layers' params (leading layer axis) into what a
    scan over the layers slices and the held experts' weights, which the
    layers read whole with their index (``p["layer"]``)."""
    rest = {k: v for k, v in stack.items() if k not in STACKED}
    return rest, {k: stack[k] for k in STACKED}


def route(p, xf: jax.Array, cfg: MoEConfig):
    """(T, D) -> weights (T, k) f32, experts (T, k) int32, scores (T, E)."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"],
                        precision=HIGHEST)
    if cfg.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + p["router_bias"] if "router_bias" in p else scores
    if cfg.n_group > 1:
        t, e = choice.shape
        per = e // cfg.n_group
        grouped = choice.reshape(t, cfg.n_group, per)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)       # (T, G)
        _, keep = jax.lax.top_k(group_score, cfg.topk_group)
        kept = jnp.zeros((t, cfg.n_group), bool).at[
            jnp.arange(t)[:, None], keep].set(True)
        choice = jnp.where(jnp.repeat(kept, per, axis=1), choice, -jnp.inf)
    _, idx = jax.lax.top_k(choice, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20) * cfg.routed_scale
    return w, idx.astype(jnp.int32), scores


def token_bytes(d: int, cfg: MoEConfig) -> int:
    """Expert-buffer bytes a token of a block needs: ``min(top_k, held)``
    rows, each gathered and produced in bf16 (2 x d), its gate, up and
    hidden activations in bf16 (3 x d_ff_expert) and its f32 share of the
    combine (d)."""
    return min(cfg.top_k, cfg.held) * (2 * d * 2 + 3 * cfg.d_ff_expert * 2
                                       + 4 * d)


def block_tokens(t: int, d: int, cfg: MoEConfig) -> int:
    """Tokens per expert block: ``t`` halved while the block's buffers
    would exceed ``TEMP_BYTES``."""
    tb = t
    while tb * token_bytes(d, cfg) > TEMP_BYTES and tb % 2 == 0:
        tb //= 2
    return tb


def _held_block(p, xb, wb, ib, cfg: MoEConfig):
    """The held experts' part for one block of tokens: (tb, D) f32, and the
    block's routed pairs and touched experts."""
    tb, k = ib.shape
    n = cfg.held
    local = ib - cfg.expert_offset
    held = (local >= 0) & (local < n)
    key = jnp.where(held, local, n).reshape(-1)
    rows = tb * min(k, n)                    # the most pairs a block can route
    order = jnp.argsort(key, stable=True)[:rows]
    sizes = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
    tok = order // k
    layer = p.get("layer")
    hid = moe_grouped_matmul(xb[tok], p["w_gate_up"], sizes, layer, glu=True)
    y = moe_grouped_matmul(hid, p["w_down"], sizes, layer)
    pairs = jnp.sum(sizes)
    live = jnp.arange(rows) < pairs           # rows past the pairs are unset
    contrib = jnp.where(live[:, None],
                        y.astype(jnp.float32) * wb.reshape(-1)[order][:, None],
                        0.0)
    out = jnp.zeros((tb, xb.shape[1]), jnp.float32).at[tok].add(contrib)
    return out, pairs, jnp.sum(sizes > 0)


def moe_ffn(p, x: jax.Array, cfg: MoEConfig) -> Tuple[jax.Array, Dict]:
    """x: (B, S, D) -> (out (B, S, D), stats).

    ``stats``: ``balance`` (the Switch load-balancing loss over the router's
    scores), ``pairs`` (routed (token, held expert) pairs computed) and
    ``touched`` (held experts with a pair, summed over token blocks)."""
    b, s, d = x.shape
    t = b * s
    xf = constrain(x.reshape(t, d), "moe_td")
    w, idx, scores = route(p, xf, cfg)
    probs = constrain(scores / scores.sum(-1, keepdims=True), "moe_te")
    top1 = jax.nn.one_hot(idx[:, 0], cfg.num_experts, dtype=jnp.float32)
    balance = cfg.num_experts * jnp.sum(top1.mean(0) * probs.mean(0))

    tb = block_tokens(t, d, cfg)
    if tb == t:
        routed, pairs, touched = _held_block(p, xf, w, idx, cfg)
    else:
        nb = t // tb

        def step(_, blk):
            return None, _held_block(p, *blk, cfg)

        _, (routed, pairs, touched) = scanctl.scan(
            step, None, (xf.reshape(nb, tb, d), w.reshape(nb, tb, -1),
                         idx.reshape(nb, tb, -1)))
        routed = routed.reshape(t, d)
        pairs, touched = jnp.sum(pairs), jnp.sum(touched)
    if "shared" in p:
        routed = routed + L.mlp(p["shared"], x).reshape(t, d).astype(
            jnp.float32)
    out = constrain(routed.astype(x.dtype), "moe_td")
    return out.reshape(b, s, d), {"balance": balance, "pairs": pairs,
                                  "touched": touched}


def moe_ffn_dense_ref(p, x: jax.Array, cfg: MoEConfig) -> jax.Array:
    """O(T·E) reference: every held expert on every token, then the routing
    weights as a mask (and the shared experts). Only for tests at tiny
    sizes."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    w, idx, _ = route(p, xf, cfg)
    local = idx - cfg.expert_offset
    gu = jnp.einsum("td,edf->etf", xf, p["w_gate_up"],
                    preferred_element_type=jnp.float32)
    g, u = jnp.split(gu, 2, axis=-1)
    act = (jax.nn.silu(g) * u).astype(x.dtype)
    y_all = jnp.einsum("etf,efd->etd", act, p["w_down"],
                       preferred_element_type=jnp.float32)       # (E,T,D)
    weights = jnp.sum(jax.nn.one_hot(local, cfg.held) * w[..., None], axis=1)
    yf = jnp.einsum("etd,te->td", y_all, weights)
    if "shared" in p:
        yf = yf + L.mlp(p["shared"], x).reshape(b * s, d).astype(jnp.float32)
    return yf.astype(x.dtype).reshape(b, s, d)
