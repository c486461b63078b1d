"""Plain float32 reference of a pre-norm MLA transformer (MiniCPM3 layout).

Multi-head latent attention in its expanded form:
  q = W_qb rmsnorm(W_qa h) per head, split into a nope part and a rope part;
  [c, k_r] = W_kva h; c = rmsnorm(c) is the cached latent, k_r (one head,
  rope-rotated) the cached rope key; [k_nope, v] = W_kvb c per head;
  scores (q_nope k_nope + q_rope k_r) / sqrt(nope + rope), causal softmax.
The MLP and the head are the GQA reference's. Departures from the published
MiniCPM3, as in the program: no muP scalings, plain RoPE, latent norms at
eps 1e-6 (see the configuration's notes).

Weights follow the program's recipe, drawn from ``seeds.key(seed,
"weights")``: as in ``gqa.py``, except that the attention part of a layer key
splits six ways into W_qa (d^-1/2), W_qb (q_lora^-1/2), W_kva (d^-1/2), W_kvb
(kv_lora^-1/2) and W_o ((H v)^-1/2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import gqa
from bench.reference.common import causal_attention, draw, mm, rms_norm, rope

embed = gqa.embed
head = gqa.head
LATENT_EPS = 1e-6


def _sizes(c):
    v = c["hidden_size"] // c["num_attention_heads"]
    return (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"], v,
            c["intermediate_size"])


def layer_weights(c, wkey, i):
    d, h, qr, r, nope, rp, v, ff = _sizes(c)
    ks = jax.random.split(wkey, 8)
    lk = jax.random.split(ks[3], c["num_hidden_layers"])[i]
    k_attn, k_mlp = jax.random.split(lk)
    a = jax.random.split(k_attn, 6)
    m = jax.random.split(k_mlp, 3)
    s = d ** -0.5
    return {
        "wq_a": draw(a[0], (d, qr), s),
        "wq_b": draw(a[1], (qr, h, nope + rp), qr ** -0.5),
        "wkv_a": draw(a[2], (d, r + rp), s),
        "wkv_b": draw(a[3], (r, h, nope + v), r ** -0.5),
        "wo": draw(a[4], (h, v, d), (h * v) ** -0.5),
        "w_gate": draw(m[0], (d, ff), s), "w_up": draw(m[1], (d, ff), s),
        "w_down": draw(m[2], (ff, d), ff ** -0.5),
    }


def layer(c, w, x, *, mode):
    """One layer over one row: x (T, d) float32."""
    d, h, qr, r, nope, rp, v, ff = _sizes(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    t = x.shape[0]
    pos = jnp.arange(t)
    hn = rms_norm(x, 1.0, eps)
    q = mm("tr,rhk->thk", rms_norm(mm("td,dr->tr", hn, w["wq_a"], mode), 1.0,
                                   LATENT_EPS), w["wq_b"], mode)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, theta)], -1)
    kv = mm("td,dr->tr", hn, w["wkv_a"], mode)
    latent = rms_norm(kv[:, :r], 1.0, LATENT_EPS)
    k_rope = rope(kv[:, None, r:], pos, theta)                  # (T, 1, rp)
    kvb = mm("tr,rhk->thk", latent, w["wkv_b"], mode)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_rope, (t, h, rp))], -1)
    o = causal_attention(q, k, kvb[..., nope:], 1.0 / np.sqrt(nope + rp),
                         mode)
    x = x + mm("thv,hvd->td", o, w["wo"], mode)
    h2 = rms_norm(x, 1.0, eps)
    g = mm("td,df->tf", h2, w["w_gate"], mode)
    u = mm("td,df->tf", h2, w["w_up"], mode)
    return x + mm("tf,fd->td", jax.nn.silu(g) * u, w["w_down"], mode)


# -- counts -----------------------------------------------------------------

def layer_params(c) -> int:
    d, h, qr, r, nope, rp, v, ff = _sizes(c)
    return (d * qr + qr * h * (nope + rp) + d * (r + rp)
            + r * h * (nope + v) + h * v * d + 3 * d * ff)


def weight_bytes(c) -> int:
    """bf16 bytes a decode step reads: every layer, the head, the norms."""
    d, _, qr, r = _sizes(c)[:4]
    n = c["num_hidden_layers"]
    return 2 * (n * (layer_params(c) + 2 * d + qr + r)
                + d * c["vocab_size"] + d)


def kv_bytes_per_token(c) -> int:
    """Raw bf16 latent-cache bytes of one token over all layers."""
    return 2 * c["num_hidden_layers"] * (c["kv_lora_rank"]
                                         + c["qk_rope_head_dim"])


def prefill_flops(c, batch: int, seq: int) -> float:
    """Useful FLOPs of a prefill in the expanded form the program runs: the
    layers' products, causal attention once per (query, key <= query) pair,
    the head at the last position only."""
    d, h, qr, r, nope, rp, v, ff = _sizes(c)
    n = c["num_hidden_layers"]
    pairs = seq * (seq + 1) / 2
    per_row = n * (2 * layer_params(c) * seq
                   + 2 * h * (nope + rp + v) * pairs) \
        + 2 * d * c["vocab_size"]
    return batch * per_row


def decode_step(c, batch: int, ctx: int):
    """(FLOPs, bytes) of an absorbed-form decode step over ``ctx`` latent
    tokens a row: the query, latent and output products, the absorption of
    W_kvb into query and output, scores over latent and rope parts, the MLP
    and the head; weights read once and the live latent cache."""
    d, h, qr, r, nope, rp, v, ff = _sizes(c)
    n = c["num_hidden_layers"]
    per_layer = 2 * (d * qr + qr * h * (nope + rp) + d * (r + rp)
                     + h * nope * r + h * r * v + h * v * d + 3 * d * ff) \
        + attention_flops(c, 1, ctx)
    flops = batch * (n * per_layer + 2 * d * c["vocab_size"])
    return flops, weight_bytes(c) + batch * ctx * kv_bytes_per_token(c)


def attention_flops(c, rows: int, tokens: int) -> float:
    """Scores over latent + rope and the latent context, one layer."""
    h, r, rp = (c["num_attention_heads"], c["kv_lora_rank"],
                c["qk_rope_head_dim"])
    return rows * 2 * h * (r + rp + r) * tokens


# -- the paged kernel of the compressed-resident decode ---------------------

paged_kernel = "paged_mla_attention"


def paged_dims(c):
    """(heads, query width, value width) of the paged kernel in its absorbed
    form: scores are q_lat . c + q_rope . k_r over the latent and rope pages,
    and the context is summed over the latent c."""
    r = c["kv_lora_rank"]
    return c["num_attention_heads"], r + c["qk_rope_head_dim"], r
