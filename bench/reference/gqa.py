"""Plain float32 reference of a pre-norm GQA transformer (Qwen3 layout).

Layer: x += W_o attn(rope(W_q h), rope(W_k h), W_v h) with h = rmsnorm(x);
x += W_down (silu(W_gate h2) * W_up h2) with h2 = rmsnorm(x); the logits are
rmsnorm(x) W_head. RoPE rotates the two halves of each head; query head j
reads KV head j // (H / H_kv). Departure from the published Qwen3: no per-head
q/k norm, as in the program (see the configuration's notes).

Weights follow the recipe the program's ``init_params`` uses, drawn from
``seeds.key(seed, "weights")`` here and not read from the program: key split
8 ways; the embedding N(0, 1) * 0.02 from part 0, the head the same from part
1, layer i from part 3 split num_layers ways. A layer key splits in two: the
first four ways into W_q, W_k, W_v, W_o at d^-1/2, the second three ways into
W_gate, W_up at d^-1/2 and W_down at d_ff^-1/2. Norm weights are ones. Every
value is rounded to bf16.

Also the operation and byte counts of the served programs, from the sizes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import causal_attention, draw, mm, rms_norm, rope


def _sizes(c):
    return (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"])


def embed(c, wkey, tokens):
    ks = jax.random.split(wkey, 8)
    table = draw(ks[0], (c["vocab_size"], c["hidden_size"]), 0.02)
    return table[tokens]


def head(c, wkey):
    ks = jax.random.split(wkey, 8)
    return draw(ks[1], (c["hidden_size"], c["vocab_size"]), 0.02)


def layer_weights(c, wkey, i):
    d, h, hkv, hd, ff = _sizes(c)
    ks = jax.random.split(wkey, 8)
    lk = jax.random.split(ks[3], c["num_hidden_layers"])[i]
    k_attn, k_mlp = jax.random.split(lk)
    a = jax.random.split(k_attn, 4)
    m = jax.random.split(k_mlp, 3)
    s = d ** -0.5
    return {
        "wq": draw(a[0], (d, h, hd), s), "wk": draw(a[1], (d, hkv, hd), s),
        "wv": draw(a[2], (d, hkv, hd), s), "wo": draw(a[3], (h, hd, d), s),
        "w_gate": draw(m[0], (d, ff), s), "w_up": draw(m[1], (d, ff), s),
        "w_down": draw(m[2], (ff, d), ff ** -0.5),
    }


def layer(c, w, x, *, mode):
    """One layer over one row: x (T, d) float32."""
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.arange(x.shape[0])
    h = rms_norm(x, 1.0, eps)
    q = rope(mm("td,dhk->thk", h, w["wq"], mode), pos, theta)
    k = rope(mm("td,dhk->thk", h, w["wk"], mode), pos, theta)
    v = mm("td,dhk->thk", h, w["wv"], mode)
    o = causal_attention(q, k, v, 1.0 / np.sqrt(c["head_dim"]), mode)
    x = x + mm("thk,hkd->td", o, w["wo"], mode)
    h2 = rms_norm(x, 1.0, eps)
    g = mm("td,df->tf", h2, w["w_gate"], mode)
    u = mm("td,df->tf", h2, w["w_up"], mode)
    return x + mm("tf,fd->td", jax.nn.silu(g) * u, w["w_down"], mode)


# -- counts -----------------------------------------------------------------

def layer_params(c) -> int:
    d, h, hkv, hd, ff = _sizes(c)
    return d * h * hd * 2 + d * hkv * hd * 2 + 3 * d * ff


def weight_bytes(c) -> int:
    """bf16 bytes a decode step reads: every layer, the head, the norms."""
    d, v, n = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    return 2 * (n * (layer_params(c) + 2 * d) + d * v + d)


def kv_bytes_per_token(c) -> int:
    """Raw bf16 cache bytes of one token over all layers (K and V)."""
    return 2 * 2 * c["num_hidden_layers"] * c["num_key_value_heads"] \
        * c["head_dim"]


def prefill_flops(c, batch: int, seq: int) -> float:
    """Useful FLOPs of a prefill: the layers' products, causal attention
    counted once per (query, key <= query) pair, the head at the last
    position only (what the served prefill computes)."""
    d, h, _, hd, _ = _sizes(c)
    n = c["num_hidden_layers"]
    pairs = seq * (seq + 1) / 2
    per_row = n * (2 * layer_params(c) * seq + 2 * 2 * h * hd * pairs) \
        + 2 * d * c["vocab_size"]
    return batch * per_row


def decode_step(c, batch: int, ctx: int):
    """(FLOPs, bytes) a decode step needs when each row attends over ``ctx``
    tokens (its cache plus the new one): the products, the weights read
    once, the live cache read."""
    d, h, _, hd, _ = _sizes(c)
    n = c["num_hidden_layers"]
    flops = batch * (n * (2 * layer_params(c) + 2 * 2 * h * hd * ctx)
                     + 2 * d * c["vocab_size"])
    return flops, weight_bytes(c) + batch * ctx * kv_bytes_per_token(c)


def attention_flops(c, rows: int, tokens: int) -> float:
    """QK^T and PV of one layer's decode attention over ``tokens`` keys."""
    return rows * 2 * 2 * c["num_attention_heads"] * c["head_dim"] * tokens


# -- the paged kernel of the compressed-resident decode ---------------------

paged_kernel = "paged_gqa_attention"


def paged_dims(c):
    """(heads, query width, value width) of the paged kernel: each query
    head scores its KV head's keys and sums its values."""
    return c["num_attention_heads"], c["head_dim"], c["head_dim"]
