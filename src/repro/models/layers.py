"""Shared transformer layers: RMSNorm, RoPE, chunked (flash-style) attention,
GQA/MQA attention blocks, sliding-window attention, SwiGLU MLP.

All attention paths are memory-efficient by construction: scores are never
materialized at (S, S) — prefill/train attention scans over KV blocks with an
online softmax (the standard flash recurrence), so the 32k-prefill cells lower
within HBM.  Decode attends over the full cache in one pass (scores are
(B, H, 1, S), which is small).

Compute dtype is bf16 with fp32 softmax statistics and accumulators.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import scanctl


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


def init_rms_norm(d: int) -> jax.Array:
    return jnp.ones((d,), jnp.bfloat16)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float, yarn=None) -> np.ndarray:
    """Rotary frequencies; with a ``configs.base.YaRN``, the YaRN blend:
    dimensions that turn more than ``beta_fast`` times over the original
    context keep their frequency, those that turn fewer than ``beta_slow``
    times are divided by ``factor``, and a linear ramp joins the two."""
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    if yarn is None:
        return freqs

    def dim_of(rotations):      # the dimension that turns ``rotations`` times
        return (head_dim * np.log(yarn.original_max_position
                                  / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))

    lo = max(np.floor(dim_of(yarn.beta_fast)), 0)
    hi = min(np.ceil(dim_of(yarn.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return freqs / yarn.factor * ramp + freqs * (1 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """x: (..., S, H, D) rotated by position; positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(d, theta, yarn), jnp.float32)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs           # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                                 # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    if yarn is not None and yarn.cos_sin_scale != 1.0:
        cos, sin = cos * yarn.cos_sin_scale, sin * yarn.cos_sin_scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# chunked attention (flash-style online softmax, pure JAX; lowers to scan)
# ---------------------------------------------------------------------------

NEG_INF = -1e30

# performance overrides installed by the dry-run/launchers (EXPERIMENTS.md
# §Perf): score_dtype bf16 halves the dominant score-chain HBM traffic at a
# ~2-decimal attention-weight precision cost; kv_block trades scan trip count
# against carried-accumulator rewrite traffic.
_ATTN_OVERRIDES = threading.local()


@contextlib.contextmanager
def attn_overrides(score_dtype=None, kv_block=None):
    prev = getattr(_ATTN_OVERRIDES, "cfg", {})
    _ATTN_OVERRIDES.cfg = {k: v for k, v in
                           dict(score_dtype=score_dtype,
                                kv_block=kv_block).items() if v is not None}
    try:
        yield
    finally:
        _ATTN_OVERRIDES.cfg = prev


def _attn_override(key, default):
    return getattr(_ATTN_OVERRIDES, "cfg", {}).get(key, default)


def chunked_attention(
    q: jax.Array,                 # (B, Sq, H, D)
    k: jax.Array,                 # (B, Skv, Hkv, D)
    v: jax.Array,                 # (B, Skv, Hkv, D)
    *,
    causal: bool,
    q_offset: int | jax.Array = 0,   # absolute position of q[0]
    window: Optional[int] = None,    # sliding-window width (None = full)
    kv_block: int = 1024,
    scale: Optional[float] = None,
) -> jax.Array:
    """Memory-efficient attention: scan over KV blocks, never materialize SxS.

    Value head dim may differ from the q/k head dim (MLA)."""
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    score_dtype = _attn_override("score_dtype", jnp.float32)
    kv_block = _attn_override("kv_block", kv_block)
    kv_block = min(kv_block, skv)
    kv_valid = skv
    pad = (-skv) % kv_block
    if pad:  # pad keys; padded positions are masked out below
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        skv += pad
    nblk = skv // kv_block

    qg = q.reshape(b, sq, hkv, g, d)
    q_pos = q_offset + jnp.arange(sq)

    kb = k.reshape(b, nblk, kv_block, hkv, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, kv_block, hkv, dv).transpose(1, 0, 2, 3, 4)

    def step(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, blk_idx = blk
        k_pos = blk_idx * kv_block + jnp.arange(kv_block)
        # scores in score_dtype (bf16 override halves the dominant HBM
        # traffic; bf16 has f32 range so NEG_INF masking still works);
        # m/l/acc accumulators stay f32 for numerical stability.
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k_blk,
                       preferred_element_type=score_dtype) * scale
        mask = jnp.broadcast_to(k_pos[None, :] < kv_valid, (sq, kv_block))
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask[None, :, None, None, :], s,
                      jnp.asarray(NEG_INF, score_dtype))
        m_new = jnp.maximum(m, s.max(axis=-1).astype(jnp.float32))
        p = jnp.exp(s - m_new.astype(score_dtype)[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, dtype=jnp.float32)
        pv = jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(v_blk.dtype), v_blk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, hkv, g), jnp.float32)
    a0 = jnp.zeros((b, sq, hkv, g, dv), jnp.float32)
    (m, l, acc), _ = scanctl.scan(step, (m0, l0, a0),
                                  (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, h, dv).astype(q.dtype)


def decode_attention(
    q: jax.Array,                 # (B, 1, H, D)
    k_cache: jax.Array,           # (B, S, Hkv, D)
    v_cache: jax.Array,
    cache_len: jax.Array,         # (B,) valid prefix length (q at cache_len-1.. ok)
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token attention over the full cache (one pass; no blocking)."""
    b, sq, h, d = q.shape
    _, s, hkv, _ = k_cache.shape
    dv = v_cache.shape[-1]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qg = q.reshape(b, sq, hkv, g, d)
    sc = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k_cache,
                    preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(s)
    valid = pos[None, :] < cache_len[:, None]                   # (B, S)
    if window is not None:
        valid &= pos[None, :] >= (cache_len[:, None] - window)
    sc = jnp.where(valid[:, None, None, None, :], sc, NEG_INF)
    # softmax in the flash form of ``attention``: un-normalized
    # probabilities meet V in its dtype, the sum divides after the matmul
    p = jnp.exp(sc - sc.max(axis=-1, keepdims=True))
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out / p.sum(axis=-1)[..., None]
    return out.reshape(b, sq, h, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------

def init_attention(key, d_model, num_heads, num_kv_heads, head_dim):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d_model ** -0.5
    return {
        "wq": (jax.random.normal(k1, (d_model, num_heads, head_dim)) * s).astype(jnp.bfloat16),
        "wk": (jax.random.normal(k2, (d_model, num_kv_heads, head_dim)) * s).astype(jnp.bfloat16),
        "wv": (jax.random.normal(k3, (d_model, num_kv_heads, head_dim)) * s).astype(jnp.bfloat16),
        "wo": (jax.random.normal(k4, (num_heads, head_dim, d_model)) * s).astype(jnp.bfloat16),
    }


def attention_qkv(p, x, positions, theta):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    return q, k, v


def attention_out(p, o):
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def full_attention_block(p, x, positions, theta, *, causal=True, window=None,
                         kv_block=1024):
    q, k, v = attention_qkv(p, x, positions, theta)
    o = chunked_attention(q, k, v, causal=causal, window=window, kv_block=kv_block)
    return attention_out(p, o), (k, v)


def decode_attention_block(p, x, cache_k, cache_v, cache_len, theta, *,
                           window=None):
    """x: (B, 1, D); writes the new kv at cache_len, attends over prefix+self."""
    positions = cache_len[:, None]  # new token position == current length
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    b = x.shape[0]
    idx = cache_len  # (B,)
    cache_k = jax.vmap(lambda c, kk, i: jax.lax.dynamic_update_slice(
        c, kk, (i, 0, 0)))(cache_k, k, idx)
    cache_v = jax.vmap(lambda c, vv, i: jax.lax.dynamic_update_slice(
        c, vv, (i, 0, 0)))(cache_v, v, idx)
    o = decode_attention(q, cache_k, cache_v, cache_len + 1, window=window)
    return attention_out(p, o), (cache_k, cache_v)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d_model, d_ff):
    k1, k2, k3 = jax.random.split(key, 3)
    s = d_model ** -0.5
    return {
        "w_gate": (jax.random.normal(k1, (d_model, d_ff)) * s).astype(jnp.bfloat16),
        "w_up": (jax.random.normal(k2, (d_model, d_ff)) * s).astype(jnp.bfloat16),
        "w_down": (jax.random.normal(k3, (d_ff, d_model)) * (d_ff ** -0.5)).astype(jnp.bfloat16),
    }


def mlp(p, x):
    with jax.named_scope("mlp"):
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
        return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"])
