"""Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 style).

The KV cache is the *compressed latent*: per token only
(kv_lora_rank + qk_rope_head_dim) values — this is what crosses the PD
boundary and what SplitZip compresses (DESIGN.md §4).

RoPE on the rope parts is YaRN-scaled when ``MLAConfig.yarn`` is set
(DeepSeek-V3), which also multiplies the softmax scale by mscale²
(``MLAConfig.softmax_scale``).

Prefill uses the naive expanded form (latent -> per-head K/V, chunked
attention).  Decode uses the **absorbed form**: the k_nope projection is
folded into the query and the v projection into the output, so per-step cost
is O(S · kv_lora_rank) instead of re-expanding the whole cache.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig
from repro.distributed.sharding import constrain
from repro.models.layers import NEG_INF, apply_rope, chunked_attention, rms_norm


def init_mla(key, d_model: int, num_heads: int, cfg: MLAConfig):
    ks = jax.random.split(key, 6)
    qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    s = d_model ** -0.5
    return {
        "wq_a": (jax.random.normal(ks[0], (d_model, cfg.q_lora_rank)) * s).astype(jnp.bfloat16),
        "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.bfloat16),
        "wq_b": (jax.random.normal(ks[1], (cfg.q_lora_rank, num_heads, qk_dim))
                 * cfg.q_lora_rank ** -0.5).astype(jnp.bfloat16),
        "wkv_a": (jax.random.normal(ks[2], (d_model, cfg.kv_lora_rank + cfg.qk_rope_head_dim))
                  * s).astype(jnp.bfloat16),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.bfloat16),
        "wkv_b": (jax.random.normal(
            ks[3], (cfg.kv_lora_rank, num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim))
            * cfg.kv_lora_rank ** -0.5).astype(jnp.bfloat16),
        "wo": (jax.random.normal(ks[4], (num_heads, cfg.v_head_dim, d_model))
               * (num_heads * cfg.v_head_dim) ** -0.5).astype(jnp.bfloat16),
    }


def _queries(p, x, positions, cfg: MLAConfig, theta: float):
    q_lat = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"])
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_head_dim:], positions, theta,
                        cfg.yarn)
    return q_nope, q_rope


def _latent_kv(p, x, positions, cfg: MLAConfig, theta: float):
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv = rms_norm(kv[..., : cfg.kv_lora_rank], p["kv_norm"])
    k_rope = kv[..., cfg.kv_lora_rank:]                         # (B, S, rope)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, theta,
                        cfg.yarn)[:, :, 0, :]
    return c_kv, k_rope


def mla_prefill(p, x, positions, cfg: MLAConfig, theta: float,
                kv_block: int = 1024) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence attention; returns (out, (c_kv, k_rope)) latent cache."""
    b, s, d = x.shape
    h = p["wq_b"].shape[1]
    q_nope, q_rope = _queries(p, x, positions, cfg, theta)
    c_kv, k_rope = _latent_kv(p, x, positions, cfg, theta)

    kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"])
    k_nope = kv[..., : cfg.qk_nope_head_dim]
    v = constrain(kv[..., cfg.qk_nope_head_dim:], "bthd")
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, cfg.qk_rope_head_dim))],
        axis=-1)
    # MHA heads (40) don't divide the model axis: the bthd rule falls back to
    # sequence sharding — without it GSPMD replicates the whole score chain
    # on every model shard (16x waste; EXPERIMENTS.md §Perf Cell A).
    k = constrain(k, "bthd")
    q = constrain(jnp.concatenate([q_nope, q_rope], axis=-1), "bthd")
    o = constrain(chunked_attention(q, k, v, causal=True, kv_block=kv_block,
                                    scale=cfg.softmax_scale), "bthd")
    out = jnp.einsum("bshv,hvd->bsd", o, p["wo"])
    return out, (c_kv, k_rope)


def mla_decode(p, x, cache_ckv, cache_krope, cache_len, cfg: MLAConfig,
               theta: float) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Absorbed-form decode over the latent cache.

    x: (B, 1, D); cache_ckv: (B, S, kv_r); cache_krope: (B, S, rope)."""
    b = x.shape[0]
    positions = cache_len[:, None]
    q_nope, q_rope = _queries(p, x, positions, cfg, theta)      # (B,1,H,·)
    c_new, kr_new = _latent_kv(p, x, positions, cfg, theta)     # (B,1,kv_r/rope)

    cache_ckv = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(c, n, (i, 0)))(
        cache_ckv, c_new, cache_len)
    cache_krope = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(c, n, (i, 0)))(
        cache_krope, kr_new, cache_len)

    w_knope = p["wkv_b"][..., : cfg.qk_nope_head_dim]            # (r, H, nope)
    w_v = p["wkv_b"][..., cfg.qk_nope_head_dim:]                 # (r, H, v)

    # absorb: q_lat[h] = q_nope[h] @ w_knope[:, h, :].T  -> latent-space query
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_knope)
    scale = cfg.softmax_scale
    sc = (jnp.einsum("bqhr,bsr->bqhs", q_lat, cache_ckv) +
          jnp.einsum("bqhp,bsp->bqhs", q_rope, cache_krope)).astype(jnp.float32)
    sc = sc * scale
    s_len = cache_ckv.shape[1]
    valid = jnp.arange(s_len)[None, :] < (cache_len + 1)[:, None]
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    prob = jax.nn.softmax(sc, axis=-1)
    ctx_lat = jnp.einsum("bqhs,bsr->bqhr", prob.astype(cache_ckv.dtype), cache_ckv)
    o = jnp.einsum("bqhr,rhv->bqhv", ctx_lat, w_v)
    out = jnp.einsum("bqhv,hvd->bqd", o, p["wo"])
    return out, (cache_ckv, cache_krope)
