"""Per-family inference cache structures.

The cache is *the* object SplitZip exists for: it is produced by prefill
workers, crosses the PD boundary compressed, and is consumed by decode
workers.  Every family stores its state stacked over layers (leading dim =
layer-stack) so the whole cache is one pytree the transfer engine can map
the codec over.

  dense/moe/vlm : k, v           (L, B, S, Hkv, hd)        bf16
  mla           : ckv, krope     (L, B, S, r) / (L, B, S, p) bf16
  ssm           : ssm, conv      (L, B, H, P, N) fp32 / (L, B, W-1, C) bf16
  hybrid        : attn k/v (windowed, right-aligned) + rglru h/conv
  audio         : none (encoder-only; the shipped artifact is the encoder
                  output itself)
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DecodeState:
    """Raw decode-worker state.

    ``cache_len`` is PER ROW: a mixed-length (ragged) batch right-pads each
    row to the padded sequence length, and every consumer — decode
    attention's validity mask, transfer accounting, the resident pool's
    page tables — must read the per-row length, never the padded S.
    ``models.model.prefill`` builds it from ``batch['lengths']`` (scoring
    each row's logits at its own last real token).  The compressed-resident
    analogue is :class:`repro.models.kvpool.ResidentState`, which carries
    the same (B,) vector next to page tables instead of a raw cache.

    ``moe`` carries a mixture-of-experts model's routing counts on the
    device (``models.model.moe_counts``): prefill sets them to its own, each
    decode step adds its own, and a transfer passes them on. None where no
    count is kept: a model without MoE, or a state built by hand."""
    cache: dict
    cache_len: jax.Array  # (B,) int32 — valid prefix length per row
    moe: Optional[dict] = None

    def valid_mask(self, max_seq: Optional[int] = None) -> jax.Array:
        """(B, S) bool — True where the cache holds a real token.  Only
        meaningful for families with a sequence axis (dense/moe/vlm/mla);
        S defaults to the cache's own sequence length."""
        if max_seq is None:
            max_seq = max(v.shape[2] for v in self.cache.values()
                          if v.ndim >= 3)
        return jnp.arange(max_seq)[None, :] < self.cache_len[:, None]


def n_triples_extra(cfg: ArchConfig):
    pat = len(cfg.hybrid.pattern)
    return cfg.num_layers // pat, cfg.num_layers % pat


def init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype=jnp.bfloat16) -> dict:
    l, b, s = cfg.num_layers, batch, max_seq
    if cfg.ssm is not None:
        d_inner = cfg.ssm.expand * cfg.d_model
        heads = d_inner // cfg.ssm.head_dim
        conv_ch = d_inner + 2 * cfg.ssm.n_groups * cfg.ssm.d_state
        return {
            "ssm": jnp.zeros((l, b, heads, cfg.ssm.head_dim, cfg.ssm.d_state), jnp.float32),
            "conv": jnp.zeros((l, b, cfg.ssm.conv_width - 1, conv_ch), dtype),
        }
    if cfg.hybrid is not None:
        nt, ne = n_triples_extra(cfg)
        w = min(cfg.hybrid.window, max_seq)
        u = cfg.hybrid.lru_width or cfg.d_model
        cw = cfg.hybrid.conv_width
        return {
            "attn_k": jnp.zeros((nt, b, w, cfg.num_kv_heads, cfg.head_dim), dtype),
            "attn_v": jnp.zeros((nt, b, w, cfg.num_kv_heads, cfg.head_dim), dtype),
            "rec_h": jnp.zeros((nt, 2, b, u), jnp.float32),
            "rec_conv": jnp.zeros((nt, 2, b, cw - 1, u), dtype),
            "extra_h": jnp.zeros((ne, b, u), jnp.float32),
            "extra_conv": jnp.zeros((ne, b, cw - 1, u), dtype),
        }
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "ckv": jnp.zeros((l, b, s, m.kv_lora_rank), dtype),
            "krope": jnp.zeros((l, b, s, m.qk_rope_head_dim), dtype),
        }
    if cfg.encoder_only:
        return {}
    return {
        "k": jnp.zeros((l, b, s, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((l, b, s, cfg.num_kv_heads, cfg.head_dim), dtype),
    }


def cache_bytes(cache: dict) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


def transferable_leaves(cache: dict):
    """(path, leaf) pairs the transfer engine compresses (bf16) vs ships raw
    (fp32 recurrent states — see DESIGN.md: the bf16 codec extends to fp32 as
    a beyond-paper variant, tracked separately)."""
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    comp, raw = [], []
    for path, leaf in flat:
        (comp if leaf.dtype == jnp.bfloat16 else raw).append((path, leaf))
    return comp, raw
