"""Flash attention as a Pallas TPU kernel (EXPERIMENTS.md §Perf Cell A).

Why this kernel exists: the XLA prefill attention materializes the per-block
score chain through HBM (on CPU-XLA even the reductions are unfused), which
is the dominant byte term of every long-context prefill cell in §Roofline.
A fused kernel keeps Q·Kᵀ, the online-softmax state and P·V in VMEM; its HBM
traffic is exactly q+k+v+o.

TPU mapping:
  * grid = (B·H, Sq/blk_q, Skv/blk_k), last axis fastest => sequential
    accumulation over KV blocks per (head, q-block) with carried VMEM
    scratch (m, l, acc) — the canonical TPU flash schedule.
  * BlockSpecs tile Q (blk_q, d), K/V (blk_k, d) into VMEM; GQA is handled
    in the K/V index maps (query head h reads kv head h // group).
  * MXU-aligned tiles: blk_q, blk_k multiples of 128 by default; working set
    at (256, 512, d=128): q 64 KB + k/v 256 KB + scores 512 KB + acc 128 KB
    ≈ 1 MB — comfortably inside the 16 MB VMEM budget.
  * Causal masking via position iota; blocks strictly above the diagonal
    short-circuit through @pl.when (visited but skipped).

Validated on CPU in interpret mode against the jnp oracle (ref.py) across
shapes/dtypes/causality — see tests/test_flash_attention.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import auto_interpret

DEFAULT_BLK_Q = 256
DEFAULT_BLK_K = 512
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, blk_q: int, blk_k: int,
            nk: int, seq_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * blk_q
    k_start = ki * blk_k

    # skip blocks strictly above the causal diagonal (no query attends there)
    @pl.when((k_start <= q_start + blk_q - 1) if causal else (ki >= 0))
    def _step():
        q = q_ref[0].astype(jnp.float32)                    # (blk_q, d)
        k = k_ref[0].astype(jnp.float32)                    # (blk_k, d)
        v = v_ref[0].astype(jnp.float32)                    # (blk_k, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (blk_q, blk_k)

        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_kv                               # kv padding
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask &= q_pos >= k_pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                  # (blk_q, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                               # (blk_q, blk_k)
        corr = jnp.exp(m_prev - m_new)                       # (blk_q, 1)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "blk_q", "blk_k", "interpret"))
def flash_attention(
    q: jax.Array,                  # (B, Sq, H, D)
    k: jax.Array,                  # (B, Skv, Hkv, D)
    v: jax.Array,                  # (B, Skv, Hkv, Dv)
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    blk_q: int = DEFAULT_BLK_Q,
    blk_k: int = DEFAULT_BLK_K,
    interpret: bool | None = None,  # None: Mosaic on TPU, interpreted elsewhere
) -> jax.Array:
    """Fused multi-head attention; value head dim may differ (MLA)."""
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)

    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, skv)
    pad_q = (-sq) % blk_q
    pad_k = (-skv) % blk_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v

    # head-major layout: (B*H, S, d) queries, (B*Hkv, S, d) keys/values
    qh = qp.transpose(0, 2, 1, 3).reshape(b * h, sq + pad_q, d)
    kh = kp.transpose(0, 2, 1, 3).reshape(b * hkv, skv + pad_k, d)
    vh = vp.transpose(0, 2, 1, 3).reshape(b * hkv, skv + pad_k, dv)

    nq = (sq + pad_q) // blk_q
    nk = (skv + pad_k) // blk_k

    def kv_head(i):   # query-head program index -> kv-head row
        bb, hh = i // h, (i % h) // g
        return bb * hkv + hh

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, blk_q=blk_q,
                          blk_k=blk_k, nk=nk, seq_kv=skv),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda i, j, t: (kv_head(i), t, 0)),
            pl.BlockSpec((1, blk_k, dv), lambda i, j, t: (kv_head(i), t, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, dv), lambda i, j, t: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq + pad_q, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),   # m: running row max
            pltpu.VMEM((blk_q, 1), jnp.float32),   # l: running denominator
            pltpu.VMEM((blk_q, dv), jnp.float32),  # acc: running numerator
        ],
        interpret=auto_interpret(interpret),
    )(qh, kh, vh)

    out = out[:, :sq].reshape(b, h, sq, dv).transpose(0, 2, 1, 3)
    return out


def hbm_bytes(b, sq, skv, h, hkv, d, dv, bytes_per_el=2) -> int:
    """Analytic HBM traffic of the fused kernel: q + k + v + o only."""
    return bytes_per_el * (b * sq * h * d + b * skv * hkv * (d + dv)
                           + b * sq * h * dv)


def flops(b, sq, skv, h, d, dv, causal=True) -> float:
    """2 matmuls; causal ≈ half the S² area."""
    area = sq * skv * (0.5 if causal else 1.0)
    return 2.0 * b * h * area * (d + dv)
