"""Flash attention as a Pallas TPU kernel: full-sequence (prefill) attention
with the score block, the online-softmax state and P·V kept in VMEM.

The XLA path (``models/layers.chunked_attention``) writes every
(query, KV-block) score block to HBM in f32 and runs the softmax over it as
separate fusions, and under a causal mask it computes the blocks above the
diagonal too.  This kernel's HBM traffic is q + k + v + o (``hbm_bytes``),
and it neither fetches nor computes a KV block above the diagonal.

TPU mapping:
  * Queries are grouped by KV head: ``(B·Hkv, g, S, d)``, a q tile is
    ``(1, g, blk_q, d)``, so the g query heads of a group share each K/V
    block read (GQA reads K/V once per group, not g times).
  * The grid is ``(B·Hkv, pairs)``: ``pairs`` lists, q block by q block,
    the KV blocks at or below the diagonal (every block when not causal),
    read from a scalar-prefetched schedule by the index maps.  A KV block
    above the diagonal is never a grid step, so it costs neither a DMA nor
    a matmul.  The KV axis of a q block runs in order; m, l and acc are
    carried in VMEM scratch and the output is written at its last block.
    m and l are kept replicated over the 128 lanes, so a step broadcasts
    them across a score row without a lane shuffle.
  * The arithmetic is the XLA path's: bf16 operands into both dots with f32
    accumulation, the scale applied to the f32 scores, f32 max/exp/sum, p
    cast to v's dtype before P·V.  Only blocks that cross the diagonal or
    hold KV padding are masked.
  * Block sizes are chosen from the shapes under a VMEM budget
    (``block_sizes``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import auto_interpret

NEG_INF = -1e30
LANES = 128
MAX_BLOCK = 1024           # positions of a q or KV block
MAX_TILE = 1 << 21         # score elements (g · blk_q rows × blk_k keys) of a step
VMEM_BUDGET = 40 * 1024 * 1024
VMEM_LIMIT = 64 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(blk_q: int, blk_k: int, g: int, d: int, dv: int) -> int:
    """VMEM a grid step holds: double-buffered bf16 q, k, v and o tiles,
    f32 m, l and acc scratch, and about three f32 score-sized temporaries
    (s, p and the mask or exp operand)."""
    rows = g * blk_q
    tiles = 2 * 2 * (rows * d + blk_k * (d + dv) + rows * dv)
    scratch = 4 * rows * (dv + 2 * LANES)
    return tiles + scratch + 3 * 4 * rows * blk_k


def block_sizes(sq: int, skv: int, g: int, d: int, dv: int
                ) -> Tuple[int, int]:
    """(blk_q, blk_k): square blocks of the largest power of two up to 1024
    positions whose score tile (g · blk_q rows × blk_k keys) holds at most
    2^21 elements.  Large steps keep the MXU fed between the softmax's
    reductions; square blocks compute the least above the diagonal.  A
    sequence shorter than a block is one block, rounded up to the bf16
    sublane tile (16) for q and the lane width (128) for k.  Halved until a
    step fits the VMEM budget."""
    blk = MAX_BLOCK
    while g * blk * blk > MAX_TILE and blk > LANES:
        blk //= 2
    blk_q = min(blk, _round_up(sq, 16))
    blk_k = min(blk, _round_up(skv, LANES))
    while vmem_bytes(blk_q, blk_k, g, d, dv) > VMEM_BUDGET and blk_q > 16:
        blk_q //= 2
    while vmem_bytes(blk_q, blk_k, g, d, dv) > VMEM_BUDGET and blk_k > LANES:
        blk_k //= 2
    return blk_q, blk_k


def _schedule(nq: int, nk: int, blk_q: int, blk_k: int, sq: int, skv: int,
              causal: bool) -> np.ndarray:
    """(3, pairs) int32: per grid step its q block, its KV block, and 1 where
    that KV block is the q block's last.  Causal: a q block visits the KV
    blocks up to the one holding the key of its last valid query."""
    qi, ki, fin = [], [], []
    for j in range(nq):
        last = nk - 1
        if causal:
            last = min(min((j + 1) * blk_q, sq) - 1, skv - 1) // blk_k
        for t in range(last + 1):
            qi.append(j)
            ki.append(t)
            fin.append(int(t == last))
    return np.asarray([qi, ki, fin], np.int32)


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """A (rows, 128) lane-replicated statistic at width n."""
    if n <= LANES:
        return x[:, :n]
    return jnp.tile(x, (1, -(-n // LANES)))[:, :n]


def _kernel(sched, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale: float, causal: bool, g: int, blk_q: int, blk_k: int,
            seq_kv: int):
    p = pl.program_id(1)
    qi, ki = sched[0, p], sched[1, p]
    rows = g * blk_q
    q_start, k_start = qi * blk_q, ki * blk_k

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked: bool):
        q = q_ref[0].reshape(rows, q_ref.shape[-1])           # (rows, d)
        s = jax.lax.dot_general(
            q, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (rows, blk_k)
        if masked:
            shape = (blk_q, blk_k)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            mask = k_pos < seq_kv
            if causal:
                q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                mask &= q_pos >= k_pos
            s = jnp.where(mask[None], s.reshape(g, blk_q, blk_k),
                          NEG_INF).reshape(rows, blk_k)
        m_prev = m_sc[...]                                     # (rows, 128)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        pr = jnp.exp(s - _lanes(m_new, blk_k))
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + pr.sum(axis=-1, keepdims=True)
        v = v_ref[0]
        acc_sc[...] = acc_sc[...] * _lanes(corr, v.shape[-1]) + jax.lax.dot_general(
            pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    # a block needs the mask where one of its keys lies past a query of the
    # tile (it crosses the diagonal) or past the sequence (KV padding)
    needs_mask = k_start + blk_k > seq_kv
    if causal:
        needs_mask |= k_start + blk_k - 1 > q_start
    pl.when(needs_mask)(lambda: step(True))
    pl.when(jnp.logical_not(needs_mask))(lambda: step(False))

    @pl.when(sched[2, p] == 1)
    def _finalize():
        l = _lanes(l_sc[...], acc_sc.shape[-1])
        out = acc_sc[...] / jnp.maximum(l, 1e-30)
        o_ref[0] = out.reshape(g, blk_q, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "blk_q", "blk_k", "interpret"))
def flash_attention(
    q: jax.Array,                  # (B, Sq, H, D)
    k: jax.Array,                  # (B, Skv, Hkv, D)
    v: jax.Array,                  # (B, Skv, Hkv, Dv)
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    blk_q: Optional[int] = None,   # None: ``block_sizes`` from the shapes
    blk_k: Optional[int] = None,
    interpret: bool | None = None,  # None: Mosaic on TPU, interpreted elsewhere
) -> jax.Array:
    """Fused multi-head attention; value head dim may differ (MLA).  Causal
    positions are aligned at 0 for queries and keys."""
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    auto_q, auto_k = block_sizes(sq, skv, g, d, dv)
    blk_q, blk_k = blk_q or auto_q, blk_k or auto_k

    pad_q = (-sq) % blk_q
    pad_k = (-skv) % blk_k
    nq, nk = (sq + pad_q) // blk_q, (skv + pad_k) // blk_k
    # head-major: queries (B·Hkv, g, Sq, d), keys and values (B·Hkv, Skv, ·)
    qh = jnp.pad(q.reshape(b, sq, hkv, g, d),
                 ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    qh = qh.transpose(0, 2, 3, 1, 4).reshape(b * hkv, g, sq + pad_q, d)
    kh = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(
        0, 2, 1, 3).reshape(b * hkv, skv + pad_k, d)
    vh = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(
        0, 2, 1, 3).reshape(b * hkv, skv + pad_k, dv)

    sched = _schedule(nq, nk, blk_q, blk_k, sq, skv, causal)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, sched.shape[1]),
        in_specs=[
            pl.BlockSpec((1, g, blk_q, d), lambda i, p, s: (i, 0, s[0, p], 0)),
            pl.BlockSpec((1, blk_k, d), lambda i, p, s: (i, s[1, p], 0)),
            pl.BlockSpec((1, blk_k, dv), lambda i, p, s: (i, s[1, p], 0)),
        ],
        out_specs=pl.BlockSpec((1, g, blk_q, dv),
                               lambda i, p, s: (i, 0, s[0, p], 0)),
        scratch_shapes=[
            pltpu.VMEM((g * blk_q, LANES), jnp.float32),  # m: running row max
            pltpu.VMEM((g * blk_q, LANES), jnp.float32),  # l: running denominator
            pltpu.VMEM((g * blk_q, dv), jnp.float32),   # acc: running numerator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), causal=causal, g=g,
                          blk_q=blk_q, blk_k=blk_k, seq_kv=skv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, sq + pad_q, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=auto_interpret(interpret),
        name="flash_attention",
    )(jnp.asarray(sched), qh, kh, vh)

    out = out[:, :, :sq].reshape(b, hkv, g, sq, dv)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv)


def hbm_bytes(b, sq, skv, h, hkv, d, dv, bytes_per_el=2) -> int:
    """Analytic HBM traffic of the fused kernel: q + k + v + o only."""
    return bytes_per_el * (b * sq * h * d + b * skv * hkv * (d + dv)
                           + b * sq * h * dv)


def flops(b, sq, skv, h, d, dv, causal=True) -> float:
    """2 matmuls; causal ≈ half the S² area."""
    area = sq * skv * (0.5 if causal else 1.0)
    return 2.0 * b * h * area * (d + dv)
