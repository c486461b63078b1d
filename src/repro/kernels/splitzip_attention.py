"""Fused Pallas attention over splitzip-compressed KV pages (ROADMAP item 1).

The decode worker keeps its KV cache *compressed at rest* (models/kvpool.py:
fixed-size, codec-chunk-aligned pages holding the splitzip streams — dense
sign-mantissa + nibble-packed exponent codes + a page-level sparse escape
list).  This kernel is the consumer: one ``pallas_call`` per attention layer
walks a sequence's page table with **scalar prefetch** (the page id read from
SMEM feeds the BlockSpec index map, so each grid step DMAs exactly one
physical page's streams into VMEM), decodes the K and V tiles **in
register** — dense exponent-stream load via the `splitzip_decode` machinery
(`_unpack_and_lut` + `_assemble`) plus the predicated per-slot escape patch —
and runs the standard flash accumulation (f32 m/l/acc scratch) over the
decoded tiles.  HBM traffic for the K/V streams is therefore the *compressed*
bytes (~1.51 B/elem vs 2 B raw); raw bf16 K/V never exists in HBM.

Shapes and conventions:

* grid = (B, P) with P = max pages per sequence; the page axis is the
  innermost (sequential) axis, accumulating into scratch like the ``ki`` loop
  of ``kernels/flash_attention.py``.
* pages are always FULL (``tokens_per_page`` tokens): decode-time growth
  lands in a raw tail page attended separately (``tail_partials``) and merged
  with ``merge_partials`` — so no intra-page length masking is needed, only
  the per-row valid-page count ``n_full = cache_len // tokens_per_page``.
* the kernel returns UN-normalized partials ``(acc, m, l)`` so the caller can
  merge the tail (and the just-appended token) before the single normalize.
* causal semantics: queries sit at absolute positions
  ``cache_len - nq + 1 + j``; full pages hold positions ``< n_full * Tp <=
  cache_len``, so for single-token decode (nq == 1) every admitted page is
  visible and the mask is a no-op; for multi-token (speculative) queries the
  in-kernel mask ``t_pos <= q_pos`` applies.
* escape-capacity overflow never reaches this kernel: admission/flush demote
  the batch to a raw-resident ``DecodeState`` (rehydrate-then-
  ``flash_attention``) before any page with more than ``cap`` escapes exists
  (see ``DisaggregatedEngine`` resident wiring).

Mosaic layout rules shape the kernels: a page's escape list arrives as one
``(1, 1, cap)`` block, its count as a scalar-prefetched ``(B, P)`` table;
queries arrive grouped by KV head, so each head is one 2-D matmul against a
lane-aligned slice of the decoded page; and the decoded ``(page_chunks,
chunk)`` tile becomes ``(tokens, m)`` by a reshape where ``m`` is a multiple
of 128 lanes, else by exact one-hot matmuls (:func:`_token_rows`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codebook import FORMATS
from repro.kernels import auto_interpret
from repro.kernels.splitzip_decode import (_assemble, _patch_escapes,
                                            _unpack_and_lut)

NEG_INF = -1e30


def _bits_uint(fmt: str):
    return jnp.uint16 if FORMATS[fmt]["bits"] == 16 else jnp.uint8


def _float_dtype(fmt: str):
    if FORMATS[fmt]["bits"] == 16:
        return jnp.bfloat16
    return jnp.float8_e5m2 if fmt == "fp8_e5m2" else jnp.float8_e4m3fn


# ---------------------------------------------------------------------------
# in-register page decode (the splitzip_decode machinery, page-level escapes)
# ---------------------------------------------------------------------------

def _token_rows(x, m: int, tokens: int):
    """(page_chunks, chunk) f32 tile -> (tokens, m), row-major element order.

    Mosaic reshapes a lane dimension only into pieces of whole 128-lane
    tiles.  For a narrower ``m`` (chunk = j·m: row r holds tokens r·j ..
    r·j + j − 1) token ``r·j + i`` is lane slice ``i`` of row ``r``; one-hot
    matmuls place the slices.  The values are bf16-exact (decoded from bf16
    or fp8 bits), so a bf16 one-hot product with f32 accumulation is exact."""
    pc, chunk = x.shape
    if m % 128 == 0 or chunk % m:
        return x.reshape(tokens, m)
    j = chunk // m
    t = jax.lax.broadcasted_iota(jnp.int32, (tokens, pc), 0)
    r = jax.lax.broadcasted_iota(jnp.int32, (tokens, pc), 1)
    out = jnp.zeros((tokens, m), jnp.float32)
    for i in range(j):
        place = (t == r * j + i).astype(jnp.bfloat16)
        out = out + jnp.dot(place, x[:, i * m:(i + 1) * m].astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
    return out


def _decode_page(packed_ref, sm_ref, pos_ref, val_ref, cnt, *, m, tokens,
                 exponents, mbits, bits_width, fmt):
    """Decode ONE page's streams to its (tokens, m) f32 values.

    Dense phase: nibble unpack + one-hot codebook contraction + bit assembly
    (identical math to ``splitzip_decode._decode_fused_kernel``).  Sparse
    phase: the page-level escape list, ``cnt`` slots; slot ``j`` selects the
    exponent field where the element's page offset equals its position
    (padding entries carry ``pos == page_elems`` and can never match)."""
    packed = packed_ref[0].astype(jnp.int32)          # (pc, chunk//2)
    a = sm_ref[0].astype(jnp.int32)                   # (pc, chunk)
    bits = _assemble(_unpack_and_lut(packed, exponents=exponents), a,
                     mbits=mbits, bits_width=bits_width)
    pc, chunk = a.shape
    offset = (jax.lax.broadcasted_iota(jnp.int32, (pc, chunk), 0) * chunk
              + jax.lax.broadcasted_iota(jnp.int32, (pc, chunk), 1))
    bits = _patch_escapes(bits, offset, pos_ref[0].astype(jnp.int32),
                          val_ref[0].astype(jnp.int32), cnt, mbits=mbits,
                          bits_width=bits_width)
    return _token_rows(_bits_to_float(bits, fmt), m, tokens)


def _bits_to_float(bits, fmt: str):
    """(rows, chunk) i32 bit tile -> f32 values."""
    u = bits.astype(_bits_uint(fmt))
    return jax.lax.bitcast_convert_type(u, _float_dtype(fmt)).astype(
        jnp.float32)


def _dot_t(a, b):
    """a @ b.T in f32 (contract the last dims)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _flash_update(s, v, m_sc, l_sc, acc_sc):
    """One online-softmax step of scores ``s`` (R, T) and values ``v`` (T, d)
    into the (R, 1)/(R, 1)/(R, d) scratch views.

    The probabilities meet the values in bf16 with f32 accumulation, as in
    the model's own attention over a raw cache (``layers.decode_attention``,
    ``layers.attention``, ``mla.mla_decode``)."""
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    pexp = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + pexp.sum(axis=-1, keepdims=True)
    acc_sc[...] = acc_sc[...] * corr + jnp.dot(
        pexp.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new


def _causal_mask(s, p, cache_len, *, tokens_per_page, nq, rows_per_q):
    """Mask keys after each query: row ``r`` is query ``r // rows_per_q``,
    at absolute position ``cache_len - nq + 1 + r // rows_per_q``."""
    t_pos = p * tokens_per_page + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    q_pos = cache_len - (nq - 1) + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0) // rows_per_q
    return jnp.where(t_pos <= q_pos, s, NEG_INF)


def _page_inputs(lg_sm, lg_pos, table):
    """BlockSpecs for one paged leaf's four stream arrays, indexed through
    a scalar-prefetched page table (``table`` picks which prefetch ref)."""
    def page(b, p, ptk, ptv, *_):
        t = ptk if table == 0 else ptv
        return (jnp.maximum(t[b, p], 0), 0, 0)

    _, pc, chunk = lg_sm.shape
    cap = lg_pos.shape[-1]
    return [
        pl.BlockSpec((1, pc, chunk), page),           # sign_mantissa
        pl.BlockSpec((1, pc, chunk // 2), page),      # packed
        pl.BlockSpec((1, 1, cap), page),              # esc_pos
        pl.BlockSpec((1, 1, cap), page),              # esc_val
    ]


def _page_counts(cnt, table):
    """(B, P) escape count of each logical page (unmapped -> page 0's)."""
    return cnt.reshape(-1)[jnp.maximum(table, 0)].astype(jnp.int32)


# ---------------------------------------------------------------------------
# the fused paged-GQA kernel
# ---------------------------------------------------------------------------

def _paged_gqa_kernel(
    # scalar prefetch
    pt_k, pt_v, lens, cnt_k, cnt_v,
    # tensor inputs
    q_ref,
    k_sm, k_packed, k_pos, k_val,
    v_sm, v_packed, v_pos, v_val,
    # outputs
    acc_ref, m_ref, l_ref,
    # scratch
    m_sc, l_sc, acc_sc,
    *, exponents, mbits, bits_width, tokens_per_page, hkv, head_dim, dv,
    nq, causal, scale, fmt,
):
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)
    rows = q_ref.shape[2]                     # nq * g query rows per KV head

    @pl.when(p == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    n_full = lens[b, 0]
    cache_len = lens[b, 1]

    @pl.when(p < n_full)
    def _():
        dec = functools.partial(
            _decode_page, tokens=tokens_per_page, exponents=exponents,
            mbits=mbits, bits_width=bits_width, fmt=fmt)
        k_tile = dec(k_packed, k_sm, k_pos, k_val, cnt_k[b, p],
                     m=hkv * head_dim)
        v_tile = dec(v_packed, v_sm, v_pos, v_val, cnt_v[b, p], m=hkv * dv)
        for h in range(hkv):                  # one 2-D matmul per KV head
            q = q_ref[0, h].astype(jnp.float32)                  # (R, hd)
            s = _dot_t(q, k_tile[:, h * head_dim:(h + 1) * head_dim]) * scale
            if causal:
                s = _causal_mask(s, p, cache_len,
                                 tokens_per_page=tokens_per_page, nq=nq,
                                 rows_per_q=rows // nq)
            _flash_update(s, v_tile[:, h * dv:(h + 1) * dv],
                          m_sc.at[h], l_sc.at[h], acc_sc.at[h])

    @pl.when(p == n_pages - 1)
    def _():
        acc_ref[0] = acc_sc[...]
        m_ref[0] = m_sc[...]
        l_ref[0] = l_sc[...]


@functools.partial(
    jax.jit,
    static_argnames=("exponents", "fmt", "chunk", "tokens_per_page", "hkv",
                     "causal", "scale", "interpret"),
)
def paged_gqa_attention(
    q: jax.Array,                   # (B, nq, H, hd)
    k_streams, v_streams,           # 5-tuples: sm, packed, pos, val, cnt
    page_table_k: jax.Array,        # (B, P) i32; -1 = unmapped
    page_table_v: jax.Array,
    cache_len: jax.Array,           # (B,) i32 tokens covered by pages+tail
    *, exponents: tuple, fmt: str = "bf16", chunk: int,
    tokens_per_page: int, hkv: int, causal: bool = True,
    scale: float | None = None, interpret: bool | None = None,
):
    """Fused attention over compressed pages -> un-normalized partials.

    Returns ``(acc, m, l)`` with ``acc (B, nq, H, dv) f32``, ``m/l (B, nq, H)
    f32`` covering the FULL pages only (``cache_len // tokens_per_page`` per
    row); merge the raw tail page via :func:`tail_partials` +
    :func:`merge_partials`, then :func:`finalize`."""
    spec = FORMATS[fmt]
    b, nq, h, hd = q.shape
    g = h // hkv
    n_pages_max = page_table_k.shape[1]
    k_sm, k_packed, k_pos, k_val, k_cnt = k_streams
    v_sm, v_packed, v_pos, v_val, v_cnt = v_streams
    # K and V have independent page geometry (dv may differ from head_dim):
    # each leaf's BlockSpecs come from its own stream shapes.
    dv = (v_sm.shape[1] * v_sm.shape[2]) // tokens_per_page // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    n_full = cache_len // tokens_per_page
    lens = jnp.stack([n_full, cache_len], axis=1).astype(jnp.int32)
    # queries grouped by KV head: (B, hkv, nq * g, hd), rows (query, group)
    qh = q.reshape(b, nq, hkv, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, nq * g, hd)
    rows = nq * g

    kernel = functools.partial(
        _paged_gqa_kernel,
        exponents=tuple(int(e) for e in exponents), mbits=spec["mbits"],
        bits_width=spec["bits"], tokens_per_page=tokens_per_page, hkv=hkv,
        head_dim=hd, dv=dv, nq=nq, causal=causal, scale=float(scale),
        fmt=fmt,
    )
    per_row = lambda b_, p_, *s: (b_, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, n_pages_max),
        in_specs=[
            pl.BlockSpec((1, hkv, rows, hd), per_row),
            *_page_inputs(k_sm, k_pos, table=0),
            *_page_inputs(v_sm, v_pos, table=1),
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, rows, dv), per_row),
            pl.BlockSpec((1, hkv, rows, 1), per_row),
            pl.BlockSpec((1, hkv, rows, 1), per_row),
        ],
        scratch_shapes=[
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, dv), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, rows, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, rows, 1), jnp.float32),
        ],
        interpret=auto_interpret(interpret),
    )(page_table_k, page_table_v, lens,
      _page_counts(k_cnt, page_table_k), _page_counts(v_cnt, page_table_v),
      qh, k_sm, k_packed, k_pos, k_val, v_sm, v_packed, v_pos, v_val)

    def heads_last(x):          # (B, hkv, nq*g, d) -> (B, nq, H, d)
        d = x.shape[-1]
        return x.reshape(b, hkv, nq, g, d).transpose(0, 2, 1, 3, 4).reshape(
            b, nq, h, d)

    return heads_last(acc), heads_last(m)[..., 0], heads_last(l)[..., 0]


# ---------------------------------------------------------------------------
# the fused paged-MLA kernel (absorbed-form decode over latent pages)
# ---------------------------------------------------------------------------

def _paged_mla_kernel(
    pt_ckv, pt_kr, lens, cnt_c, cnt_r,
    ql_ref, qr_ref,
    c_sm, c_packed, c_pos, c_val,
    r_sm, r_packed, r_pos, r_val,
    acc_ref, m_ref, l_ref,
    m_sc, l_sc, acc_sc,
    *, exponents, mbits, bits_width, tokens_per_page, kv_rank, rope_dim,
    nq, causal, scale, fmt,
):
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)
    rows = ql_ref.shape[1]                    # nq * H query rows

    @pl.when(p == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    n_full = lens[b, 0]
    cache_len = lens[b, 1]

    @pl.when(p < n_full)
    def _():
        dec = functools.partial(
            _decode_page, tokens=tokens_per_page, exponents=exponents,
            mbits=mbits, bits_width=bits_width, fmt=fmt)
        ckv = dec(c_packed, c_sm, c_pos, c_val, cnt_c[b, p], m=kv_rank)
        krope = dec(r_packed, r_sm, r_pos, r_val, cnt_r[b, p], m=rope_dim)
        ql = ql_ref[0].astype(jnp.float32)                 # (R, r)
        qr = qr_ref[0].astype(jnp.float32)                 # (R, p)
        s = (_dot_t(ql, ckv) + _dot_t(qr, krope)) * scale
        if causal:
            s = _causal_mask(s, p, cache_len,
                             tokens_per_page=tokens_per_page, nq=nq,
                             rows_per_q=rows // nq)
        _flash_update(s, ckv, m_sc, l_sc, acc_sc)

    @pl.when(p == n_pages - 1)
    def _():
        acc_ref[0] = acc_sc[...]
        m_ref[0] = m_sc[...]
        l_ref[0] = l_sc[...]


@functools.partial(
    jax.jit,
    static_argnames=("exponents", "fmt", "chunk", "tokens_per_page",
                     "causal", "scale", "interpret"),
)
def paged_mla_attention(
    q_lat: jax.Array,               # (B, nq, H, kv_rank) absorbed query
    q_rope: jax.Array,              # (B, nq, H, rope_dim)
    ckv_streams, krope_streams,     # 5-tuples
    page_table_ckv: jax.Array, page_table_krope: jax.Array,
    cache_len: jax.Array,
    *, exponents: tuple, fmt: str = "bf16", chunk: int,
    tokens_per_page: int, scale: float, causal: bool = True,
    interpret: bool | None = None,
):
    """Absorbed-form MLA attention over compressed latent pages.

    Scores are ``q_lat . ckv + q_rope . krope``; the context is accumulated
    over the decoded ``ckv`` tile, so ``acc`` is latent-space ``(B, nq, H,
    kv_rank)`` and the caller applies the ``w_v``/``wo`` up-projections after
    the tail merge (exactly ``mla.mla_decode``'s structure)."""
    spec = FORMATS[fmt]
    b, nq, h, kv_rank = q_lat.shape
    rope_dim = q_rope.shape[-1]
    n_pages_max = page_table_ckv.shape[1]
    rows = nq * h
    n_full = cache_len // tokens_per_page
    lens = jnp.stack([n_full, cache_len], axis=1).astype(jnp.int32)

    kernel = functools.partial(
        _paged_mla_kernel,
        exponents=tuple(int(e) for e in exponents), mbits=spec["mbits"],
        bits_width=spec["bits"], tokens_per_page=tokens_per_page,
        kv_rank=kv_rank, rope_dim=rope_dim, nq=nq, causal=causal,
        scale=float(scale), fmt=fmt,
    )
    # ckv and krope have independent page geometry (kv_lora_rank vs
    # qk_rope_head_dim): each leaf's BlockSpecs come from its own streams.
    per_row = lambda b_, p_, *s: (b_, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, n_pages_max),
        in_specs=[
            pl.BlockSpec((1, rows, kv_rank), per_row),
            pl.BlockSpec((1, rows, rope_dim), per_row),
            *_page_inputs(ckv_streams[0], ckv_streams[2], table=0),
            *_page_inputs(krope_streams[0], krope_streams[2], table=1),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, kv_rank), per_row),
            pl.BlockSpec((1, rows, 1), per_row),
            pl.BlockSpec((1, rows, 1), per_row),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, kv_rank), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, rows, kv_rank), jnp.float32),
            jax.ShapeDtypeStruct((b, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, rows, 1), jnp.float32),
        ],
        interpret=auto_interpret(interpret),
    )(page_table_ckv, page_table_krope, lens,
      _page_counts(ckv_streams[4], page_table_ckv),
      _page_counts(krope_streams[4], page_table_krope),
      q_lat.reshape(b, rows, kv_rank), q_rope.reshape(b, rows, rope_dim),
      *ckv_streams[:4], *krope_streams[:4])
    return (acc.reshape(b, nq, h, kv_rank), m.reshape(b, nq, h),
            l.reshape(b, nq, h))


# ---------------------------------------------------------------------------
# tail partials + softmax-partial merge (shared by both families)
# ---------------------------------------------------------------------------

def tail_partials(s: jax.Array, v: jax.Array, valid: jax.Array):
    """Un-normalized flash partials for the raw tail page.

    ``s``: (B, nq, ..., T) f32 scores (already scaled), ``v``: (B, T, dv) or
    (B, T, hkv, dv) values, ``valid``: (B, T) bool.  Returns (acc, m, l)
    shaped like the kernel partials so :func:`merge_partials` composes."""
    extra = s.ndim - 3                                     # dims between nq and T
    vm = valid.reshape(valid.shape[0], *([1] * (extra + 1)), valid.shape[1])
    s = jnp.where(vm, s, NEG_INF)
    m = s.max(axis=-1)
    pexp = jnp.exp(s - m[..., None])
    l = pexp.sum(axis=-1)
    pb, vb = pexp.astype(jnp.bfloat16), v.astype(jnp.bfloat16)  # as the kernel
    if v.ndim == 3:                                        # (B, T, dv) latent
        acc = jnp.einsum("bqht,btd->bqhd", pb, vb,
                         preferred_element_type=jnp.float32)
    else:                                                  # (B, T, hkv, dv)
        acc = jnp.einsum("bqhgt,bthd->bqhgd", pb, vb,
                         preferred_element_type=jnp.float32)
    return acc, m, l


def merge_partials(a, b):
    """Combine two un-normalized flash partials (acc, m, l)."""
    acc_a, m_a, l_a = a
    acc_b, m_b, l_b = b
    m = jnp.maximum(m_a, m_b)
    ca = jnp.exp(m_a - m)
    cb = jnp.exp(m_b - m)
    return (acc_a * ca[..., None] + acc_b * cb[..., None],
            m, l_a * ca + l_b * cb)


def finalize(acc, l, dtype=jnp.bfloat16):
    return (acc / jnp.maximum(l[..., None], 1e-30)).astype(dtype)
