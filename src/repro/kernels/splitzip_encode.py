"""Pallas TPU kernel: SplitZip single-pass fused encode (paper §3.2).

``encode_fused`` emits the complete per-chunk compressed streams — field
split, codebook lookup, nibble packing, AND the sparse escape compaction —
from one ``pallas_call``.  The paper describes a two-stage encode ("a
separate escape-collection stage keeps the common path simple and regular");
that structure survives *inside* the kernel as two phases over the same VMEM
tile, so the bit stream is read from HBM exactly once and no post-kernel
full-stream pass remains.  The pre-fusion dense-only kernel is kept as
``encode_dense`` for the two-stage A/B path (:mod:`repro.kernels.twostage`).

TPU adaptation (DESIGN.md §2): the GPU version gathers through a 256-byte
encode LUT; a per-lane byte gather is not VPU-shaped, so we bake the 16
calibrated exponents in as compile-time scalars and evaluate 16 broadcast
compares per element.  All arithmetic is int32 (native VPU width); inputs and
outputs are narrow integer streams.

In-kernel escape compaction (the fused stage 2) is gather/scatter-free:

  rank   : per-row inclusive prefix sum of the escape mask (log2(chunk)
           shift-add steps — Hillis-Steele, VPU-shaped, no lax.cumsum
           dependency in Mosaic),
  slot j : ``esc_pos[r, j] = chunk - Σ_c (chunk - c)·[rank masked == j+1]``
           and ``esc_val[r, j] = Σ_c e[r, c]·[rank masked == j+1]`` — one
           compare + two masked reductions per capacity slot.  A row with
           fewer than j+1 escapes contributes an empty mask, so the slot
           naturally lands on the padding convention (pos == chunk, val == 0).

The slot loop runs up to the block's largest escape count (at most ``cap``):
at the paper's escape rates (ε ≈ 0.16%, ~2 escapes per 1024-chunk) only a
handful of slots execute, so the fused stage adds ~O(blockmax) VPU passes —
comparable to the 16-compare dense stage — instead of cap passes.
Capacities above ``MAX_FUSED_CAP`` are not fused (the slot passes would
dominate); :mod:`repro.kernels.ops` routes those to the two-stage path.

Nibble packing puts element ``c`` of a chunk row in the low nibble and
element ``c + chunk/2`` in the high one: both are lane-aligned halves of the
tile, which Mosaic packs with plain VPU ops (an adjacent-pair pack needs a
lane shuffle Mosaic cannot express).

Tiling: the flat bit stream is viewed as (rows, CHUNK) with CHUNK = the
escape-chunk size (1024 = 8 sublanes × 128 lanes, hardware-aligned).  Each
grid step processes BLOCK_ROWS rows (a multiple of 32, the u8 sublane tile;
the last step may be partial); with BLOCK_ROWS = 256 the working set is
  in  : 256×1024×4B (i32 upcast of the u16 bits)     = 1.0 MiB
  out : a (1B) + packed (0.5B) + escapes (~3B·cap/chunk) = 0.6 MiB
inside the 16 MiB of VMEM a v5e kernel may use by default, with double
buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.codebook import FORMATS
from repro.kernels import auto_interpret

DEFAULT_BLOCK_ROWS = 256

#: Largest per-chunk escape capacity the fused kernel compacts; above
#: this the compaction passes would dominate the kernel and the two-stage
#: path wins (see kernels/ops.py dispatch).
MAX_FUSED_CAP = 128


#: Rows of a grid step are a multiple of this: the kernels write ``uint8``
#: blocks, which the TPU tiles in (32, 128) slabs.
ROW_ALIGN = 32


def fit_block_rows(rows: int, want: int) -> int:
    """Rows per grid step: ``want`` rounded down to :data:`ROW_ALIGN`, or
    all of ``rows`` when that is no more.  The grid is ``cdiv(rows, br)``;
    the last step may be partial (its out-of-range rows are never written)."""
    br = max(ROW_ALIGN, want // ROW_ALIGN * ROW_ALIGN)
    return rows if rows <= br else br


def _split_and_code(x, *, exponents, mbits, ebits):
    """Shared dense phase: field split + compare-select code assignment."""
    e = (x >> mbits) & ((1 << ebits) - 1)
    a = ((x >> ebits) & (1 << mbits)) | (x & ((1 << mbits) - 1))
    code = jnp.zeros_like(e)
    member = jnp.zeros(e.shape, dtype=jnp.bool_)
    for idx, ce in enumerate(exponents):  # static unroll, K <= 16
        hit = e == ce
        code = jnp.where(hit, idx, code)
        member = member | hit
    return e, a, code, member


def _pack_halves(code):
    """Pack two 4-bit codes per byte: element ``c`` of a chunk row in the low
    nibble, element ``c + chunk/2`` in the high one (lane-aligned halves)."""
    half = code.shape[1] // 2
    return (code[:, :half] | (code[:, half:] << 4)).astype(jnp.uint8)


def _inclusive_cumsum_lanes(x, chunk):
    """Hillis-Steele inclusive prefix sum along the lane (last) axis.

    log2(chunk) shift-add steps on (rows, chunk) int32 — expressed as
    pad+slice so it lowers on Mosaic without relying on lax.cumsum support.
    """
    s = x
    d = 1
    while d < chunk:
        s = s + jnp.pad(s, ((0, 0), (d, 0)))[:, :chunk]
        d *= 2
    return s


def _encode_kernel(bits_ref, a_ref, packed_ref, esc_ref, *, exponents, mbits, ebits):
    x = bits_ref[...].astype(jnp.int32)
    _, a, code, member = _split_and_code(
        x, exponents=exponents, mbits=mbits, ebits=ebits)
    a_ref[...] = a.astype(jnp.uint8)
    esc_ref[...] = (~member).astype(jnp.uint8)
    packed_ref[...] = _pack_halves(code)


def _encode_fused_kernel(
    bits_ref, a_ref, packed_ref, esc_pos_ref, esc_val_ref, esc_cnt_ref,
    *, exponents, mbits, ebits, chunk, cap,
):
    x = bits_ref[...].astype(jnp.int32)
    e, a, code, member = _split_and_code(
        x, exponents=exponents, mbits=mbits, ebits=ebits)
    a_ref[...] = a.astype(jnp.uint8)
    packed_ref[...] = _pack_halves(code)

    # ---- fused stage 2: per-row escape compaction, gather/scatter-free ----
    r = x.shape[0]
    is_esc = (~member).astype(jnp.int32)
    s = _inclusive_cumsum_lanes(is_esc, chunk)      # rank+1 at each escape
    count = s[:, chunk - 1:chunk]                   # (r, 1) TRUE per-row count
    esc_cnt_ref[...] = count
    se = s * is_esc                                 # 0 off-escape, rank+1 on

    # chunk - c per lane: one masked reduction gives both the position and
    # the padding fallback (empty mask -> pos = chunk) without a gather
    wpos = chunk - jax.lax.broadcasted_iota(jnp.int32, (r, chunk), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (r, cap), 1)

    def fill_slot(j, carry):
        pos, val = carry
        m = (se == j + 1).astype(jnp.int32)
        pos_j = chunk - jnp.sum(wpos * m, axis=-1, keepdims=True)
        val_j = jnp.sum(e * m, axis=-1, keepdims=True)
        return (jnp.where(slot == j, pos_j, pos), jnp.where(slot == j, val_j, val))

    # padding convention first (pos == chunk -> dropped on decode, val == 0);
    # only slots below the block's largest count run a pass
    pos, val = jax.lax.fori_loop(
        0, jnp.minimum(jnp.max(count), cap), fill_slot,
        (jnp.full((r, cap), chunk, jnp.int32), jnp.zeros((r, cap), jnp.int32)))
    esc_pos_ref[...] = pos.astype(jnp.uint16)
    esc_val_ref[...] = val.astype(jnp.uint8)


@functools.partial(
    jax.jit, static_argnames=("exponents", "fmt", "chunk", "block_rows", "interpret")
)
def encode_dense(
    bits: jax.Array,
    exponents: tuple,
    fmt: str = "bf16",
    chunk: int = 1024,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
):
    """Dense-only encode of a (rows, chunk) bit tensor (two-stage A/B path).

    Returns (sign_mantissa u8[rows,chunk], packed u8[rows,chunk//2],
    is_escape u8[rows,chunk]); escape compaction happens outside (XLA).
    """
    spec = FORMATS[fmt]
    rows, c = bits.shape
    if c != chunk:
        raise ValueError(f"expected trailing dim == chunk ({chunk}), got {c}")
    br = fit_block_rows(rows, block_rows)
    grid = (pl.cdiv(rows, br),)
    kernel = functools.partial(
        _encode_kernel,
        exponents=tuple(int(e) for e in exponents),
        mbits=spec["mbits"],
        ebits=spec["ebits"],
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, chunk), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((br, chunk), lambda i: (i, 0)),
            pl.BlockSpec((br, chunk // 2), lambda i: (i, 0)),
            pl.BlockSpec((br, chunk), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, chunk), jnp.uint8),
            jax.ShapeDtypeStruct((rows, chunk // 2), jnp.uint8),
            jax.ShapeDtypeStruct((rows, chunk), jnp.uint8),
        ],
        interpret=auto_interpret(interpret),
    )(bits)


@functools.partial(
    jax.jit,
    static_argnames=("exponents", "fmt", "chunk", "cap", "block_rows", "interpret"),
)
def encode_fused(
    bits: jax.Array,
    exponents: tuple,
    fmt: str = "bf16",
    chunk: int = 1024,
    cap: int = 64,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
):
    """Single-pass fused encode of a (rows, chunk) bit tensor.

    One ``pallas_call`` returns the complete per-chunk streams:
    (sign_mantissa u8[rows,chunk], packed u8[rows,chunk//2],
    esc_pos u16[rows,cap], esc_val u8[rows,cap], esc_count i32[rows,1]).
    ``esc_count`` is the TRUE per-row escape count (may exceed ``cap``;
    entries beyond ``cap`` are dropped, matching
    :func:`repro.core.codec.collect_escapes`).
    """
    spec = FORMATS[fmt]
    rows, c = bits.shape
    if c != chunk:
        raise ValueError(f"expected trailing dim == chunk ({chunk}), got {c}")
    if cap > MAX_FUSED_CAP:
        raise ValueError(
            f"cap ({cap}) exceeds MAX_FUSED_CAP ({MAX_FUSED_CAP}); use the "
            "two-stage path (repro.kernels.twostage) for oversized capacities")
    br = fit_block_rows(rows, block_rows)
    grid = (pl.cdiv(rows, br),)
    kernel = functools.partial(
        _encode_fused_kernel,
        exponents=tuple(int(e) for e in exponents),
        mbits=spec["mbits"],
        ebits=spec["ebits"],
        chunk=chunk,
        cap=cap,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, chunk), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((br, chunk), lambda i: (i, 0)),
            pl.BlockSpec((br, chunk // 2), lambda i: (i, 0)),
            pl.BlockSpec((br, cap), lambda i: (i, 0)),
            pl.BlockSpec((br, cap), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, chunk), jnp.uint8),
            jax.ShapeDtypeStruct((rows, chunk // 2), jnp.uint8),
            jax.ShapeDtypeStruct((rows, cap), jnp.uint16),
            jax.ShapeDtypeStruct((rows, cap), jnp.uint8),
            jax.ShapeDtypeStruct((rows, 1), jnp.int32),
        ],
        interpret=auto_interpret(interpret),
    )(bits)
