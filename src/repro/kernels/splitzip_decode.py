"""Pallas TPU kernel: SplitZip single-pass fused decode (paper §3.2).

``decode_fused`` unpacks two 4-bit codes per byte, maps each through the
16-entry codebook (baked in as compile-time scalars — a one-hot select chain
instead of a gather), reassembles the BF16/FP8 bit pattern with the exact
sign-mantissa stream, AND applies the sparse escape correction — all inside
one ``pallas_call`` that emits the final container bits.  The paper's "dense
lookup path + separate sparse overwrite" structure (its Table 6 ablation
shows it 3.5× faster than sentinel-style in-stream detection) survives as
two phases over the same VMEM tile; no post-kernel re-extract → scatter →
join-fields pass over the full stream remains.

The in-kernel correction is scatter-free: capacity slot j broadcasts its
per-row ``(pos, val)`` pair across the lane axis and predicated-selects the
exponent field where ``lane == pos`` — padding entries carry ``pos == chunk``
and can never match.  The slot loop runs up to the largest per-row count in
the block (the per-row counts arrive as a kernel input — the encode kernel
already computed them), so at the paper's escape rates only a handful of
slots execute.

``decode_dense`` (the pre-fusion dense-only kernel) is kept for the
two-stage A/B path and for layouts whose correction stays outside the kernel
(``layout='global'`` and oversized capacities — see kernels/ops.py).

Tiling mirrors the encode kernel: (BLOCK_ROWS, CHUNK) tiles, CHUNK = 1024
lanes-aligned, everything int32 on the VPU.  The code stream holds chunk
halves in the two nibbles of a byte, so unpacking joins two lane-aligned
halves of the tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.codebook import FORMATS
from repro.kernels import auto_interpret
from repro.kernels.splitzip_encode import fit_block_rows

DEFAULT_BLOCK_ROWS = 256


def _unpack_and_lut(packed, *, exponents):
    """Shared dense phase: nibble unpack + one-hot × codebook contraction.

    ``packed`` is (rows, chunk//2): the low nibbles hold the first half of
    each chunk row and the high nibbles the second, so unpacking is two
    lane-aligned halves joined, with no lane shuffle."""
    code = jnp.concatenate([packed & 0xF, (packed >> 4) & 0xF], axis=-1)
    e = jnp.zeros_like(code)
    for idx, ce in enumerate(exponents):  # static unroll, K <= 16
        e = jnp.where(code == idx, ce, e)
    return e


def _assemble(e, a, *, mbits, bits_width):
    """x = (sign << (bits-1)) | (e << mbits) | mantissa."""
    sign = (a >> mbits) & 1
    return (sign << (bits_width - 1)) | (e << mbits) | (a & ((1 << mbits) - 1))


def _decode_kernel(packed_ref, a_ref, bits_ref, *, exponents, mbits, bits_width):
    packed = packed_ref[...].astype(jnp.int32)
    a = a_ref[...].astype(jnp.int32)
    e = _unpack_and_lut(packed, exponents=exponents)
    bits_ref[...] = _assemble(e, a, mbits=mbits, bits_width=bits_width
                              ).astype(bits_ref.dtype)


def _patch_escapes(bits, index, pos, val, n, *, mbits, bits_width):
    """Overwrite the exponent field of the i32 ``bits`` tile wherever
    ``index`` (same shape: each element's position in the escape coordinate)
    equals an escape position.  ``pos``/``val`` are i32 slot buffers, one
    row per tile row or a single row for the whole tile; padding positions
    match no element.  ``n`` bounds the slots visited.  Slot ``j`` is picked
    out of the buffers by a masked lane sum, so the loop index may be
    dynamic."""
    slot = jax.lax.broadcasted_iota(jnp.int32, pos.shape, 1)
    keep = ((1 << bits_width) - 1) ^ (((1 << (bits_width - mbits - 1)) - 1)
                                      << mbits)  # clears the exponent field

    def pick(buf, j):
        hit = jnp.where(slot == j, buf, 0)
        if buf.shape[0] == 1:          # one row serves the tile: a scalar
            return jnp.sum(hit)
        return jnp.sum(hit, axis=-1, keepdims=True)

    def patch(j, bits):
        return jnp.where(index == pick(pos, j),
                         (bits & keep) | (pick(val, j) << mbits), bits)

    return jax.lax.fori_loop(0, n, patch, bits)


def _decode_fused_kernel(
    packed_ref, a_ref, esc_pos_ref, esc_val_ref, esc_cnt_ref, bits_ref,
    *, exponents, mbits, bits_width, chunk, cap,
):
    packed = packed_ref[...].astype(jnp.int32)
    a = a_ref[...].astype(jnp.int32)
    e = _unpack_and_lut(packed, exponents=exponents)
    bits = _assemble(e, a, mbits=mbits, bits_width=bits_width)
    # ---- fused sparse correction: only slots below the block's largest
    # (clipped) per-row count run a pass
    n = jnp.minimum(jnp.max(esc_cnt_ref[...]), cap)
    lane = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1)
    bits = _patch_escapes(bits, lane, esc_pos_ref[...].astype(jnp.int32),
                          esc_val_ref[...].astype(jnp.int32), n, mbits=mbits,
                          bits_width=bits_width)
    bits_ref[...] = bits.astype(bits_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("exponents", "fmt", "chunk", "block_rows", "interpret")
)
def decode_dense(
    packed: jax.Array,
    sign_mantissa: jax.Array,
    exponents: tuple,
    fmt: str = "bf16",
    chunk: int = 1024,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
):
    """Dense decode to container bits: (rows, chunk//2) packed + (rows, chunk)
    sign-mantissa -> (rows, chunk) u16/u8 bit patterns (escapes still dummy)."""
    spec = FORMATS[fmt]
    rows, c = sign_mantissa.shape
    if c != chunk or packed.shape != (rows, chunk // 2):
        raise ValueError("stream shapes inconsistent with chunk")
    br = fit_block_rows(rows, block_rows)
    grid = (pl.cdiv(rows, br),)
    out_dtype = jnp.uint16 if spec["bits"] == 16 else jnp.uint8
    kernel = functools.partial(
        _decode_kernel,
        exponents=tuple(int(e) for e in exponents),
        mbits=spec["mbits"],
        bits_width=spec["bits"],
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, chunk // 2), lambda i: (i, 0)),
            pl.BlockSpec((br, chunk), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, chunk), out_dtype),
        interpret=auto_interpret(interpret),
    )(packed, sign_mantissa)


@functools.partial(
    jax.jit,
    static_argnames=("exponents", "fmt", "chunk", "block_rows", "interpret"),
)
def decode_fused(
    packed: jax.Array,
    sign_mantissa: jax.Array,
    esc_pos: jax.Array,
    esc_val: jax.Array,
    esc_count: jax.Array,
    exponents: tuple,
    fmt: str = "bf16",
    chunk: int = 1024,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
):
    """Single-pass fused decode to FINAL container bits.

    (rows, chunk//2) packed + (rows, chunk) sign-mantissa +
    (rows, cap) esc_pos u16 / esc_val u8 + (rows, 1) esc_count i32 (clipped
    to cap by the caller) -> (rows, chunk) u16/u8 bit patterns with the
    sparse correction already applied.
    """
    spec = FORMATS[fmt]
    rows, c = sign_mantissa.shape
    cap = esc_pos.shape[1]
    if c != chunk or packed.shape != (rows, chunk // 2):
        raise ValueError("stream shapes inconsistent with chunk")
    if esc_pos.shape != (rows, cap) or esc_val.shape != (rows, cap) \
            or esc_count.shape != (rows, 1):
        raise ValueError("escape stream shapes inconsistent with rows/cap")
    br = fit_block_rows(rows, block_rows)
    grid = (pl.cdiv(rows, br),)
    out_dtype = jnp.uint16 if spec["bits"] == 16 else jnp.uint8
    kernel = functools.partial(
        _decode_fused_kernel,
        exponents=tuple(int(e) for e in exponents),
        mbits=spec["mbits"],
        bits_width=spec["bits"],
        chunk=chunk,
        cap=cap,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, chunk // 2), lambda i: (i, 0)),
            pl.BlockSpec((br, chunk), lambda i: (i, 0)),
            pl.BlockSpec((br, cap), lambda i: (i, 0)),
            pl.BlockSpec((br, cap), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, chunk), out_dtype),
        interpret=auto_interpret(interpret),
    )(packed, sign_mantissa, esc_pos, esc_val, esc_count)
