"""Jit'd wrappers around the single-pass fused SplitZip Pallas kernels.

``encode``/``decode`` here are drop-in replacements for
:mod:`repro.core.codec`'s pure-XLA versions.  Encode emits the complete
``CompressedTensor`` streams (dense + compacted escapes + true per-chunk
counts) from ONE ``pallas_call``; decode consumes the escape buffers inside
the dense kernel and emits final container bits — no post-kernel full-stream
pass (field re-extract, cumsum, scatter, join) remains on either side.  The
pre-fusion structure survives in :mod:`repro.kernels.twostage` for A/B
comparison (``PallasBackend(fused=False)``) and for escape capacities above
``MAX_FUSED_CAP``, where the in-kernel compaction passes would dominate
the kernel.

On non-TPU backends the kernels run in ``interpret=True`` mode (Python
semantics of the kernel body), which is how this repo validates them on CPU;
on TPU they compile to Mosaic.

Both escape layouts of the core codec are supported.  ``layout='chunked'``
(the paper's per-chunk buffers) is fully fused end-to-end.  ``layout='global'``
(two-level per-tensor compaction) keeps a bounded XLA second level: encode
compacts the kernel's per-chunk buffers into the global buffer — consuming
the kernel's per-row counts, never recomputing the escape mask over the
stream (:func:`repro.core.codec.compact_chunked_to_global`) — and decode
patches escape positions directly into the kernel's output bits, touching
only the ~0.16% escaped elements instead of re-extracting and rejoining the
whole stream.

The serving path reaches these wrappers through the ``pallas`` entry of the
:mod:`repro.core.backend` registry, never by importing this module directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec as core_codec
from repro.core.codebook import FORMATS, Codebook
from repro.kernels import splitzip_decode, splitzip_encode, twostage
from repro.kernels.splitzip_encode import MAX_FUSED_CAP


def encode(
    x: jax.Array,
    codebook: Codebook,
    chunk: int = core_codec.DEFAULT_CHUNK,
    cap: int = core_codec.DEFAULT_CAP,
    layout: str = "chunked",
    block_rows: int = splitzip_encode.DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
    fused: bool = True,
) -> core_codec.CompressedTensor:
    """SplitZip encode with the single-pass fused Pallas kernel."""
    if not fused or (layout != "global" and cap > MAX_FUSED_CAP):
        # two-stage A/B path, or a capacity too large to compact in-kernel
        return twostage.encode(x, codebook, chunk=chunk, cap=cap,
                               layout=layout, block_rows=block_rows,
                               interpret=interpret)
    fmt = codebook.fmt
    orig_shape, orig_dtype = x.shape, x.dtype
    bits = core_codec.to_bits(x, fmt).reshape(-1)
    pad_e = codebook.exponents[0]
    pad_bits = jnp.asarray(np.uint64(pad_e) << FORMATS[fmt]["mbits"], dtype=bits.dtype)
    bits = core_codec._pad_to_chunk(bits, chunk, pad_bits)
    rows = bits.shape[0] // chunk
    bits2 = bits.reshape(rows, chunk)

    kcap = cap if layout != "global" else min(chunk, MAX_FUSED_CAP)
    a, packed, esc_pos_c, esc_val_c, cnt = splitzip_encode.encode_fused(
        bits2,
        tuple(codebook.exponents),
        fmt=fmt,
        chunk=chunk,
        cap=kcap,
        block_rows=block_rows,
        interpret=interpret,
    )
    esc_count = cnt.reshape(-1)
    if layout == "global":
        if cap == core_codec.DEFAULT_CAP:
            cap = core_codec.default_global_cap(bits.shape[0])
        # bounded second level over C×cap1 entries (not the full stream)
        esc_pos, esc_val, esc_count, ok = core_codec.compact_chunked_to_global(
            esc_pos_c, esc_val_c, esc_count, chunk, cap, bits.shape[0])
    else:
        esc_pos, esc_val = esc_pos_c, esc_val_c
        ok = jnp.all(esc_count <= cap)  # O(C) reduction over the counts
    return core_codec.CompressedTensor(
        sign_mantissa=a.reshape(-1),
        packed=packed.reshape(-1),
        esc_pos=esc_pos,
        esc_val=esc_val,
        esc_count=esc_count,
        ok=ok,
        shape=tuple(orig_shape),
        dtype=str(orig_dtype),
        fmt=fmt,
        exponents=tuple(codebook.exponents),
        chunk=chunk,
        cap=cap,
        layout=layout,
    )


def _patch_escape_bits(bits: jax.Array,
                       ct: core_codec.CompressedTensor) -> jax.Array:
    """Sparse bit-level correction for layouts the kernel can't consume
    per-row (global buffer / oversized caps): patch the exponent field of the
    kernel's output bits at escape positions only — a bounded gather/scatter
    over the ≤cap escape entries, never a full-stream pass."""
    spec = FORMATS[ct.fmt]
    mbits, ebits, width = spec["mbits"], spec["ebits"], spec["bits"]
    n_pad = bits.shape[0]
    if ct.layout == "global":
        flat = ct.esc_pos.reshape(-1).astype(jnp.int32)  # padding == n_pad
    else:
        c = ct.esc_pos.shape[0]
        base = (jnp.arange(c, dtype=jnp.int32) * ct.chunk)[:, None]
        pos = ct.esc_pos.astype(jnp.int32)               # padding == chunk
        flat = jnp.where(pos < ct.chunk, base + pos, n_pad).reshape(-1)
    val = ct.esc_val.reshape(-1).astype(bits.dtype)
    cur = bits[jnp.minimum(flat, n_pad - 1)]
    keep = jnp.asarray(((1 << width) - 1) ^ (((1 << ebits) - 1) << mbits),
                       dtype=bits.dtype)
    patched = (cur & keep) | (val << mbits)
    return bits.at[flat].set(patched, mode="drop")


def decode_bits(
    ct: core_codec.CompressedTensor,
    block_rows: int = splitzip_decode.DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
    fused: bool = True,
) -> jax.Array:
    """Fused decode to the FLAT container bit stream (length n_elements)."""
    if not fused:
        return twostage.decode_to_bits(ct, block_rows=block_rows,
                                       interpret=interpret)
    chunk = ct.chunk
    rows = ct.n_padded // chunk
    packed2 = ct.packed.reshape(rows, chunk // 2)
    a2 = ct.sign_mantissa.reshape(rows, chunk)
    if ct.layout == "chunked" and ct.cap <= MAX_FUSED_CAP:
        # fully fused: the kernel applies the sparse correction and emits
        # final bits; the clipped per-row counts bound its slot loop
        cnt = jnp.minimum(ct.esc_count, ct.cap).astype(jnp.int32)
        bits2 = splitzip_decode.decode_fused(
            packed2, a2, ct.esc_pos, ct.esc_val, cnt.reshape(rows, 1),
            tuple(ct.exponents), fmt=ct.fmt, chunk=chunk,
            block_rows=block_rows, interpret=interpret)
        return bits2.reshape(-1)[:ct.n_elements]
    bits2 = splitzip_decode.decode_dense(
        packed2, a2, tuple(ct.exponents), fmt=ct.fmt, chunk=chunk,
        block_rows=block_rows, interpret=interpret)
    bits = _patch_escape_bits(bits2.reshape(-1), ct)
    return bits[:ct.n_elements]


def decode(
    ct: core_codec.CompressedTensor,
    block_rows: int = splitzip_decode.DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
    fused: bool = True,
) -> jax.Array:
    """SplitZip decode with the single-pass fused Pallas kernel."""
    bits = decode_bits(ct, block_rows=block_rows, interpret=interpret,
                       fused=fused)
    return core_codec.from_bits(bits.reshape(ct.shape), jnp.dtype(ct.dtype))
