"""Two-stage Pallas codec path: dense kernels + XLA escape compaction.

This is the pre-fusion structure — the dense transformation runs in a Pallas
kernel while escape collection / sparse correction are separate XLA passes
over the full stream (the paper's literal two-stage description).  It is kept
for A/B benchmarking against the fused single-pass path
(:mod:`repro.kernels.ops`, ``PallasBackend(fused=False)``) and as the
dispatch target for escape capacities above
:data:`repro.kernels.splitzip_encode.MAX_FUSED_CAP`, where the in-kernel
compaction passes would dominate the kernel.

Cost model (why the fused path exists): per codec call this path re-reads
the full bit stream to re-derive the exponent field (encode: ``split_fields``
after the kernel already computed it; decode: re-extract before the scatter),
then runs cumsum + scatter / scatter + ``join_fields`` as additional
full-tensor HBM round-trips — three-plus extra stream passes and XLA launches
that the fused kernels eliminate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec as core_codec
from repro.core.codebook import FORMATS, Codebook
from repro.kernels import splitzip_decode, splitzip_encode


def encode(
    x: jax.Array,
    codebook: Codebook,
    chunk: int = core_codec.DEFAULT_CHUNK,
    cap: int = core_codec.DEFAULT_CAP,
    layout: str = "chunked",
    block_rows: int = splitzip_encode.DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> core_codec.CompressedTensor:
    """Two-stage encode: Pallas dense kernel + XLA escape collection."""
    fmt = codebook.fmt
    orig_shape, orig_dtype = x.shape, x.dtype
    bits = core_codec.to_bits(x, fmt).reshape(-1)
    pad_e = codebook.exponents[0]
    pad_bits = jnp.asarray(np.uint64(pad_e) << FORMATS[fmt]["mbits"], dtype=bits.dtype)
    bits = core_codec._pad_to_chunk(bits, chunk, pad_bits)
    rows = bits.shape[0] // chunk
    bits2 = bits.reshape(rows, chunk)

    a, packed, is_esc = splitzip_encode.encode_dense(
        bits2,
        tuple(codebook.exponents),
        fmt=fmt,
        chunk=chunk,
        block_rows=block_rows,
        interpret=interpret,
    )
    # stage 2 (XLA): full-stream field re-extract + cumsum + bounded scatter
    e, _ = core_codec.split_fields(bits, fmt)
    member = ~(is_esc.reshape(-1).astype(bool))
    if layout == "global":
        if cap == core_codec.DEFAULT_CAP:
            cap = core_codec.default_global_cap(bits.shape[0])
        esc_pos, esc_val, esc_count, ok = core_codec.collect_escapes_global(
            e, member, cap)
    else:
        esc_pos, esc_val, esc_count, ok = core_codec.collect_escapes(
            e, member, chunk, cap)
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


def decode_to_bits(
    ct: core_codec.CompressedTensor,
    block_rows: int = splitzip_decode.DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """Two-stage decode to flat bits: dense kernel + XLA sparse correction."""
    chunk = ct.chunk
    rows = ct.n_padded // chunk
    packed2 = ct.packed.reshape(rows, chunk // 2)
    a2 = ct.sign_mantissa.reshape(rows, chunk)
    bits2 = splitzip_decode.decode_dense(
        packed2,
        a2,
        tuple(ct.exponents),
        fmt=ct.fmt,
        chunk=chunk,
        block_rows=block_rows,
        interpret=interpret,
    )
    # stage 2 (XLA): re-extract the exponent field over the full stream,
    # scatter the escapes, and reassemble — three more full-stream passes
    bits = bits2.reshape(-1)
    spec = FORMATS[ct.fmt]
    mbits, ebits = spec["mbits"], spec["ebits"]
    e = ((bits.astype(jnp.int32) >> mbits) & ((1 << ebits) - 1)).astype(jnp.uint8)
    if ct.layout == "global":
        e = core_codec.scatter_escapes_global(e, ct.esc_pos, ct.esc_val)
    else:
        e = core_codec.scatter_escapes(e, ct.esc_pos, ct.esc_val, chunk)
    bits = core_codec.join_fields(e, ct.sign_mantissa, ct.fmt)
    return bits[:ct.n_elements]


def decode(
    ct: core_codec.CompressedTensor,
    block_rows: int = splitzip_decode.DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """Two-stage decode: dense Pallas kernel + XLA sparse correction."""
    bits = decode_to_bits(ct, block_rows=block_rows, interpret=interpret)
    return core_codec.from_bits(bits.reshape(ct.shape), jnp.dtype(ct.dtype))
