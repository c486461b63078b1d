"""SplitZip in-graph codec (paper §3.2) — static-shape, jit/shard-friendly.

This is the codec that lives *inside* JAX programs (serving graphs, transfer
engines, gradient compression).  XLA requires static shapes, so the paper's
variable-length escape stream becomes a fixed-capacity per-chunk buffer plus a
per-tensor ``ok`` flag; callers (e.g. the transfer engine) fall back to raw
transfer when ``ok`` is False, so the system is unconditionally lossless.
Exact variable-length byte accounting is analytic (``compressed_bytes``) and
is cross-checked against the host wire codec in tests.

Layout for a tensor of N elements (N padded to a chunk multiple):

  sign_mantissa : u8[N]              exact `a_i` bytes (dense stream 1)
  packed        : u8[N//2]           two 4-bit codes per byte (dense stream 2;
                                     chunk halves share a byte)
  esc_pos       : u16[C, cap]        chunk-relative escape positions
  esc_val       : u8[C, cap]         raw escaped exponents
  esc_count     : i32[C]             true escapes per chunk (may exceed cap)
  ok            : bool[]             no chunk overflowed its escape capacity

TPU adaptation (DESIGN.md §2): encode membership/code assignment uses
broadcast-compare against the 16 codebook entries instead of a 256-byte LUT
gather; decode uses a one-hot × codebook contraction instead of a 16-entry
gather.  Both are VPU-shaped: fixed-width integer compares and reductions.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codebook import FORMATS, Codebook

DEFAULT_CHUNK = 1024  # paper §4.1: "chunked escape value with chunk size 1024"
DEFAULT_CAP = 64      # escape capacity per chunk (6.25%; paper's ε ≈ 0.16%)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CompressedTensor:
    """Pytree carrying the SplitZip streams for one tensor."""

    sign_mantissa: jax.Array  # u8[N]
    packed: jax.Array         # u8[N//2] (nibble-packed, k<=16) or u8[N] (k>16)
    esc_pos: jax.Array        # u16[C, cap]
    esc_val: jax.Array        # u8[C, cap]
    esc_count: jax.Array      # i32[C]
    ok: jax.Array             # bool[]

    # static metadata
    shape: tuple = dataclasses.field(metadata=dict(static=True))
    dtype: str = dataclasses.field(metadata=dict(static=True))
    fmt: str = dataclasses.field(metadata=dict(static=True))
    exponents: tuple = dataclasses.field(metadata=dict(static=True))
    chunk: int = dataclasses.field(metadata=dict(static=True))
    cap: int = dataclasses.field(metadata=dict(static=True))
    # 'chunked' (paper layout) or 'global' (two-level compaction, beyond-paper)
    layout: str = dataclasses.field(default="chunked", metadata=dict(static=True))

    @property
    def codebook(self) -> Codebook:
        return Codebook(fmt=self.fmt, exponents=self.exponents)

    @property
    def n_elements(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def n_padded(self) -> int:
        return self.sign_mantissa.shape[0]


# ---------------------------------------------------------------------------
# bit plumbing
# ---------------------------------------------------------------------------

def _uint_dtype(fmt: str):
    return jnp.uint16 if FORMATS[fmt]["bits"] == 16 else jnp.uint8


def to_bits(x: jax.Array, fmt: str = "bf16") -> jax.Array:
    """Bitcast a float tensor to its unsigned container type."""
    want = _uint_dtype(fmt)
    if x.dtype in (jnp.uint16, jnp.uint8):
        return x.astype(want)
    return jax.lax.bitcast_convert_type(x, want)


def from_bits(bits: jax.Array, dtype) -> jax.Array:
    if bits.dtype == jnp.dtype(dtype):
        return bits
    return jax.lax.bitcast_convert_type(bits, dtype)


def split_fields(bits: jax.Array, fmt: str) -> Tuple[jax.Array, jax.Array]:
    """bits -> (exponent u8, sign_mantissa u8).  Paper §3.2 exactly (bf16):
    e = (x >> 7) & 0xff ;  a = ((x >> 8) & 0x80) | (x & 0x7f)."""
    s = FORMATS[fmt]
    ebits, mbits = s["ebits"], s["mbits"]
    b = bits.astype(jnp.uint32)
    e = (b >> mbits) & ((1 << ebits) - 1)
    a = ((b >> ebits) & (1 << mbits)) | (b & ((1 << mbits) - 1))
    return e.astype(jnp.uint8), a.astype(jnp.uint8)


def join_fields(e: jax.Array, a: jax.Array, fmt: str) -> jax.Array:
    """(exponent, sign_mantissa) -> container bits.  Paper §3.2:
    x = ((a & 0x80) << 8) | (e << 7) | (a & 0x7f)   (bf16 instance)."""
    s = FORMATS[fmt]
    ebits, mbits, bits = s["ebits"], s["mbits"], s["bits"]
    ei = e.astype(jnp.uint32)
    ai = a.astype(jnp.uint32)
    sign = (ai >> mbits) & 1
    out = (sign << (bits - 1)) | (ei << mbits) | (ai & ((1 << mbits) - 1))
    return out.astype(_uint_dtype(fmt))


# ---------------------------------------------------------------------------
# dense path: code assignment via broadcast-compare (TPU-friendly, no gather)
# ---------------------------------------------------------------------------

def assign_codes(e: jax.Array, exponents: tuple) -> Tuple[jax.Array, jax.Array]:
    """exponent byte -> (code u8, member bool).

    Compare against each codebook entry; the code is the index of the matching
    entry (codebook entries are unique so at most one compare fires).  Escapes
    get the dummy code 0 (paper §3.4) and are fixed by sparse correction.
    """
    cb = jnp.asarray(exponents, dtype=jnp.uint8)          # [K]
    eq = e[..., None] == cb                                # [..., K]
    member = jnp.any(eq, axis=-1)
    idx = jnp.arange(len(exponents), dtype=jnp.uint8)
    code = jnp.sum(eq.astype(jnp.uint8) * idx, axis=-1)   # 0 when no match
    return code, member


def decode_codes(code: jax.Array, exponents: tuple) -> jax.Array:
    """code -> exponent via one-hot × codebook contraction (gather-free)."""
    cb = jnp.asarray(exponents, dtype=jnp.uint8)
    k = len(exponents)
    onehot = code[..., None] == jnp.arange(k, dtype=code.dtype)
    return jnp.sum(onehot.astype(jnp.uint8) * cb, axis=-1)


def pack_nibbles(code: jax.Array, chunk: int) -> jax.Array:
    """[N] 4-bit codes -> [N//2] bytes, chunk by chunk: element ``c`` of a
    chunk in the low nibble of byte ``c``, element ``c + chunk/2`` in the
    high one.  Both halves are lane-aligned on the TPU, so the kernels pack
    and unpack without a lane shuffle (docs/wire_format.md §2)."""
    halves = code.astype(jnp.uint8).reshape(-1, 2, chunk // 2)
    return (halves[:, 0] | (halves[:, 1] << 4)).reshape(-1)


def unpack_nibbles(packed: jax.Array, chunk: int) -> jax.Array:
    p = packed.reshape(-1, chunk // 2)
    return jnp.concatenate([p & 0xF, p >> 4], axis=-1).reshape(-1)


# ---------------------------------------------------------------------------
# escape collection: per-chunk cumsum compaction (stream compaction on TPU)
# ---------------------------------------------------------------------------

def collect_escapes(
    e: jax.Array, member: jax.Array, chunk: int, cap: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Compact escape (position, value) pairs into fixed-capacity buffers.

    The GPU version is a warp-level stream compaction; on TPU we express the
    same thing as an exclusive cumsum (ranks) + bounded scatter per chunk.
    Padding entries carry position == chunk (scattered with mode='drop' on the
    decode side).  Returns (esc_pos u16[C,cap], esc_val u8[C,cap],
    esc_count i32[C], ok bool[]).
    """
    n = e.shape[0]
    c = n // chunk
    e2 = e.reshape(c, chunk)
    is_esc = ~member.reshape(c, chunk)
    rank = jnp.cumsum(is_esc.astype(jnp.int32), axis=-1) - 1  # rank within chunk
    esc_count = is_esc.sum(axis=-1).astype(jnp.int32)
    ok = jnp.all(esc_count <= cap)

    pos_in_chunk = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    # scatter target column: rank where escape (and within capacity), else OOB
    col = jnp.where(is_esc & (rank < cap), rank, cap)
    rows = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[:, None], (c, chunk))

    esc_pos = jnp.full((c, cap), chunk, dtype=jnp.uint16)
    esc_val = jnp.zeros((c, cap), dtype=jnp.uint8)
    esc_pos = esc_pos.at[rows, col].set(
        jnp.broadcast_to(pos_in_chunk, (c, chunk)).astype(jnp.uint16), mode="drop"
    )
    esc_val = esc_val.at[rows, col].set(e2.astype(jnp.uint8), mode="drop")
    return esc_pos, esc_val, esc_count, ok


def scatter_escapes(
    e_decoded: jax.Array, esc_pos: jax.Array, esc_val: jax.Array, chunk: int
) -> jax.Array:
    """Sparse correction: overwrite decoded exponents at escape positions."""
    c, cap = esc_pos.shape
    base = (jnp.arange(c, dtype=jnp.int32) * chunk)[:, None]
    pos = esc_pos.astype(jnp.int32)
    flat = jnp.where(pos < chunk, base + pos, e_decoded.shape[0])  # OOB -> drop
    return e_decoded.at[flat.reshape(-1)].set(esc_val.reshape(-1), mode="drop")


# ---------------------------------------------------------------------------
# two-level (global) escape compaction — BEYOND-PAPER (EXPERIMENTS.md §Perf)
#
# The paper's chunked escape buffers become, in-graph, static u16/u8 arrays of
# shape [chunks, cap]; `cap` must absorb the WORST single chunk, so the static
# wire overhead is chunks*cap*3 bytes even when almost every slot is padding.
# A single per-tensor buffer only needs to absorb the TOTAL escape count
# (tight by concentration), cutting in-graph transfer overhead ~10x at equal
# overflow risk.  Positions widen to u32 (5 bytes/escape instead of 3) —
# a good trade because the buffer shrinks far more than entries grow.
# ---------------------------------------------------------------------------

def collect_escapes_global(
    e: jax.Array, member: jax.Array, total_cap: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Compact escapes into one per-tensor buffer via global cumsum ranks.

    Returns (esc_pos u32[1, total_cap] global element indices, esc_val
    u8[1, total_cap], esc_count i32[1], ok bool[]).  Padding entries carry
    position == N (scattered with mode='drop')."""
    n = e.shape[0]
    is_esc = ~member
    rank = jnp.cumsum(is_esc.astype(jnp.int32)) - 1
    esc_count = is_esc.sum().astype(jnp.int32)
    ok = esc_count <= total_cap
    idx = jnp.where(is_esc & (rank < total_cap), rank, total_cap)
    esc_pos = jnp.full((total_cap,), n, dtype=jnp.uint32).at[idx].set(
        jnp.arange(n, dtype=jnp.uint32), mode="drop")
    esc_val = jnp.zeros((total_cap,), dtype=jnp.uint8).at[idx].set(
        e.astype(jnp.uint8), mode="drop")
    return esc_pos[None], esc_val[None], esc_count[None], ok


def scatter_escapes_global(
    e_decoded: jax.Array, esc_pos: jax.Array, esc_val: jax.Array
) -> jax.Array:
    """Sparse correction for the global layout (positions are element indices)."""
    pos = esc_pos.reshape(-1).astype(jnp.int32)  # padding == N -> dropped
    return e_decoded.at[pos].set(esc_val.reshape(-1), mode="drop")


def compact_chunked_to_global(
    esc_pos_c: jax.Array, esc_val_c: jax.Array, esc_count_c: jax.Array,
    chunk: int, total_cap: int, n: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Second-level compaction: per-chunk escape buffers -> one global buffer.

    Consumes the PER-CHUNK buffers and counts the fused Pallas encode kernel
    already produced (esc_pos_c u16[C, cap1], esc_val_c u8[C, cap1],
    esc_count_c i32[C] true counts) instead of recomputing the escape mask
    over the full stream — this XLA pass touches only ``C × cap1`` entries
    (~cap1/chunk of the stream), not N elements.  Entries stay in position
    order, so when nothing is dropped the output is bit-identical to
    :func:`collect_escapes_global` on the same data.

    ``ok`` is the conjunction of the global capacity check (total escapes <=
    ``total_cap``) and the first-level one (no chunk exceeded ``cap1``): a
    chunk that overflowed its level-1 buffer already lost escapes, so the
    tensor must take the raw fallback even if the total would have fit.
    This is (slightly) more conservative than the single-pass global
    reference, never less lossless.
    """
    c, cap1 = esc_pos_c.shape
    cnt = jnp.minimum(esc_count_c, cap1)               # entries present
    jj = jnp.arange(cap1, dtype=jnp.int32)[None, :]
    valid = jj < cnt[:, None]
    offsets = (jnp.cumsum(cnt) - cnt)[:, None]         # exclusive over chunks
    rank = offsets + jj                                # global rank per entry
    gpos = (jnp.arange(c, dtype=jnp.uint32)[:, None] * chunk
            + esc_pos_c.astype(jnp.uint32))
    idx = jnp.where(valid & (rank < total_cap), rank, total_cap).reshape(-1)
    esc_pos = jnp.full((total_cap,), n, dtype=jnp.uint32).at[idx].set(
        gpos.reshape(-1), mode="drop")
    esc_val = jnp.zeros((total_cap,), dtype=jnp.uint8).at[idx].set(
        esc_val_c.reshape(-1), mode="drop")
    total = jnp.sum(esc_count_c).astype(jnp.int32)
    ok = (total <= total_cap) & jnp.all(esc_count_c <= cap1)
    return esc_pos[None], esc_val[None], total[None], ok


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _pad_to_chunk(flat: jax.Array, chunk: int, pad_bits) -> jax.Array:
    n = flat.shape[0]
    pad = (-n) % chunk
    if pad:
        flat = jnp.concatenate([flat, jnp.full((pad,), pad_bits, dtype=flat.dtype)])
    return flat


def default_global_cap(n: int, budget: float = 0.01) -> int:
    """Static per-tensor escape capacity for layout='global': a 1% escape
    budget — 6x the paper's WORST layer-wise escape rate (1.23%, Fig. 5 V-cache
    tail is close) and 60x its mean (0.16%) — rounded up to a lane-aligned
    size.  Still ~4x less wire overhead than the chunked layout's per-chunk
    capacity, which must absorb the worst single chunk rather than the mean."""
    return max(128, int(-(-n * budget // 128)) * 128)


def encode(
    x: jax.Array,
    codebook: Codebook,
    chunk: int = DEFAULT_CHUNK,
    cap: int = DEFAULT_CAP,
    layout: str = "chunked",
) -> CompressedTensor:
    """SplitZip encode (paper §3.2, encoding path).

    Stage 1 (dense): split fields, assign 4-bit codes via compare-select,
    pack nibbles, store sign-mantissa exactly.
    Stage 2 (sparse): compact uncovered exponents into escape buffers —
    per-chunk (paper layout) or one per-tensor buffer (layout='global',
    beyond-paper; `cap` is then the TOTAL capacity, default from
    `default_global_cap`).
    """
    fmt = codebook.fmt
    orig_shape, orig_dtype = x.shape, x.dtype
    bits = to_bits(x, fmt).reshape(-1)
    # Pad with the most frequent exponent pattern => padding never escapes.
    pad_e = codebook.exponents[0]
    pad_bits = np.uint64(pad_e) << FORMATS[fmt]["mbits"]
    bits = _pad_to_chunk(bits, chunk, jnp.asarray(pad_bits, dtype=bits.dtype))

    e, a = split_fields(bits, fmt)
    code, member = assign_codes(e, codebook.exponents)
    packed = pack_nibbles(code, chunk) if codebook.k <= 16 else code
    if layout == "global":
        cap = default_global_cap(bits.shape[0]) if cap == DEFAULT_CAP else cap
        esc_pos, esc_val, esc_count, ok = collect_escapes_global(e, member, cap)
    else:
        esc_pos, esc_val, esc_count, ok = collect_escapes(e, member, chunk, cap)
    return CompressedTensor(
        sign_mantissa=a,
        packed=packed,
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


def decode_to_bits(ct: CompressedTensor) -> jax.Array:
    """SplitZip decode to the FLAT container bit stream (length n_elements):
    dense unpack + LUT + reassemble, then sparse overwrite.  The transfer
    engine consumes bits directly (it ships bit streams); ``decode`` adds
    only the reshape + bitcast back to the original dtype."""
    code = (unpack_nibbles(ct.packed, ct.chunk) if len(ct.exponents) <= 16
            else ct.packed)
    e = decode_codes(code, ct.exponents)
    if ct.layout == "global":
        e = scatter_escapes_global(e, ct.esc_pos, ct.esc_val)
    else:
        e = scatter_escapes(e, ct.esc_pos, ct.esc_val, ct.chunk)
    bits = join_fields(e, ct.sign_mantissa, ct.fmt)
    return bits[:ct.n_elements]


def decode(ct: CompressedTensor) -> jax.Array:
    """SplitZip decode: dense unpack + LUT + reassemble, then sparse overwrite."""
    bits = decode_to_bits(ct).reshape(ct.shape)
    return from_bits(bits, jnp.dtype(ct.dtype))


def roundtrip_ok(x: jax.Array, ct: CompressedTensor) -> jax.Array:
    """Bit-level equality check (float == would fail on NaN)."""
    return jnp.all(to_bits(x, ct.fmt) == to_bits(decode(ct), ct.fmt))


# ---------------------------------------------------------------------------
# byte accounting (paper §3.2 size model; DESIGN.md §1 item 4 for the 3M term)
# ---------------------------------------------------------------------------

def compressed_bytes(ct: CompressedTensor) -> jax.Array:
    """Exact wire bytes for this tensor under the paper's layout:
    N sign-mantissa + N/2 codes + 3 bytes per escape (5 for layout='global',
    whose positions are u32 element indices).  Uses the TRUE element count
    (chunk padding is an in-graph artifact the wire format never ships;
    padding uses the top-1 exponent so it can never escape)."""
    s = FORMATS[ct.fmt]
    n = ct.n_elements
    dense = n * (1 + s["mbits"]) / 8.0  # sign+mantissa bits
    k = len(ct.exponents)
    code_bits = max(1, int(np.ceil(np.log2(max(2, k)))))
    codes = n * code_bits / 8.0
    per_escape = 5.0 if ct.layout == "global" else 3.0
    esc = per_escape * jnp.sum(ct.esc_count)
    return dense + codes + esc


def static_stream_bytes(ct: CompressedTensor) -> int:
    """Bytes the IN-GRAPH streams actually occupy (and actually cross a mesh
    axis when transferred with collectives): static escape buffers are shipped
    at full capacity, padding included.  This is what the two-level global
    layout optimizes — see EXPERIMENTS.md §Perf."""
    return int(ct.sign_mantissa.size * 1 + ct.packed.size * 1
               + ct.esc_pos.size * ct.esc_pos.dtype.itemsize
               + ct.esc_val.size * 1 + ct.esc_count.size * 4 + 1)


def raw_bytes(ct: CompressedTensor) -> float:
    return ct.n_elements * FORMATS[ct.fmt]["bits"] / 8.0


def compression_ratio(ct: CompressedTensor) -> jax.Array:
    return raw_bytes(ct) / compressed_bytes(ct)


def theoretical_ratio(fmt: str = "bf16", k: int = 16, escape_rate: float = 0.0) -> float:
    """ρ = 2 / (3/2 + 3ε) for bf16/top-16; generalized per format/k."""
    s = FORMATS[fmt]
    code_bits = max(1, int(np.ceil(np.log2(max(2, k)))))
    per_elem_bytes = (1 + s["mbits"]) / 8.0 + code_bits / 8.0 + 3.0 * escape_rate
    return (s["bits"] / 8.0) / per_elem_bytes


# ---------------------------------------------------------------------------
# Top-15 + sentinel variant (paper §3.4 / Table 6 ablation)
# ---------------------------------------------------------------------------

SENTINEL = 15


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SentinelCompressed:
    sign_mantissa: jax.Array
    packed: jax.Array
    esc_val: jax.Array      # u8[C, cap] escape values in occurrence order
    esc_count: jax.Array    # i32[C]
    ok: jax.Array
    shape: tuple = dataclasses.field(metadata=dict(static=True))
    dtype: str = dataclasses.field(metadata=dict(static=True))
    fmt: str = dataclasses.field(metadata=dict(static=True))
    exponents: tuple = dataclasses.field(metadata=dict(static=True))  # 15 entries
    chunk: int = dataclasses.field(metadata=dict(static=True))
    cap: int = dataclasses.field(metadata=dict(static=True))


def encode_sentinel(
    x: jax.Array, codebook: Codebook, chunk: int = DEFAULT_CHUNK, cap: int = DEFAULT_CAP
) -> SentinelCompressed:
    """Top-15 + escape-token design: code 15 marks an escape; escape *values*
    are stored in occurrence order (no positions — the decoder finds sentinels
    in the dense stream).  Saves 2 bytes/escape but makes decode irregular."""
    exps = tuple(codebook.exponents[:15])
    fmt = codebook.fmt
    orig_shape, orig_dtype = x.shape, x.dtype
    bits = to_bits(x, fmt).reshape(-1)
    pad_bits = np.uint64(exps[0]) << FORMATS[fmt]["mbits"]
    bits = _pad_to_chunk(bits, chunk, jnp.asarray(pad_bits, dtype=bits.dtype))
    e, a = split_fields(bits, fmt)
    code, member = assign_codes(e, exps)
    code = jnp.where(member, code, jnp.uint8(SENTINEL))
    packed = pack_nibbles(code, chunk)
    # values-only compaction, occurrence order per chunk
    _, esc_val, esc_count, ok = collect_escapes(e, member, chunk, cap)
    return SentinelCompressed(
        sign_mantissa=a, packed=packed, esc_val=esc_val, esc_count=esc_count,
        ok=ok, shape=tuple(orig_shape), dtype=str(orig_dtype), fmt=fmt,
        exponents=exps, chunk=chunk, cap=cap,
    )


def decode_sentinel(ct: SentinelCompressed) -> jax.Array:
    """Irregular decode path: every element must inspect the code stream for
    the sentinel, rank sentinels per chunk, and gather from the value stream.
    This models the paper's measured 3.5× decode slowdown structurally."""
    code = unpack_nibbles(ct.packed, ct.chunk)
    is_esc = code == SENTINEL
    e = decode_codes(jnp.where(is_esc, 0, code), ct.exponents)
    c = ct.esc_val.shape[0]
    chunk = ct.chunk
    is_esc2 = is_esc.reshape(c, chunk)
    rank = jnp.cumsum(is_esc2.astype(jnp.int32), axis=-1) - 1
    rank = jnp.clip(rank, 0, ct.cap - 1)
    vals = jnp.take_along_axis(ct.esc_val, rank.astype(jnp.int32), axis=-1)
    e = jnp.where(is_esc2, vals, e.reshape(c, chunk)).reshape(-1).astype(jnp.uint8)
    bits = join_fields(e, ct.sign_mantissa, ct.fmt)
    n = int(np.prod(ct.shape)) if ct.shape else 1
    return from_bits(bits[:n].reshape(ct.shape), jnp.dtype(ct.dtype))


def sentinel_bytes(ct: SentinelCompressed) -> jax.Array:
    """N + N/2 + 1 byte per escape (values only)."""
    s = FORMATS[ct.fmt]
    n = ct.sign_mantissa.shape[0]
    return n * (1 + s["mbits"]) / 8.0 + n * 0.5 + 1.0 * jnp.sum(ct.esc_count)


# ---------------------------------------------------------------------------
# Dynamic (per-call) calibration variant (paper §4.3.5 ablation)
# ---------------------------------------------------------------------------

def dynamic_topk_exponents(bits: jax.Array, fmt: str = "bf16", k: int = 16) -> jax.Array:
    """Online histogram + top-k selection (the expensive path the paper's
    pre-calibration avoids).  Returns the top-k exponents as a traced array —
    usable with `encode_with_dynamic_codebook` below."""
    s = FORMATS[fmt]
    e, _ = split_fields(bits.reshape(-1), fmt)
    hist = jnp.zeros((1 << s["ebits"],), jnp.int32).at[e.astype(jnp.int32)].add(1)
    _, top = jax.lax.top_k(hist, k)
    return top.astype(jnp.uint8)


def encode_with_dynamic_codebook(
    x: jax.Array, fmt: str = "bf16", k: int = 16,
    chunk: int = DEFAULT_CHUNK, cap: int = DEFAULT_CAP,
):
    """Dynamic-codebook encode: rebuild the codebook per input (slow path).

    Returns (streams tuple, codebook array).  Used only by the Table 7
    ablation; the production path is `encode` with an offline Codebook.
    """
    bits = to_bits(x, fmt).reshape(-1)
    cb = dynamic_topk_exponents(bits, fmt, k)
    pad = (-bits.shape[0]) % chunk
    if pad:
        padv = (cb[0].astype(jnp.uint32) << FORMATS[fmt]["mbits"]).astype(bits.dtype)
        bits = jnp.concatenate([bits, jnp.full((pad,), 0, bits.dtype) + padv])
    e, a = split_fields(bits, fmt)
    eq = e[..., None] == cb
    member = jnp.any(eq, axis=-1)
    code = jnp.sum(eq.astype(jnp.uint8) * jnp.arange(k, dtype=jnp.uint8), axis=-1)
    packed = pack_nibbles(code, chunk)
    esc_pos, esc_val, esc_count, ok = collect_escapes(e, member, chunk, cap)
    return (a, packed, esc_pos, esc_val, esc_count, ok), cb


def decode_with_dynamic_codebook(streams, cb, shape, dtype, fmt="bf16",
                                 chunk: int = DEFAULT_CHUNK):
    a, packed, esc_pos, esc_val, esc_count, ok = streams
    code = unpack_nibbles(packed, chunk)
    k = cb.shape[0]
    onehot = code[..., None] == jnp.arange(k, dtype=code.dtype)
    e = jnp.sum(onehot.astype(jnp.uint8) * cb, axis=-1)
    e = scatter_escapes(e, esc_pos, esc_val, chunk)
    bits = join_fields(e, a, fmt)
    n = int(np.prod(shape)) if shape else 1
    return from_bits(bits[:n].reshape(shape), jnp.dtype(dtype))
