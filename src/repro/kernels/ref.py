"""Pure-jnp oracles for the SplitZip Pallas kernels.

Kernel-equivalent signatures so tests can `assert_allclose` (bit equality —
these are integer streams) against `splitzip_encode.encode_dense` /
`splitzip_decode.decode_dense` across shape/dtype sweeps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.codebook import FORMATS
from repro.core.codec import pack_nibbles, unpack_nibbles


def encode_dense_ref(bits: jax.Array, exponents: tuple, fmt: str = "bf16"):
    """(rows, chunk) bits -> (sign_mantissa, packed, is_escape)."""
    spec = FORMATS[fmt]
    mbits, ebits = spec["mbits"], spec["ebits"]
    x = bits.astype(jnp.int32)
    e = (x >> mbits) & ((1 << ebits) - 1)
    a = ((x >> ebits) & (1 << mbits)) | (x & ((1 << mbits) - 1))

    cb = jnp.asarray(exponents, dtype=jnp.int32)
    eq = e[..., None] == cb
    member = jnp.any(eq, axis=-1)
    code = jnp.sum(eq.astype(jnp.int32) * jnp.arange(len(exponents)), axis=-1)

    r, c = code.shape
    packed = pack_nibbles(code, c).reshape(r, c // 2)
    return a.astype(jnp.uint8), packed, (~member).astype(jnp.uint8)


def decode_dense_ref(packed: jax.Array, sign_mantissa: jax.Array,
                     exponents: tuple, fmt: str = "bf16"):
    """(rows, chunk//2) packed + (rows, chunk) sign-mantissa -> container bits."""
    spec = FORMATS[fmt]
    mbits, width = spec["mbits"], spec["bits"]
    a = sign_mantissa.astype(jnp.int32)
    code = unpack_nibbles(packed.astype(jnp.uint8), a.shape[1]).reshape(
        a.shape).astype(jnp.int32)
    cb = jnp.asarray(exponents, dtype=jnp.int32)
    onehot = code[..., None] == jnp.arange(len(exponents))
    e = jnp.sum(onehot.astype(jnp.int32) * cb, axis=-1)
    sign = (a >> mbits) & 1
    out = (sign << (width - 1)) | (e << mbits) | (a & ((1 << mbits) - 1))
    return out.astype(jnp.uint16 if width == 16 else jnp.uint8)


# ---------------------------------------------------------------------------
# flash attention oracle (direct softmax; materializes S×S — small shapes only)
# ---------------------------------------------------------------------------

def attention_ref(q, k, v, *, causal=True, scale=None):
    """(B, Sq, H, D) x (B, Skv, Hkv, D[v]) GQA attention, f32 math."""
    import numpy as np
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    g = h // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kf) * scale
    if causal:
        mask = jnp.arange(sq)[:, None] >= jnp.arange(skv)[None, :]
        s = jnp.where(mask[None, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p, vf)
    return o.reshape(b, sq, h, vf.shape[-1]).astype(q.dtype)
