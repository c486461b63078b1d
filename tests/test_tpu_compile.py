"""Ahead-of-time compiles of the served path's Pallas kernels for a TPU v5e.

The TPU compiler is installed alongside jax, and it compiles for a chip that
is described, not attached: these tests lower and compile each kernel at real
sizes with ``interpret=False`` and assert the Mosaic kernel is in the
executable.  That catches what interpret mode cannot (unaligned block shapes,
lane shuffles Mosaic cannot express, VMEM overflow) without a chip.

Sizes are those of the one-chip smoke (``chip_smoke.py``): the qwen3-32b KV
layer of 4 x 2048 tokens x 8 heads x 128 (8192 codec rows of 1024), 32 KiB
pages of 16 tokens, and minicpm3-4b's MLA latent pages (kv_lora 256, rope 32);
and DeepSeek-V3's expert products (d_model 7168, experts 2048 wide, 8 held,
seven MoE layers stacked) at a decode step's 64 rows and a prefill block's
16384; and the fused prefill attention at the three cells' prompt shapes.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import splitzip_attention as SA
from repro.kernels import splitzip_decode, splitzip_encode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_matmul import moe_grouped_matmul

EXPONENTS = tuple(range(112, 128))
CHUNK, CAP = 1024, 64
# 8192 rows: the smoke's KV layer; 8200 is no power of two and leaves a
# partial last grid step
ROWS = (8192, 8200)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (an entry written here could not be read back without a
    chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _streams(sharding, n_pages, page_chunks, cap):
    """A paged leaf's five stream arrays (kvpool.PagedLeaf.streams())."""
    return (_shape(sharding, (n_pages, page_chunks, CHUNK), jnp.uint8),
            _shape(sharding, (n_pages, page_chunks, CHUNK // 2), jnp.uint8),
            _shape(sharding, (n_pages, 1, cap), jnp.uint16),
            _shape(sharding, (n_pages, 1, cap), jnp.uint8),
            _shape(sharding, (n_pages, 1), jnp.int32))


@pytest.mark.parametrize("rows", ROWS)
def test_encode_fused(one_chip, rows):
    _compile(lambda b: splitzip_encode.encode_fused(
        b, EXPONENTS, chunk=CHUNK, cap=CAP, interpret=False),
        _shape(one_chip, (rows, CHUNK), jnp.uint16))


@pytest.mark.parametrize("rows", ROWS)
def test_decode_fused(one_chip, rows):
    _compile(lambda p, a, pos, val, cnt: splitzip_decode.decode_fused(
        p, a, pos, val, cnt, EXPONENTS, chunk=CHUNK, interpret=False),
        _shape(one_chip, (rows, CHUNK // 2), jnp.uint8),
        _shape(one_chip, (rows, CHUNK), jnp.uint8),
        _shape(one_chip, (rows, CAP), jnp.uint16),
        _shape(one_chip, (rows, CAP), jnp.uint8),
        _shape(one_chip, (rows, 1), jnp.int32))


@pytest.mark.parametrize("rows", ROWS)
def test_encode_dense(one_chip, rows):
    _compile(lambda b: splitzip_encode.encode_dense(
        b, EXPONENTS, chunk=CHUNK, interpret=False),
        _shape(one_chip, (rows, CHUNK), jnp.uint16))


@pytest.mark.parametrize("rows", ROWS)
def test_decode_dense(one_chip, rows):
    _compile(lambda p, a: splitzip_decode.decode_dense(
        p, a, EXPONENTS, chunk=CHUNK, interpret=False),
        _shape(one_chip, (rows, CHUNK // 2), jnp.uint8),
        _shape(one_chip, (rows, CHUNK), jnp.uint8))


def test_paged_gqa_attention_qwen3_32b(one_chip):
    """H 64, hkv 8, head_dim 128; 32 KiB pages of 16 tokens (16 chunks,
    64 escape slots); 4 rows of 256 pages over an 8-layer pool."""
    b, pages, n_pages = 4, 256, 8 * 4 * 256
    leaf = _streams(one_chip, n_pages, 16, 64)
    table = _shape(one_chip, (b, pages), jnp.int32)
    _compile(lambda q, k, v, pk, pv, n: SA.paged_gqa_attention(
        q, k, v, pk, pv, n, exponents=EXPONENTS, chunk=CHUNK,
        tokens_per_page=16, hkv=8, interpret=False),
        _shape(one_chip, (b, 1, 64, 128), jnp.bfloat16), leaf, leaf,
        table, table, _shape(one_chip, (b,), jnp.int32))


def test_paged_mla_attention_minicpm3_4b(one_chip):
    """H 40, kv_lora 256, rope 32: 64-token pages, 16 latent chunks and 2
    rope chunks a page, 64 and 8 escape slots."""
    b, pages, n_pages = 4, 64, 62 * 4 * 64
    table = _shape(one_chip, (b, pages), jnp.int32)
    _compile(lambda ql, qr, c, r, pc, pr, n: SA.paged_mla_attention(
        ql, qr, c, r, pc, pr, n, exponents=EXPONENTS, chunk=CHUNK,
        tokens_per_page=64, scale=1.0 / (96 ** 0.5), interpret=False),
        _shape(one_chip, (b, 1, 40, 256), jnp.bfloat16),
        _shape(one_chip, (b, 1, 40, 32), jnp.bfloat16),
        _streams(one_chip, n_pages, 16, 64), _streams(one_chip, n_pages, 2, 8),
        table, table, _shape(one_chip, (b,), jnp.int32))


@pytest.mark.parametrize("rows", (64, 16384))
@pytest.mark.parametrize("glu", (True, False), ids=("gate-up", "down"))
def test_moe_grouped_matmul_deepseek_v3(one_chip, rows, glu):
    k, n = (7168, 2 * 2048) if glu else (2048, 7168)
    _compile(lambda x, w, s, i: moe_grouped_matmul(
        x, w, s, i, glu=glu, interpret=False),
        _shape(one_chip, (rows, k), jnp.bfloat16),
        _shape(one_chip, (7, 8, k, n), jnp.bfloat16),
        _shape(one_chip, (8,), jnp.int32), _shape(one_chip, (), jnp.int32))


@pytest.mark.parametrize("b,s,h,hkv,d,dv", [
    (4, 2048, 64, 8, 128, 128),      # qwen3-32b: GQA, 8 heads a group
    (2, 4096, 40, 40, 96, 64),       # minicpm3-4b: MLA, d 96 / dv 64
    (8, 512, 128, 128, 192, 128),    # DeepSeek-V3: MLA, d 192 / dv 128
], ids=("qwen3-32b", "minicpm3-4b", "deepseek-v3"))
def test_flash_attention_prefill(one_chip, b, s, h, hkv, d, dv):
    """The fused causal prefill kernel at the cells' widths, with the block
    sizes it chooses from the shapes."""
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             interpret=False),
             _shape(one_chip, (b, s, h, d), jnp.bfloat16),
             _shape(one_chip, (b, s, hkv, d), jnp.bfloat16),
             _shape(one_chip, (b, s, hkv, dv), jnp.bfloat16))
