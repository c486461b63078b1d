"""Flash-attention Pallas kernel vs the jnp oracle (interpret mode on CPU).

Sweeps shapes (incl. GQA groupings, MLA-style dv != d, non-divisible sequence
lengths that exercise padding) and dtypes, causal and bidirectional, plus a
seeded random-shape sweep (hypothesis-free so collection never depends on an
optional package).  Then the kernel against the XLA scan it replaces on a TPU
(``layers._scan_attention``), its causal schedule, the custom VJP of the
fused path and the rule that picks kernel or scan.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.sharding import ShardingPolicy, use_policy
from repro.kernels import flash_attention as FA
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import attention_ref
from repro.models import layers as L

TOL = {jnp.bfloat16: 3e-2, jnp.float32: 2e-5}


def make(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape), dtype)


def check(b, sq, skv, h, hkv, d, dv, dtype, causal, blk_q=64, blk_k=64):
    q = make((b, sq, h, d), dtype, 1)
    k = make((b, skv, hkv, d), dtype, 2)
    v = make((b, skv, hkv, dv), dtype, 3)
    out = flash_attention(q, k, v, causal=causal, blk_q=blk_q, blk_k=blk_k)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


class TestShapes:
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("causal", [True, False])
    def test_mha(self, dtype, causal):
        check(2, 128, 128, 4, 4, 64, 64, dtype, causal)

    @pytest.mark.parametrize("g", [2, 4, 8])
    def test_gqa_groups(self, g):
        check(1, 128, 128, 8, 8 // g, 32, 32, jnp.bfloat16, True)

    def test_mqa(self):
        check(2, 128, 128, 8, 1, 64, 64, jnp.bfloat16, True)

    def test_mla_value_dim(self):
        # MLA: value head dim differs from qk head dim
        check(1, 128, 128, 4, 4, 96, 64, jnp.bfloat16, True)

    @pytest.mark.parametrize("sq", [65, 100, 127, 200])
    def test_ragged_seq_padding(self, sq):
        check(1, sq, sq, 4, 2, 32, 32, jnp.bfloat16, True)

    def test_cross_attention_lengths(self):
        check(1, 64, 192, 4, 2, 32, 32, jnp.bfloat16, False)

    @pytest.mark.parametrize("blk", [(32, 32), (64, 128), (128, 64)])
    def test_block_shapes(self, blk):
        check(1, 256, 256, 4, 2, 32, 32, jnp.bfloat16, True,
              blk_q=blk[0], blk_k=blk[1])


class TestTailBlocks:
    """Boundary-shape pinning tests (ISSUE 8 satellite).

    The suspected tail-block masking bug — q/kv lengths that leave a
    partial final block, where an unmasked padding lane could leak into the
    softmax — did NOT reproduce under any of these probes: the kernel masks
    the ragged tail correctly for every (seq % blk) residue class,
    including the hardest cases (residue 1, blk-1, and a kv tail shorter
    than one block).  Kept as regression pins so a future refactor of the
    tail masking cannot break these silently."""

    # residues 1 and blk-1 on both axes, plus a sub-block kv tail
    @pytest.mark.parametrize("sq,skv,blk_q,blk_k", [
        (65, 65, 64, 64),      # residue 1 on both axes
        (127, 127, 64, 64),    # residue blk-1
        (64, 65, 64, 64),      # exact q blocks, kv residue 1
        (65, 64, 64, 64),      # q residue 1, exact kv blocks
        (100, 33, 64, 32),     # kv tail of one lane past a block
        (33, 100, 32, 64),
        (16, 16, 64, 64),      # whole sequence smaller than one block
        (1, 200, 64, 64),      # single-query decode shape, ragged kv
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_partial_tail_blocks(self, sq, skv, blk_q, blk_k, causal):
        if causal and sq != skv:
            pytest.skip("causal path assumes aligned q/kv positions")
        check(1, sq, skv, 4, 2, 32, 32, jnp.bfloat16, causal,
              blk_q=blk_q, blk_k=blk_k)

    def test_tail_block_ignores_padding_values(self):
        """Poison the padded kv region with huge values: the output over
        the valid prefix must be unchanged (padding fully masked)."""
        sq = skv = 65                              # one ragged tail block
        q = make((1, sq, 4, 32), jnp.float32, 1)
        k = make((1, skv, 2, 32), jnp.float32, 2)
        v = make((1, skv, 2, 32), jnp.float32, 3)
        base = flash_attention(q, k, v, causal=True, blk_q=64, blk_k=64)
        # the kernel pads internally; poison by extending with huge values
        # and re-truncating the VALID region must not change
        kp = jnp.concatenate([k, jnp.full((1, 63, 2, 32), 1e4, k.dtype)], 1)
        vp = jnp.concatenate([v, jnp.full((1, 63, 2, 32), 1e4, v.dtype)], 1)
        qp = jnp.concatenate([q, jnp.zeros((1, 63, 4, 32), q.dtype)], 1)
        ext = flash_attention(qp, kp, vp, causal=True, blk_q=64, blk_k=64)
        np.testing.assert_allclose(
            np.asarray(base, np.float32), np.asarray(ext[:, :sq], np.float32),
            atol=2e-5, rtol=2e-5)


class TestConsistency:
    def test_matches_chunked_attention(self):
        """The XLA path (models/layers.chunked_attention) and the kernel are
        independent implementations; they must agree."""
        q = make((2, 128, 8, 64), jnp.bfloat16, 5)
        k = make((2, 128, 2, 64), jnp.bfloat16, 6)
        v = make((2, 128, 2, 64), jnp.bfloat16, 7)
        a = flash_attention(q, k, v, causal=True, blk_q=64, blk_k=64)
        b = L.chunked_attention(q, k, v, causal=True, kv_block=64)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=3e-2, rtol=3e-2)

    def test_numerical_stability_large_scores(self):
        # online softmax must not overflow on large logits
        q = make((1, 64, 2, 32), jnp.float32, 8) * 30
        k = make((1, 64, 2, 32), jnp.float32, 9) * 30
        v = make((1, 64, 2, 32), jnp.float32, 10)
        out = flash_attention(q, k, v, causal=True, blk_q=32, blk_k=32)
        assert bool(jnp.all(jnp.isfinite(out)))
        ref = attention_ref(q, k, v, causal=True)
        # online (two-pass) softmax reorders f32 ops; at |logit| ~ 900 the
        # divergence vs the direct oracle is ~5e-4 — finite and stable
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("seed", range(10))
def test_random_shapes(seed):
    """Seeded stand-in for the former hypothesis property test: random
    (batch, seq, heads, group, head-dim, causality) combinations."""
    rng = np.random.default_rng(seed)
    b = int(rng.integers(1, 3))
    sq = int(rng.integers(8, 97))
    h = int(rng.choice([2, 4, 8]))
    g = int(rng.choice([1, 2]))
    d = int(rng.choice([16, 32]))
    causal = bool(rng.integers(0, 2))
    hkv = max(1, h // g)
    h = hkv * g
    check(b, sq, sq, h, hkv, d, d, jnp.float32, causal, blk_q=32, blk_k=32)


# ---------------------------------------------------------------------------
# the kernel against the XLA path it replaces on a TPU, the schedule, the
# custom VJP and the dispatch rule
# ---------------------------------------------------------------------------

# (b, s, h, hkv, d, dv, blocks): GQA g = 8 at d 128, MiniCPM3's and
# DeepSeek-V3's MLA widths; S a multiple of neither block, blocks chosen
# from the shapes (None) or small enough for several of each
XLA_SHAPES = {
    "gqa8_d128": (1, 200, 16, 2, 128, 128, None),
    "gqa8_d128_blocks": (1, 300, 16, 2, 128, 128, (32, 128)),
    "mla_d96_dv64": (1, 300, 4, 4, 96, 64, (64, 128)),
    "mla_d192_dv128": (1, 200, 2, 2, 192, 128, None),
}


def _qkv(b, s, h, hkv, d, dv, dtype=jnp.bfloat16, seed=20):
    return (make((b, s, h, d), dtype, seed), make((b, s, hkv, d), dtype, seed + 1),
            make((b, s, hkv, dv), dtype, seed + 2))


def _blocks(blocks):
    return dict(zip(("blk_q", "blk_k"), blocks)) if blocks else {}


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", list(XLA_SHAPES))
def test_matches_xla_path(shape, causal):
    """The kernel's arithmetic is the XLA scan's (bf16 dots with f32
    accumulation, f32 softmax statistics, bf16 p into P·V): the two differ
    only by where the online softmax's blocks fall."""
    b, s, h, hkv, d, dv, blocks = XLA_SHAPES[shape]
    q, k, v = _qkv(b, s, h, hkv, d, dv)
    out = flash_attention(q, k, v, causal=causal, scale=0.3, **_blocks(blocks))
    ref = L._scan_attention(q, k, v, causal=causal, scale=0.3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_right_padded_ragged_batch():
    """Rows right-padded to a common length: a causal row's valid positions
    read nothing past them, so poisoning the padding changes none of them,
    and they match the XLA path."""
    lens = (300, 173)
    q, k, v = _qkv(2, 300, 16, 2, 128, 128)
    pad = np.arange(300)[None, :, None, None] >= np.asarray(lens)[:, None, None, None]
    poison = lambda x: jnp.where(pad, jnp.asarray(1e4, x.dtype), x)
    clean = flash_attention(q, k, v, causal=True, blk_q=32, blk_k=128)
    dirty = flash_attention(poison(q), poison(k), poison(v), causal=True,
                            blk_q=32, blk_k=128)
    ref = L._scan_attention(q, k, v, causal=True)
    for row, n in enumerate(lens):
        np.testing.assert_array_equal(
            np.asarray(clean[row, :n]).view(np.uint16),
            np.asarray(dirty[row, :n]).view(np.uint16))
        np.testing.assert_allclose(np.asarray(clean[row, :n], np.float32),
                                   np.asarray(ref[row, :n], np.float32),
                                   atol=3e-2, rtol=3e-2)


_SCHEDULE = FA._schedule


def _full_schedule(nq, nk, blk_q, blk_k, sq, skv, causal):
    """Every KV block for every q block, masked or not."""
    return _SCHEDULE(nq, nk, blk_q, blk_k, sq, skv, False)


@pytest.mark.parametrize("shape", ["gqa8_d128_blocks", "mla_d96_dv64"])
def test_causal_schedule_equals_visiting_every_block(shape, monkeypatch):
    """Skipping the KV blocks above the diagonal changes no bit: a fully
    masked block adds exp(-inf) = 0 to l and acc and leaves m alone."""
    b, s, h, hkv, d, dv, blocks = XLA_SHAPES[shape]
    q, k, v = _qkv(b, s, h, hkv, d, dv)
    run = lambda: jax.jit(functools.partial(
        flash_attention.__wrapped__, causal=True, **_blocks(blocks)))(q, k, v)
    skipped = run()
    monkeypatch.setattr(FA, "_schedule", _full_schedule)
    every = run()
    np.testing.assert_array_equal(np.asarray(skipped).view(np.uint16),
                                  np.asarray(every).view(np.uint16))


@pytest.mark.parametrize("blk_q,blk_k,pairs", [
    (128, 128, 1 + 2 + 3 + 4),      # square blocks: the lower triangle
    (64, 128, 1 + 1 + 2 + 2 + 3 + 3 + 4 + 4),
    (128, 256, 1 + 1 + 2 + 2),
])
def test_causal_schedule_counts(blk_q, blk_k, pairs):
    nq, nk = 512 // blk_q, 512 // blk_k
    sched = FA._schedule(nq, nk, blk_q, blk_k, 512, 512, True)
    assert sched.shape == (3, pairs)
    # each q block ends on the KV block holding its last query's key
    last = sched[1][sched[2] == 1]
    assert list(last) == [((j + 1) * blk_q - 1) // blk_k for j in range(nq)]
    assert FA._schedule(nq, nk, blk_q, blk_k, 512, 512, False).shape == (
        3, nq * nk)


@pytest.mark.parametrize("sq,g,d,dv,blocks", [
    (2048, 8, 128, 128, (512, 512)),     # qwen3-32b: 8 heads a group
    (4096, 1, 96, 64, (1024, 1024)),     # minicpm3-4b: MLA
    (512, 1, 192, 128, (512, 512)),      # DeepSeek-V3: one block
    (200, 8, 128, 128, (208, 256)),
])
def test_block_sizes_fit_the_budget(sq, g, d, dv, blocks):
    blk_q, blk_k = FA.block_sizes(sq, sq, g, d, dv)
    assert (blk_q, blk_k) == blocks
    assert blk_q % 16 == 0 and blk_k % 128 == 0
    assert g * blk_q * blk_k <= FA.MAX_TILE
    assert FA.vmem_bytes(blk_q, blk_k, g, d, dv) <= FA.VMEM_BUDGET


def _loss(attn, q, k, v, w):
    return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)


@pytest.mark.parametrize("causal", [True, False])
def test_grad_through_the_fused_path(causal, monkeypatch):
    """On a TPU ``chunked_attention`` takes the kernel, whose VJP is the XLA
    scan's: gradients equal the scan's."""
    q, k, v = _qkv(1, 130, 8, 2, 64, 64, jnp.float32)
    w = make((1, 130, 8, 64), jnp.float32, 30)
    scan = functools.partial(L._scan_attention, causal=causal, kv_block=64)
    ref = jax.grad(_loss, argnums=(1, 2, 3))(scan, q, k, v, w)
    monkeypatch.setattr(L, "use_fused_attention", lambda window: True)
    fused = functools.partial(L.chunked_attention, causal=causal, kv_block=64)
    out = fused(q, k, v)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(flash_attention(q, k, v, causal=causal)))
    got = jax.grad(_loss, argnums=(1, 2, 3))(fused, q, k, v, w)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def _one_device_policy():
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    return ShardingPolicy(mesh)


@pytest.mark.parametrize("backend,policy,window,fused", [
    ("tpu", False, None, True),
    ("tpu", True, None, False),
    ("tpu", False, 64, False),
    ("cpu", False, None, False),
])
def test_dispatch_rule(backend, policy, window, fused, monkeypatch):
    """The kernel runs on a TPU with no sharding policy and no window;
    everything else keeps the XLA scan."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    calls = []
    monkeypatch.setattr(L, "_fused_attention",
                        lambda *a: calls.append(a) or L._scan_attention(
                            *a[:3], causal=a[3], scale=a[4]))
    q, k, v = _qkv(1, 64, 4, 2, 32, 32)
    with use_policy(_one_device_policy() if policy else None):
        assert L.use_fused_attention(window) is fused
        L.chunked_attention(q, k, v, causal=True, window=window)
    assert len(calls) == int(fused)
