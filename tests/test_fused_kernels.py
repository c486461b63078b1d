"""Single-pass fused Pallas codec tests.

The fused kernels promise three things, pinned down here:

1. **Stream parity** — the fused encode's CompressedTensor is bit-identical
   to the pure-XLA reference (and hence to the two-stage path) on every
   input class: special values, fp8, all-escape, zero-escape, and the
   capacity-overflow boundary (``esc_count == cap`` and ``cap + 1``).
2. **Single-launch structure** — one ``pallas_call`` per direction and no
   XLA scatter tail in the fused decode (jaxpr-level assertions; the
   benchmark re-checks this on lowered HLO).
3. **Engine integration** — the chunked pipelined transfer engine with the
   fused backend reassembles caches bit-identically, and the adaptive
   capacity retry recovers heavy-tailed chunks before the raw fallback.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as B
from repro.core import codebook as cbm
from repro.core import codec as C
from repro.kernels import ops, splitzip_decode, splitzip_encode, twostage
from repro.serving.plan import TransferConfig, TransferPlan

CODEBOOK = tuple(range(118, 134))
BF16_CB = cbm.Codebook(fmt="bf16", exponents=CODEBOOK)
FP8_CB = cbm.Codebook(fmt="fp8_e5m2", exponents=tuple(range(8, 24)))

BF16_SPECIALS = np.array(
    [0x7FC0, 0x7FC1, 0xFFC0, 0x7F80, 0xFF80, 0x0000, 0x8000,
     0x0001, 0x8001, 0x7F7F, 0xFF7F, 0x0080, 0xFFFF, 0x7FFF],
    dtype=np.uint16)


def _bf16_specials_input(seed=0, n=8192):
    rng = np.random.default_rng(seed)
    bits = np.array(jax.lax.bitcast_convert_type(
        jnp.asarray(rng.standard_normal(n).astype(np.float32)
                    * np.exp(rng.standard_normal(n))).astype(jnp.bfloat16),
        jnp.uint16))
    pos = rng.choice(n, size=4 * BF16_SPECIALS.size, replace=False)
    bits[pos] = np.tile(BF16_SPECIALS, 4)
    return jax.lax.bitcast_convert_type(jnp.asarray(bits), jnp.bfloat16)


def _assert_streams_equal(ct_a, ct_b):
    for la, lb in zip(jax.tree.leaves(ct_a), jax.tree.leaves(ct_b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _exact_escape_input(n_escapes: int, chunk: int = 1024):
    """One chunk with exactly ``n_escapes`` escaping elements (exponent 7 is
    not in CODEBOOK; exponent 120 is) at scattered positions."""
    bits = np.full(chunk, np.uint16(120 << 7), dtype=np.uint16)
    pos = np.linspace(0, chunk - 1, n_escapes).astype(int) if n_escapes else []
    for p in pos:
        bits[p] = np.uint16(7 << 7) | np.uint16(p % 128)  # varied mantissae
    return jax.lax.bitcast_convert_type(jnp.asarray(bits), jnp.bfloat16)


class TestFusedStreamParity:
    def test_bf16_specials_streams_and_roundtrip(self):
        x = _bf16_specials_input(seed=1)
        ct_f = ops.encode(x, BF16_CB)
        _assert_streams_equal(ct_f, C.encode(x, BF16_CB))
        y = ops.decode(ct_f)
        np.testing.assert_array_equal(
            np.asarray(C.to_bits(x, "bf16")),
            np.asarray(C.to_bits(y, "bf16")))

    def test_fp8_streams_and_roundtrip(self):
        rng = np.random.default_rng(2)
        # biased toward covered exponents so capacity holds, plus specials
        e = rng.choice(np.arange(8, 24), size=4096).astype(np.uint8)
        bits = ((e << 2) | rng.integers(0, 4, 4096)).astype(np.uint8)
        bits[:64] = rng.integers(0, 256, 64)  # escapes incl. NaN/Inf patterns
        bits = jnp.asarray(bits)
        ct_f = ops.encode(bits, FP8_CB)
        _assert_streams_equal(ct_f, C.encode(bits, FP8_CB))
        np.testing.assert_array_equal(
            np.asarray(bits), np.asarray(C.to_bits(ops.decode(ct_f),
                                                   "fp8_e5m2")))

    def test_all_escape_tensor(self):
        bits = jnp.full((4096,), np.uint16(7 << 7), dtype=jnp.uint16)
        x = jax.lax.bitcast_convert_type(bits, jnp.bfloat16)
        ct_f = ops.encode(x, BF16_CB)
        ct_r = C.encode(x, BF16_CB)
        _assert_streams_equal(ct_f, ct_r)
        assert not bool(ct_f.ok)
        assert np.asarray(ct_f.esc_count).tolist() == [1024] * 4

    def test_zero_escape_tensor(self):
        bits = jnp.full((4096,), np.uint16(120 << 7), dtype=jnp.uint16)
        x = jax.lax.bitcast_convert_type(bits, jnp.bfloat16)
        ct_f = ops.encode(x, BF16_CB)
        _assert_streams_equal(ct_f, C.encode(x, BF16_CB))
        assert bool(ct_f.ok)
        assert int(jnp.sum(ct_f.esc_count)) == 0
        np.testing.assert_array_equal(
            np.asarray(bits), np.asarray(C.to_bits(ops.decode(ct_f), "bf16")))

    @pytest.mark.parametrize("n_esc,expect_ok", [(63, True), (64, True),
                                                 (65, False)])
    def test_capacity_boundary(self, n_esc, expect_ok):
        """esc_count == cap is still ok; cap + 1 overflows — and the streams
        (first cap entries, TRUE count) match the reference either way."""
        x = _exact_escape_input(n_esc)
        ct_f = ops.encode(x, BF16_CB, cap=64)
        ct_r = C.encode(x, BF16_CB, cap=64)
        _assert_streams_equal(ct_f, ct_r)
        assert bool(ct_f.ok) is expect_ok
        assert int(ct_f.esc_count[0]) == n_esc
        if expect_ok:
            np.testing.assert_array_equal(
                np.asarray(C.to_bits(x, "bf16")),
                np.asarray(C.to_bits(ops.decode(ct_f), "bf16")))

    def test_fused_equals_two_stage(self):
        """Same layout, bit-identical streams and decode across the A/B pair."""
        x = _bf16_specials_input(seed=3, n=16384)
        ct_f = ops.encode(x, BF16_CB)
        ct_t = twostage.encode(x, BF16_CB)
        _assert_streams_equal(ct_f, ct_t)
        np.testing.assert_array_equal(
            np.asarray(C.to_bits(ops.decode(ct_f), "bf16")),
            np.asarray(C.to_bits(twostage.decode(ct_t), "bf16")))

    def test_backend_fused_flag(self):
        be_f = B.PallasBackend()
        be_t = B.PallasBackend(fused=False)
        assert be_f.fused and not be_t.fused
        x = _bf16_specials_input(seed=4, n=8192)
        _assert_streams_equal(be_f.encode(x, BF16_CB), be_t.encode(x, BF16_CB))

    def test_global_layout_streams_match_reference(self):
        x = _bf16_specials_input(seed=5, n=16384)
        ct_f = ops.encode(x, BF16_CB, layout="global", cap=4096)
        ct_r = C.encode(x, BF16_CB, layout="global", cap=4096)
        _assert_streams_equal(ct_f, ct_r)
        # decode uses the sparse bit-patch (bounded, no full-stream pass)
        np.testing.assert_array_equal(
            np.asarray(C.to_bits(x, "bf16")),
            np.asarray(C.to_bits(ops.decode(ct_f), "bf16")))

    def test_global_layout_chunk_overflow_is_conservative(self):
        """A chunk overflowing the level-1 buffer forces ok=False (raw
        fallback) even when the global capacity would fit — losslessness is
        preserved by being conservative, never by dropping escapes."""
        bits = np.full(4096, np.uint16(120 << 7), dtype=np.uint16)
        bits[: splitzip_encode.MAX_FUSED_CAP + 1] = np.uint16(7 << 7)  # 1 chunk
        x = jax.lax.bitcast_convert_type(jnp.asarray(bits), jnp.bfloat16)
        ct_f = ops.encode(x, BF16_CB, layout="global", cap=4096)
        assert not bool(ct_f.ok)
        assert bool(C.encode(x, BF16_CB, layout="global", cap=4096).ok)

    def test_oversized_cap_delegates_to_two_stage(self):
        x = _bf16_specials_input(seed=6, n=8192)
        cap = splitzip_encode.MAX_FUSED_CAP * 8
        ct = ops.encode(x, BF16_CB, cap=cap)
        _assert_streams_equal(ct, C.encode(x, BF16_CB, cap=cap))
        np.testing.assert_array_equal(
            np.asarray(C.to_bits(x, "bf16")),
            np.asarray(C.to_bits(ops.decode(ct), "bf16")))

    def test_decode_bits_equals_decode(self):
        x = _bf16_specials_input(seed=7, n=8192)
        for be in (B.get_backend("xla"), B.PallasBackend(),
                   B.PallasBackend(fused=False)):
            ct = be.encode(x, BF16_CB)
            np.testing.assert_array_equal(
                np.asarray(be.decode_bits(ct)),
                np.asarray(C.to_bits(be.decode(ct), "bf16").reshape(-1)))


class TestSingleLaunchStructure:
    """The launch-count claim, asserted at the jaxpr level (the benchmark
    re-asserts on lowered HLO): fused encode/decode each contain exactly one
    pallas_call, and fused decode has NO scatter tail."""

    @staticmethod
    def _prims(fn, *args):
        jaxpr = jax.make_jaxpr(fn)(*args)
        names = []

        def walk(j):
            for eqn in j.eqns:
                names.append(eqn.primitive.name)
                for sub in eqn.params.values():
                    if hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr)
        walk(jaxpr.jaxpr)
        return names

    def test_fused_encode_single_pallas_call_no_scatter(self):
        x = _bf16_specials_input(seed=8, n=8192)
        prims = self._prims(lambda v: ops.encode(v, BF16_CB), x)
        assert prims.count("pallas_call") == 1
        assert not any(p.startswith("scatter") for p in prims)

    def test_fused_decode_single_pallas_call_no_scatter(self):
        x = _bf16_specials_input(seed=8, n=8192)
        ct = ops.encode(x, BF16_CB)
        prims = self._prims(ops.decode, ct)
        assert prims.count("pallas_call") == 1
        assert not any(p.startswith("scatter") for p in prims)

    def test_two_stage_decode_has_scatter_tail(self):
        """The structural regression the fusion removes, pinned as contrast."""
        x = _bf16_specials_input(seed=8, n=8192)
        ct = twostage.encode(x, BF16_CB)
        prims = self._prims(twostage.decode, ct)
        assert any(p.startswith("scatter") for p in prims)

    def test_fused_kernels_lower_for_tpu_without_execution(self):
        """The fused kernels must lower (interpret=False) even though we
        can't run them on CPU — the TPU-targeting proof for the fused path."""
        bits = jax.ShapeDtypeStruct((64, 1024), jnp.uint16)
        try:
            low_e = jax.jit(lambda b: splitzip_encode.encode_fused(
                b, CODEBOOK, cap=64, interpret=False)).lower(bits)
            a = jax.ShapeDtypeStruct((64, 1024), jnp.uint8)
            p = jax.ShapeDtypeStruct((64, 512), jnp.uint8)
            ep = jax.ShapeDtypeStruct((64, 64), jnp.uint16)
            ev = jax.ShapeDtypeStruct((64, 64), jnp.uint8)
            ec = jax.ShapeDtypeStruct((64, 1), jnp.int32)
            low_d = jax.jit(lambda *t: splitzip_decode.decode_fused(
                *t, CODEBOOK, interpret=False)).lower(p, a, ep, ev, ec)
        except Exception:
            pytest.skip("pallas TPU lowering unavailable on this backend")
        for low in (low_e, low_d):
            txt = low.as_text()
            assert "custom_call" in txt or "tpu" in txt.lower()


class TestAutoBackend:
    def test_auto_registered_and_resolves(self):
        assert "auto" in B.available_backends()
        be = B.get_backend("auto")
        expect = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert be.name == expect

    def test_auto_roundtrip_through_transfer_config(self):
        x = _bf16_specials_input(seed=9, n=4096)
        tc = TransferConfig(codebook=BF16_CB, backend="auto")
        be = tc.get_backend()
        np.testing.assert_array_equal(
            np.asarray(C.to_bits(x, "bf16")),
            np.asarray(C.to_bits(jnp.asarray(be.decode(be.encode(x, BF16_CB))
                                             ).reshape(x.shape), "bf16")))


def _toy_cache(seed=0):
    rng = np.random.default_rng(seed)

    def kv(shape):
        x = rng.normal(size=shape) * rng.choice([0.25, 1.0, 4.0], size=shape)
        return jnp.asarray(x, dtype=jnp.bfloat16)

    return {"k": kv((4, 2, 128, 4, 32)), "v": kv((4, 2, 128, 4, 32)),
            "ssm": jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)}


def _chunked_transfer(cache, tc):
    """One chunked-granularity transfer on a fresh plan: (cache, stats)."""
    sess = TransferPlan.build(cache, tc, granularity="chunked").session()
    return sess.transfer(cache), sess.last_stats


def _assert_bit_identical(a_tree, b_tree):
    for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree)):
        w = {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
        np.testing.assert_array_equal(
            np.asarray(jax.lax.bitcast_convert_type(a, w)),
            np.asarray(jax.lax.bitcast_convert_type(b, w)))


class TestChunkedEngineWithFusedBackend:
    @pytest.mark.parametrize("n_chunks", (1, 4))
    def test_chunked_parity_fused_vs_xla(self, n_chunks):
        cache = _toy_cache(seed=10)
        leaves = [np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16)).ravel()
                  for x in jax.tree.leaves(cache) if x.dtype == jnp.bfloat16]
        cb = cbm.calibrate(leaves, k=16)
        out_p, st_p = _chunked_transfer(
            cache, TransferConfig(codebook=cb, backend="pallas",
                                  n_chunks=n_chunks))
        out_x, st_x = _chunked_transfer(
            cache, TransferConfig(codebook=cb, backend="xla",
                                  n_chunks=n_chunks))
        _assert_bit_identical(cache, out_p)
        _assert_bit_identical(out_x, out_p)
        assert st_p.chunk_wire_bytes == st_x.chunk_wire_bytes
        assert st_p.all_ok and st_p.n_retries == 0

    def test_adaptive_capacity_recovers_heavy_tailed_chunk(self):
        """A chunk that overflows cap but fits 2·cap is retried (not rawed):
        ok stays True, the retry is recorded, and the wire bytes stay
        compressed."""
        rng = np.random.default_rng(11)
        n = 8 * 1024
        bits = np.full(n, np.uint16(120 << 7), dtype=np.uint16)
        # ~48 escapes per 1024-chunk: over cap=32, under 2*cap=64
        esc = rng.choice(n, size=(48 * n) // 1024, replace=False)
        bits[esc] = np.uint16(7 << 7)
        cache = {"a": jax.lax.bitcast_convert_type(jnp.asarray(bits),
                                                   jnp.bfloat16)}
        tc = TransferConfig(codebook=BF16_CB, cap=32, n_chunks=4,
                            backend="pallas")
        out, stats = _chunked_transfer(cache, tc)
        _assert_bit_identical(cache, out)
        assert stats.all_ok
        assert stats.n_retries >= 1
        raw = 2.0 * n / len(stats.chunk_wire_bytes)
        for wb in stats.chunk_wire_bytes:
            assert wb < raw

    def test_adaptive_retry_global_layout_clears_level1_overflow(self):
        """fused-global's conservative ok (level-1 chunk buffer overflow)
        must not make the doubled-cap retry futile: for_retry hands the
        re-encode to the two-stage structure, which has no level-1 bound,
        so a chunk whose escapes fit 2x the global budget is recovered."""
        n = 16 * 1024
        bits = np.full(n, np.uint16(120 << 7), dtype=np.uint16)
        # 200 escapes concentrated in ONE codec chunk: over the fused
        # kernel's level-1 cap (128) and over the 1% global budget (128 for
        # an 8192-element segment), but under the doubled budget (256)
        bits[:200] = np.uint16(7 << 7)
        cache = {"a": jax.lax.bitcast_convert_type(jnp.asarray(bits),
                                                   jnp.bfloat16)}
        tc = TransferConfig(codebook=BF16_CB, layout="global",
                            backend="pallas", n_chunks=2)
        out, stats = _chunked_transfer(cache, tc)
        _assert_bit_identical(cache, out)
        assert stats.all_ok
        assert stats.n_retries == 1
        raw_seg = 2.0 * n / len(stats.chunk_wire_bytes)
        assert all(wb < raw_seg for wb in stats.chunk_wire_bytes)

    def test_adaptive_capacity_still_falls_back_to_raw(self):
        """Doubling can't save an all-escape chunk: retry is recorded, the
        chunk ships raw, and the cache is still bit-exact."""
        bad = np.random.default_rng(12).integers(0, 1 << 16, 4096
                                                 ).astype(np.uint16)
        cache = {"a": jax.lax.bitcast_convert_type(jnp.asarray(bad),
                                                   jnp.bfloat16)}
        tc = TransferConfig(codebook=BF16_CB, cap=4, n_chunks=2,
                            backend="pallas")
        out, stats = _chunked_transfer(cache, tc)
        _assert_bit_identical(cache, out)
        assert not stats.all_ok
        assert stats.n_retries == len([ok for ok in stats.chunk_ok if not ok])
        for okc, wb in zip(stats.chunk_ok, stats.chunk_wire_bytes):
            if not okc:
                assert wb == pytest.approx(2.0 * 4096 / len(stats.chunk_ok))
