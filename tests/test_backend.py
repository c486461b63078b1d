"""Codec-backend parity + chunked pipelined transfer tests.

The backend registry (repro.core.backend) promises that every backend is a
bit-exact implementation of the same logical codec; these tests pin that
down across xla / pallas (interpret) / wire on bf16 and fp8 inputs including
NaN / Inf / subnormal patterns, and check that the chunked pipelined
transfer engine produces caches bit-identical to the unchunked path with
correct per-chunk wire accounting.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as B
from repro.core import codebook as cbm
from repro.core import codec as C
from repro.serving.plan import TransferConfig, TransferPlan
from repro.serving.session import decode_leaves
from repro.serving.scheduler import (DisaggregatedScheduler, Request,
                                     SchedulerConfig, summarize)
from repro.core.pipeline import CodecProfile

BACKENDS = ("xla", "pallas", "wire")
BF16_CB = cbm.Codebook(fmt="bf16", exponents=tuple(range(118, 134)))
FP8_CB = cbm.Codebook(fmt="fp8_e5m2", exponents=tuple(range(8, 24)))

# bf16 specials: quiet/payload NaN, ±Inf, ±0, subnormals, max/min finite
BF16_SPECIALS = np.array(
    [0x7FC0, 0x7FC1, 0xFFC0, 0x7F80, 0xFF80, 0x0000, 0x8000,
     0x0001, 0x8001, 0x7F7F, 0xFF7F, 0x0080, 0xFFFF, 0x7FFF],
    dtype=np.uint16)
# fp8 e5m2 specials: NaNs (0x7D-0x7F), ±Inf (0x7C/0xFC), ±0, subnormals
FP8_SPECIALS = np.array(
    [0x7D, 0x7E, 0x7F, 0xFD, 0x7C, 0xFC, 0x00, 0x80, 0x01, 0x81, 0x03,
     0x7B, 0xFB, 0xFF],
    dtype=np.uint8)


def _bits_of(x, fmt):
    return C.to_bits(x, fmt)


def _bf16_input(seed=0, n=8192, specials=True):
    rng = np.random.default_rng(seed)
    bits = np.array(jax.lax.bitcast_convert_type(
        jnp.asarray(rng.standard_normal(n).astype(np.float32)
                    * np.exp(rng.standard_normal(n))).astype(jnp.bfloat16),
        jnp.uint16))
    if specials:
        pos = rng.choice(n, size=min(n // 4, 4 * BF16_SPECIALS.size),
                         replace=False)
        bits[pos] = np.tile(BF16_SPECIALS, -(-pos.size // BF16_SPECIALS.size)
                            )[: pos.size]
    return jax.lax.bitcast_convert_type(jnp.asarray(bits), jnp.bfloat16)


def _fp8_bits(seed=0, n=4096):
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 256, n).astype(np.uint8)
    pos = rng.choice(n, size=4 * FP8_SPECIALS.size, replace=False)
    bits[pos] = np.tile(FP8_SPECIALS, 4)
    return jnp.asarray(bits)


class TestRegistry:
    def test_builtin_backends_registered(self):
        for name in BACKENDS:
            assert name in B.available_backends()
            assert B.get_backend(name).name == name

    def test_unknown_backend_raises(self):
        with pytest.raises(KeyError):
            B.get_backend("does-not-exist")

    def test_register_custom_backend(self):
        class Fake(B.XlaBackend):
            name = "fake"
        B.register_backend("fake", Fake)
        try:
            assert B.get_backend("fake").name == "fake"
        finally:
            B._REGISTRY.pop("fake", None)
            B._INSTANCES.pop("fake", None)

    def test_wire_backend_rejected_inside_shard_map_path(self):
        assert not B.get_backend("wire").jittable
        assert B.get_backend("xla").jittable
        assert B.get_backend("pallas").jittable


class TestBackendParity:
    """All backends must produce bit-identical roundtrips on the same data."""

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_bf16_roundtrip_with_specials(self, backend):
        x = _bf16_input(seed=1)
        be = B.get_backend(backend)
        y = be.decode(be.encode(x, BF16_CB, cap=1024))
        np.testing.assert_array_equal(
            np.asarray(_bits_of(x, "bf16")),
            np.asarray(_bits_of(jnp.asarray(y).reshape(x.shape), "bf16")))

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_fp8_roundtrip_with_specials(self, backend):
        bits = _fp8_bits(seed=2)
        be = B.get_backend(backend)
        ct = be.encode(bits, FP8_CB, cap=1024)
        y = be.decode(ct)
        np.testing.assert_array_equal(
            np.asarray(bits),
            np.asarray(_bits_of(jnp.asarray(y).reshape(bits.shape),
                                "fp8_e5m2")))

    def test_ingraph_backends_produce_identical_streams(self):
        """xla and pallas are the SAME layout, not merely both lossless."""
        x = _bf16_input(seed=3, n=16384)
        ct_x = B.get_backend("xla").encode(x, BF16_CB)
        ct_p = B.get_backend("pallas").encode(x, BF16_CB)
        for lx, lp in zip(jax.tree.leaves(ct_x), jax.tree.leaves(ct_p)):
            np.testing.assert_array_equal(np.asarray(lx), np.asarray(lp))

    @pytest.mark.parametrize("backend", ("xla", "pallas"))
    def test_global_layout_parity(self, backend):
        x = _bf16_input(seed=4, n=8192)
        be = B.get_backend(backend)
        ct = be.encode(x, BF16_CB, layout="global", cap=8192)
        assert ct.layout == "global"
        assert bool(be.ok(ct))
        y = be.decode(ct)
        np.testing.assert_array_equal(
            np.asarray(_bits_of(x, "bf16")),
            np.asarray(_bits_of(jnp.asarray(y).reshape(x.shape), "bf16")))

    def test_wire_backend_always_ok(self):
        # all-escape input: in-graph ok goes False, wire has no capacity limit
        bits = jnp.full((4096,), np.uint16(7 << 7), dtype=jnp.uint16)
        x = jax.lax.bitcast_convert_type(bits, jnp.bfloat16)
        cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(118, 134)))
        assert not bool(B.get_backend("xla").ok(
            B.get_backend("xla").encode(x, cb, cap=8)))
        ct_w = B.get_backend("wire").encode(x, cb, cap=8)
        assert B.get_backend("wire").ok(ct_w) is True
        np.testing.assert_array_equal(
            np.asarray(bits),
            np.asarray(_bits_of(B.get_backend("wire").decode(ct_w), "bf16")))


def _toy_cache(seed=0):
    rng = np.random.default_rng(seed)
    def kv(shape):
        x = rng.normal(size=shape) * rng.choice([0.25, 1.0, 4.0], size=shape)
        return jnp.asarray(x, dtype=jnp.bfloat16)
    return {"k": kv((4, 2, 128, 4, 32)), "v": kv((4, 2, 128, 4, 32)),
            "ssm": jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)}


def _cache_cb(cache):
    leaves = [np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16)).ravel()
              for x in jax.tree.leaves(cache) if x.dtype == jnp.bfloat16]
    return cbm.calibrate(leaves, k=16)


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


class TestChunkedPipelinedTransfer:
    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("n_chunks", (1, 3, 8))
    def test_chunked_matches_unchunked_bit_exact(self, backend, n_chunks):
        cache = _toy_cache(seed=5)
        cb = _cache_cb(cache)
        tc = TransferConfig(codebook=cb, backend=backend, n_chunks=n_chunks)
        out, stats = _chunked_transfer(cache, tc)
        _assert_bit_identical(cache, out)
        assert len(stats.chunk_wire_bytes) == n_chunks
        assert stats.all_ok
        # wire accounting: compressed chunks beat raw, fp32 leaf ships raw
        bf16_raw = sum(x.size * 2 for x in jax.tree.leaves(cache)
                       if x.dtype == jnp.bfloat16)
        assert sum(stats.chunk_wire_bytes) < bf16_raw
        assert stats.raw_passthrough_bytes == 4 * 4 * 8 * 16

    def test_per_chunk_raw_fallback_stays_lossless(self):
        """Adversarial bits + tiny capacity: overflowing chunks ship raw and
        are charged raw bytes; the cache still reassembles bit-exactly."""
        rng = np.random.default_rng(6)
        # half the stream escapes everything (uniform bits), half compresses
        bad = rng.integers(0, 1 << 16, 8 * 1024).astype(np.uint16)
        good = np.full(8 * 1024, np.uint16(120 << 7), dtype=np.uint16)
        cache = {"a": jax.lax.bitcast_convert_type(jnp.asarray(bad),
                                                   jnp.bfloat16),
                 "b": jax.lax.bitcast_convert_type(jnp.asarray(good),
                                                   jnp.bfloat16)}
        cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(118, 134)))
        tc = TransferConfig(codebook=cb, cap=4, n_chunks=4)
        out, stats = _chunked_transfer(cache, tc)
        _assert_bit_identical(cache, out)
        assert not stats.all_ok and any(stats.chunk_ok)
        for okc, wb in zip(stats.chunk_ok, stats.chunk_wire_bytes):
            if not okc:  # raw fallback chunk: charged exactly raw bf16 bytes
                assert wb == pytest.approx(2 * 4 * 1024)

    def test_engine_chunked_parity_and_per_chunk_stats(self):
        """Acceptance: DisaggregatedEngine.transfer with n_chunks=8 returns a
        bit-identical cache to the unchunked path, and EngineStats reports
        per-chunk wire bytes."""
        from repro.configs.base import ShapeConfig, get_config
        from repro.models import model as M
        from repro.serving.engine import DisaggregatedEngine

        cfg = get_config("smollm-135m").reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        shape = ShapeConfig("smoke", seq_len=24, global_batch=2, kind="train")
        prompt = {k: v for k, v in M.make_inputs(cfg, shape, seq=16).items()
                  if k != "labels"}
        _, state = M.prefill(params, prompt, cfg, max_seq=24)
        cb = _cache_cb(state.cache)

        eng1 = DisaggregatedEngine(cfg, params, cb, compress=True)
        eng8 = DisaggregatedEngine(cfg, params, cb, compress=True, n_chunks=8)
        out1 = eng1.transfer(state)
        out8 = eng8.transfer(state)
        _assert_bit_identical(out1.cache, out8.cache)
        _assert_bit_identical(state.cache, out8.cache)
        assert eng1.stats.chunk_wire_bytes == []
        assert len(eng8.stats.chunk_wire_bytes) >= 2
        assert sum(eng8.stats.chunk_wire_bytes) <= eng8.stats.wire_bytes
        assert eng8.stats.wire_bytes < eng8.stats.raw_cache_bytes
        # end-to-end generation through the pipelined transfer stays exact
        toks8 = eng8.generate(prompt, num_steps=4, max_seq=24)
        toks1 = eng1.generate(prompt, num_steps=4, max_seq=24)
        np.testing.assert_array_equal(np.asarray(toks8), np.asarray(toks1))


def _tensor_transfer(cache, tc):
    """One tensor-path transfer stopped at the streams: (comp, raw, stats)."""
    sess = TransferPlan.build(cache, tc).session()
    comp, raw = sess.transfer_compressed(cache)
    return comp, raw, sess.last_stats


class TestWireBytesAccounting:
    """The tensor path's per-leaf wire accounting: compressed bytes where
    the escape capacity held, raw bytes where the schedule fell back."""

    def test_ok_tensor_charged_compressed_bytes(self):
        x = _bf16_input(seed=7, n=4096, specials=False)
        cb = cbm.calibrate(
            [np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16))], k=16)
        comp, _, stats = _tensor_transfer({"x": x}, TransferConfig(codebook=cb))
        assert stats.wire_bytes == pytest.approx(
            float(C.compressed_bytes(comp["x"])))

    def test_overflowed_tensor_charged_raw_bytes(self):
        bits = jnp.full((4096,), np.uint16(7 << 7), dtype=jnp.uint16)
        x = jax.lax.bitcast_convert_type(bits, jnp.bfloat16)
        cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(118, 134)))
        tc = TransferConfig(codebook=cb, cap=4)
        comp, raw, stats = _tensor_transfer({"x": x}, tc)
        assert "x" not in comp and "x" in raw     # shipped raw
        assert stats.leaf_ok["x"] is False
        assert stats.wire_bytes == pytest.approx(
            2.0 * 4096)  # raw bf16 bytes, not the (useless) compressed size

    def test_fp32_hi_overflow_falls_back_to_raw_leaf(self):
        """An overflowed fp32 hi-half must ship the WHOLE fp32 leaf raw
        (drop the lo-half entry, restore the original leaf) — regression
        test for the KeyError on '#hi'-suffixed comp keys."""
        import dataclasses as dc
        from repro.configs.base import get_config
        from repro.models.kvcache import DecodeState
        from repro.serving.engine import DisaggregatedEngine

        rng = np.random.default_rng(9)
        cache = {"s": jnp.asarray(rng.normal(size=(4096,)), jnp.float32)}
        bad_cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(16)))
        eng = DisaggregatedEngine(get_config("smollm-135m").reduced(), None,
                                  bad_cb, compress=True, cap=2)
        eng.tc = dc.replace(eng.tc, compress_fp32=True)
        state = DecodeState(cache=cache, cache_len=jnp.zeros((1,), jnp.int32))
        out = eng.transfer(state)
        _assert_bit_identical(cache, out.cache)
        assert not eng.stats.codec_ok
        # charged raw fp32 bytes (hi raw u16 + lo raw u16 == 4 bytes/elem)
        assert eng.stats.wire_bytes == pytest.approx(4.0 * 4096)

    def test_backend_mismatch_is_corrected_per_object(self):
        """decode_leaves with the wrong backend= argument still decodes:
        dispatch follows the compressed object's type, not the argument."""
        x = _bf16_input(seed=8, n=2048, specials=False)
        cb = cbm.calibrate(
            [np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16))], k=16)
        comp, raw, stats = _tensor_transfer(
            {"x": x}, TransferConfig(codebook=cb, backend="wire"))
        out = decode_leaves(comp, raw, {"x": x})  # default 'xla' arg
        _assert_bit_identical({"x": x}, out)
        assert stats.wire_bytes == pytest.approx(float(len(comp["x"].payload)))

    def test_mixed_tree_sums_per_tensor(self):
        good_bits = jnp.full((2048,), np.uint16(120 << 7), dtype=jnp.uint16)
        bad_bits = jnp.full((2048,), np.uint16(7 << 7), dtype=jnp.uint16)
        cache = {"good": jax.lax.bitcast_convert_type(good_bits, jnp.bfloat16),
                 "bad": jax.lax.bitcast_convert_type(bad_bits, jnp.bfloat16),
                 "raw32": jnp.zeros((100,), jnp.float32)}
        cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(118, 134)))
        comp, _, stats = _tensor_transfer(cache, TransferConfig(codebook=cb,
                                                               cap=4))
        total = stats.wire_bytes
        expect = (float(C.compressed_bytes(comp["good"]))  # ok -> compressed
                  + 2.0 * 2048                             # overflow -> raw
                  + 400.0)                                 # fp32 passthrough
        assert total == pytest.approx(expect)


class TestPipelinedSchedulerModel:
    def _run(self, compress, n_chunks=1):
        sched = DisaggregatedScheduler(SchedulerConfig(
            kv_bytes_per_token=2 * 32 * 8 * 128 * 2,
            profile=CodecProfile(g_enc=613.3e9, g_dec=2181.8e9, ratio=1.324,
                                 link_bw=87.5e9),
            compress=compress, n_chunks=n_chunks))
        for i in range(16):
            sched.submit(Request(rid=i, arrival=i * 1e-3, prompt_len=16384,
                                 max_new_tokens=16))
        return summarize(sched.run())

    def test_pipelined_beats_additive_when_codec_visible(self):
        # at 87.5 GB/s the additive codec time is non-negligible; the chunked
        # pipeline hides most of it behind the link
        additive = self._run(True, n_chunks=1)
        pipelined = self._run(True, n_chunks=8)
        assert pipelined["mean_ttft_s"] < additive["mean_ttft_s"]

    def test_pipelined_still_beats_native(self):
        native = self._run(False)
        pipelined = self._run(True, n_chunks=8)
        assert pipelined["mean_ttft_s"] < native["mean_ttft_s"]
