"""TransferPlan/TransferSession: plan build, per-leaf routing, geometric
capacity schedule, and execution parity across whole-tensor / chunked /
cross-pod targets (the api_redesign acceptance: one plan, three executions,
bit-identical results — including fp32 and fp8 leaves and forced-overflow
retry paths)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as B
from repro.core import codebook as cbm
from repro.core import codec as C
from repro.core.pipeline import (CodecProfile, pipeline_makespan,
                                 pipelined_transfer_time)
from repro.serving.plan import (FP8_DEFAULT_CODEBOOK, TransferConfig,
                                TransferPlan, transfer_report)
from repro.serving.session import decode_leaves

BF16_CB = cbm.Codebook(fmt="bf16", exponents=tuple(range(118, 134)))


def _mixed_cache(seed=0, seq=128):
    """bf16 KV + fp32 recurrent state + fp8 activations + int passthrough."""
    rng = np.random.default_rng(seed)
    def kv(shape):
        x = rng.normal(size=shape) * rng.choice([0.25, 1.0, 4.0], size=shape)
        return jnp.asarray(x, dtype=jnp.bfloat16)
    return {
        "k": kv((4, 2, seq, 4, 32)),
        "v": kv((4, 2, seq, 4, 32)),
        "ssm": jnp.asarray(rng.normal(size=(4, 8, 64)), jnp.float32),
        "act8": jnp.asarray(rng.normal(size=(4, 256)) * 0.5, jnp.float8_e5m2),
        "pos": jnp.arange(seq, dtype=jnp.int32),
    }


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
        w = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
        if jnp.issubdtype(a.dtype, jnp.integer):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_array_equal(
                np.asarray(jax.lax.bitcast_convert_type(a, w)),
                np.asarray(jax.lax.bitcast_convert_type(b, w)))


class TestPlanBuild:
    def test_routing_table(self):
        cache = _mixed_cache()
        cb = _cache_cb(cache)
        tc = TransferConfig(codebook=cb, compress_fp32=True, n_chunks=4)
        plan = TransferPlan.build(cache, tc)
        routes = plan.route_map()
        assert routes["k"].route == "splitzip"
        assert routes["v"].route == "splitzip"
        assert routes["ssm"].route == "fp32_hilo"
        assert routes["act8"].route == "fp8"
        assert routes["pos"].route == "raw"
        # fp32 hi halves fold into the stream alongside the bf16 bits
        assert plan.stream_len == (cache["k"].size + cache["v"].size
                                   + cache["ssm"].size)
        assert plan.granularity == "chunked"
        desc = plan.describe()
        for word in ("splitzip", "fp32_hilo", "fp8", "raw", "chunked"):
            assert word in desc

    def test_disabled_plan_routes_everything_raw(self):
        cache = _mixed_cache()
        plan = TransferPlan.build(
            cache, TransferConfig(codebook=BF16_CB, enabled=False, n_chunks=8))
        assert all(r.route == "raw" for r in plan.routes)
        assert plan.granularity == "tensor" and plan.stream_len == 0

    def test_segments_are_chunk_aligned_and_cover_stream(self):
        cache = _mixed_cache()
        tc = TransferConfig(codebook=_cache_cb(cache), n_chunks=5, chunk=1024)
        plan = TransferPlan.build(cache, tc)
        assert plan.segments[0].start == 0
        assert plan.segments[-1].stop == plan.stream_len
        for a, b in zip(plan.segments, plan.segments[1:]):
            assert a.stop == b.start
            assert a.n_elements % tc.chunk == 0  # all but last aligned
        assert len(plan.segments) <= 5

    def test_build_from_abstract_structure(self):
        cache = _mixed_cache()
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache)
        tc = TransferConfig(codebook=_cache_cb(cache), n_chunks=4)
        plan_a = TransferPlan.build(abstract, tc)
        plan_c = TransferPlan.build(cache, tc)
        assert plan_a.routes == plan_c.routes
        assert plan_a.segments == plan_c.segments
        assert plan_a.matches(cache)

    def test_matches_rejects_different_structure(self):
        cache = _mixed_cache()
        tc = TransferConfig(codebook=_cache_cb(cache))
        plan = TransferPlan.build(cache, tc)
        other = dict(cache, k=cache["k"][:, :1])
        assert not plan.matches(other)
        sess = plan.session()
        with pytest.raises(ValueError):
            sess.transfer(other)

    def test_mesh_plan_rejects_host_backend(self):
        from repro.launch.mesh import make_mesh
        cache = {"k": jnp.zeros((4, 8), jnp.bfloat16)}
        # single-device 'mesh' is enough to exercise build-time validation
        with pytest.raises((ValueError, AssertionError)):
            TransferPlan.build(cache, TransferConfig(codebook=BF16_CB,
                                                     backend="wire"),
                               mesh=jax.sharding.Mesh(
                                   np.array(jax.devices()[:1]).reshape(1),
                                   ("pod",)))


class TestCapacitySchedule:
    def test_geometric_then_global(self):
        be = B.get_backend("xla")
        steps = be.capacity_schedule("chunked", 64, 1 << 20)
        caps = [c for _, _, c in steps]
        layouts = [l for _, l, _ in steps]
        assert caps[:3] == [64, 128, 256]
        assert layouts[:3] == ["chunked"] * 3
        assert layouts[-1] == "global"
        assert caps[-1] >= 2 * caps[-2]

    def test_zero_doublings_disables_retries(self):
        be = B.get_backend("xla")
        assert be.capacity_schedule("chunked", 64, 1 << 20, doublings=0) == (
            (be, "chunked", 64),)
        rng = np.random.default_rng(13)
        bits = rng.integers(0, 1 << 16, 4096).astype(np.uint16)
        cache = {"a": jax.lax.bitcast_convert_type(jnp.asarray(bits),
                                                   jnp.bfloat16)}
        tc = TransferConfig(codebook=BF16_CB, cap=4, n_chunks=2,
                            retry_doublings=0)
        out, st = _chunked_transfer(cache, tc)
        _assert_bit_identical(cache, out)
        assert not st.all_ok and st.n_retry_steps == 0  # fail-fast to raw

    def test_fused_global_retry_switches_structure(self):
        be = B.PallasBackend()
        steps = be.capacity_schedule("global", 128, 1 << 16)
        # retries must route through the two-stage structure (no level-1 cap)
        assert any(isinstance(s[0], B.PallasBackend) and not s[0].fused
                   for s in steps[1:])


class TestExecutionParity:
    """One plan, executed whole-tensor vs chunked: bit-identical, and the
    accounting matches the route table."""

    @pytest.mark.parametrize("backend", ("xla", "pallas"))
    def test_whole_vs_chunked_with_fp32_and_fp8(self, backend):
        cache = _mixed_cache(seed=1)
        cb = _cache_cb(cache)
        mk = lambda n: TransferConfig(codebook=cb, backend=backend,
                                      compress_fp32=True, n_chunks=n)
        out_whole = TransferPlan.build(cache, mk(1)).session().transfer(cache)
        sess = TransferPlan.build(cache, mk(4)).session()
        out_chunk = sess.transfer(cache)
        _assert_bit_identical(cache, out_whole)
        _assert_bit_identical(cache, out_chunk)
        _assert_bit_identical(out_whole, out_chunk)
        st = sess.last_stats
        assert len(st.chunk_wire_bytes) == len(sess.plan.segments)
        assert st.all_ok
        # fp32 leaves are IN the pipe (hi) + counted lo halves, not silent raw
        assert st.fp32_lo_wire_bytes == 2.0 * cache["ssm"].size
        assert st.fp8_wire_bytes > 0
        assert st.raw_passthrough_bytes == cache["pos"].size * 4
        assert st.n_elements == sess.plan.stream_len
        # the folded stream compresses: chunks beat their raw u16 bytes
        assert sum(st.chunk_wire_bytes) < 2.0 * sess.plan.stream_len

    def test_send_recv_equals_fused_transfer(self):
        cache = _mixed_cache(seed=2)
        cb = _cache_cb(cache)
        tc = TransferConfig(codebook=cb, compress_fp32=True, n_chunks=3)
        plan = TransferPlan.build(cache, tc)
        s1, s2 = plan.session(), plan.session()
        out_fused = s1.transfer(cache)
        s2.send(cache)
        out_split = s2.recv()
        _assert_bit_identical(out_fused, out_split)
        assert s1.last_stats.chunk_wire_bytes == s2.last_stats.chunk_wire_bytes
        with pytest.raises(RuntimeError):
            s2.recv()                      # nothing staged
        s2.send(cache)
        with pytest.raises(RuntimeError):
            s2.send(cache)                 # double send

    def test_session_accumulates_across_calls(self):
        cache = _mixed_cache(seed=3)
        tc = TransferConfig(codebook=_cache_cb(cache), n_chunks=2)
        sess = TransferPlan.build(cache, tc).session()
        sess.transfer(cache)
        one = sess.total_wire_bytes
        sess.transfer(cache)
        assert sess.calls == 2
        assert sess.total_wire_bytes == pytest.approx(2 * one)

    def test_chunk_bytes_plus_passthrough_equal_wire_bytes(self):
        """With no fp32 or fp8 sidecars, a chunked transfer's wire bytes are
        its pipeline chunks plus the raw passthrough, nothing else."""
        full = _mixed_cache(seed=4)
        cache = {k: full[k] for k in ("k", "v", "pos")}
        tc = TransferConfig(codebook=_cache_cb(cache), n_chunks=4)
        out, st = _chunked_transfer(cache, tc)
        _assert_bit_identical(cache, out)
        assert len(st.chunk_wire_bytes) == 4 and st.all_ok
        assert st.raw_passthrough_bytes == cache["pos"].size * 4
        assert sum(st.chunk_wire_bytes) + st.raw_passthrough_bytes == \
            pytest.approx(st.wire_bytes)

    def test_compress_cache_shim_roundtrips_fp8(self):
        """A tensor-path session round trip over the fp8 + fp32 tree: the
        fp8 leaf rides the e5m2 repack route, the fp32 leaf the hi/lo
        split, and the decoded tree is bit-exact."""
        cache = _mixed_cache(seed=5)
        tc = TransferConfig(codebook=_cache_cb(cache), compress_fp32=True)
        sess = TransferPlan.build(cache, tc).session()
        comp, raw = sess.transfer_compressed(cache)
        assert "act8" in comp              # fp8 e5m2 repack route
        assert "ssm#hi" in comp and "ssm#lo" in raw
        out = decode_leaves(comp, raw, cache)
        _assert_bit_identical(cache, out)
        _assert_bit_identical(cache, sess.transfer(cache))


class TestGeometricRetry:
    def _stream_cache(self, bits: np.ndarray):
        return {"a": jax.lax.bitcast_convert_type(jnp.asarray(bits),
                                                  jnp.bfloat16)}

    def test_schedule_recovers_via_global_switch(self):
        """A chunk whose escapes blow cap, 2cap and 4cap but fit the global
        pool must be recovered by the schedule's last step (ok stays True,
        3 extra attempts recorded)."""
        n = 8192
        bits = np.full(n, np.uint16(120 << 7), dtype=np.uint16)
        # 40 escapes inside ONE codec chunk of segment 0: cap=4 -> 8 -> 16
        # all fail; global 5% pool (256 for a 4096 segment) absorbs them
        bits[:40] = np.uint16(7 << 7)
        tc = TransferConfig(codebook=BF16_CB, cap=4, chunk=1024, n_chunks=2)
        out, st = _chunked_transfer(self._stream_cache(bits), tc)
        _assert_bit_identical(self._stream_cache(bits), out)
        assert st.chunk_ok[0] and st.all_ok
        assert st.chunk_retried[0] is True
        assert st.chunk_retry_steps[0] == 3      # 2cap, 4cap, global
        assert st.chunk_retry_steps[1] == 0
        assert st.n_retries == 1 and st.n_retry_steps == 3

    def test_schedule_exhaustion_falls_back_to_raw(self):
        rng = np.random.default_rng(6)
        bits = rng.integers(0, 1 << 16, 4096).astype(np.uint16)  # all-escape
        tc = TransferConfig(codebook=BF16_CB, cap=4, n_chunks=2)
        cache = self._stream_cache(bits)
        out, st = _chunked_transfer(cache, tc)
        _assert_bit_identical(cache, out)
        assert not st.all_ok
        # every failing chunk walked the whole schedule before shipping raw
        sched = len(B.get_backend("xla").capacity_schedule("chunked", 4, 2048))
        for okc, steps, wb in zip(st.chunk_ok, st.chunk_retry_steps,
                                  st.chunk_wire_bytes):
            if not okc:
                assert steps == sched - 1
                assert wb == pytest.approx(2.0 * 4096 / len(st.chunk_ok))

    def test_whole_tensor_route_also_retries(self):
        """The geometric schedule applies per tensor on the whole-tensor
        path too (it replaced the chunked-only 2x retry)."""
        n = 4096
        bits = np.full(n, np.uint16(120 << 7), dtype=np.uint16)
        bits[:40] = np.uint16(7 << 7)   # one heavy codec chunk
        cache = self._stream_cache(bits)
        tc = TransferConfig(codebook=BF16_CB, cap=4, chunk=1024, n_chunks=1)
        sess = TransferPlan.build(cache, tc).session()
        out = sess.transfer(cache)
        _assert_bit_identical(cache, out)
        st = sess.last_stats
        assert st.leaf_ok["a"] is True
        assert st.n_retry_steps >= 1
        assert st.leaf_wire_bytes["a"] < 2.0 * n

    def test_engine_records_retry_steps(self):
        from repro.configs.base import get_config
        from repro.models.kvcache import DecodeState
        from repro.serving.engine import DisaggregatedEngine
        n = 8192
        bits = np.full(n, np.uint16(120 << 7), dtype=np.uint16)
        bits[:40] = np.uint16(7 << 7)
        cache = self._stream_cache(np.asarray(bits))
        eng = DisaggregatedEngine(get_config("smollm-135m").reduced(), None,
                                  BF16_CB, compress=True, cap=4,
                                  chunk=1024, n_chunks=2)
        state = DecodeState(cache=cache, cache_len=jnp.zeros((1,), jnp.int32))
        out = eng.transfer(state)
        _assert_bit_identical(cache, out.cache)
        assert eng.stats.codec_ok
        assert eng.stats.chunk_retries == 1
        assert eng.stats.chunk_retry_steps == 3


class TestPlanAwarePipelineModel:
    def test_equal_chunks_match_closed_form(self):
        p = CodecProfile(g_enc=600e9, g_dec=2000e9, ratio=1.33, link_bw=50e9,
                         fixed_overhead_s=1e-4)
        total = 1 << 30
        for n in (1, 3, 8):
            assert pipeline_makespan([total / n] * n, p) == pytest.approx(
                pipelined_transfer_time(total, p, n))

    def test_short_tail_chunk_beats_equal_split_assumption(self):
        p = CodecProfile(g_enc=600e9, g_dec=2000e9, ratio=1.33, link_bw=50e9)
        # 7 full chunks + a tiny tail (what alignment actually produces)
        chunks = [128e6] * 7 + [8e6]
        assert pipeline_makespan(chunks, p) < pipelined_transfer_time(
            sum(chunks), p, 7)

    def test_plan_estimate_uses_actual_segments(self):
        cache = _mixed_cache(seed=7)
        tc = TransferConfig(codebook=_cache_cb(cache), n_chunks=4)
        plan = TransferPlan.build(cache, tc)
        p = CodecProfile(g_enc=600e9, g_dec=2000e9, ratio=1.33, link_bw=50e9)
        est = plan.estimate_time(p)
        stream, fp8, out = plan.byte_split()
        assert stream == pytest.approx(sum(plan.chunk_raw_bytes()))
        # incompressible bytes (raw passthrough) pay FULL link cost, only
        # routed bytes get the codec ratio
        assert est == pytest.approx(
            pipeline_makespan(plan.chunk_raw_bytes(), p)
            + fp8 / (p.ratio * p.link_bw) + out / p.link_bw)

    def test_plan_aware_report_tracks_measured_totals(self):
        """transfer_report(plan=) must stay a function of the MEASURED
        totals: K-call accumulation scales both sides (speedup invariant),
        and raw-fallback-inflated wire bytes raise t_splitzip."""
        cache = _mixed_cache(seed=7)
        tc = TransferConfig(codebook=_cache_cb(cache), n_chunks=4)
        plan = TransferPlan.build(cache, tc)
        p = CodecProfile(g_enc=600e9, g_dec=2000e9, ratio=1.33, link_bw=50e9)
        raw = plan.raw_bytes()
        one = transfer_report(raw, raw / 1.33, p, n_chunks=4, plan=plan)
        many = transfer_report(8 * raw, 8 * raw / 1.33, p, n_chunks=4,
                               plan=plan)
        assert many.speedup == pytest.approx(one.speedup)
        assert many.t_splitzip == pytest.approx(8 * one.t_splitzip)
        # all-raw fallback (wire == raw) must cost more than compressed wire
        degraded = transfer_report(raw, raw, p, n_chunks=4, plan=plan)
        assert degraded.t_splitzip > one.t_splitzip
        # pipeline overlap: still cheaper than the additive accounting
        additive = transfer_report(raw, raw / 1.33, p, n_chunks=1)
        assert one.t_splitzip < additive.t_splitzip


MESH_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp, numpy as np
from repro.core import codebook as cbm
from repro.launch.mesh import make_mesh
from repro.serving.plan import TransferConfig, TransferPlan

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rng = np.random.default_rng(0)
def kv(shape):
    x = rng.normal(size=shape) * rng.choice([0.25, 1.0, 4.0], size=shape)
    return jnp.asarray(x, dtype=jnp.bfloat16)
cache = {"k": kv((2, 4, 64, 2, 16)), "v": kv((2, 4, 64, 2, 16)),
         "ssm": jnp.asarray(rng.normal(size=(2, 4, 32)), jnp.float32),
         "act8": jnp.asarray(rng.normal(size=(2, 128)) * 0.5,
                             jnp.float8_e5m2)}
cb = cbm.calibrate([np.asarray(jax.lax.bitcast_convert_type(
    cache["k"], jnp.uint16))], k=16)

def run(n_chunks):
    tc = TransferConfig(codebook=cb, chunk=256, cap=16, n_chunks=n_chunks,
                        compress_fp32=True)
    sess = TransferPlan.build(cache, tc, mesh=mesh).session()
    return sess.transfer(cache)

whole, piped = run(1), run(4)
def bits(t):
    return [np.asarray(jax.lax.bitcast_convert_type(
        x, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]))
        for x in jax.tree.leaves(t)]
assert all(np.array_equal(a, b) for a, b in zip(bits(cache), bits(piped)))
assert all(np.array_equal(a, b) for a, b in zip(bits(whole), bits(piped)))
print("MESH-PARITY-OK")
"""


class TestCrossPodParity:
    def test_chunked_mesh_matches_whole_tensor_subprocess(self):
        """Acceptance: a TransferPlan executed on a 2-pod mesh with
        n_chunks > 1 (per-chunk ppermute, double-buffered) is bit-identical
        to the whole-tensor path AND to the input, fp32 + fp8 included.
        Own process: the host-device-count override must precede jax init."""
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
            "PYTHONPATH", "")
        out = subprocess.run([sys.executable, "-c", MESH_PARITY_SCRIPT],
                             capture_output=True, text=True, env=env,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "MESH-PARITY-OK" in out.stdout
