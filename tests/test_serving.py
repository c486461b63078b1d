"""End-to-end disaggregated serving tests: the paper's Table 9 invariant —
serving THROUGH the compressed transfer produces bit-identical results to
serving without it — plus transfer accounting and scheduler behaviour."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, ShapeConfig, get_config
from repro.core import codebook as cbm
from repro.core.pipeline import (CodecProfile, additive_transfer_time,
                                 native_transfer_time, pipelined_transfer_time)
from repro.models import model as M
from repro.serving.engine import DisaggregatedEngine
from repro.serving.plan import (TransferConfig, TransferPlan, raw_wire_bytes,
                                transfer_report)
from repro.serving.session import decode_leaves
from repro.serving.scheduler import DisaggregatedScheduler, Request, SchedulerConfig, summarize

SHAPE = ShapeConfig("smoke", seq_len=24, global_batch=2, kind="train")


def _kv_codebook(cache):
    leaves = [np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16)).ravel()
              for x in jax.tree.leaves(cache) if x.dtype == jnp.bfloat16]
    if not leaves:
        return cbm.Codebook(fmt="bf16", exponents=tuple(range(112, 128)))
    return cbm.calibrate(leaves, k=16)


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen3-moe-30b-a3b",
                                  "minicpm3-4b", "mamba2-2.7b",
                                  "recurrentgemma-9b"])
def test_generation_identical_with_and_without_compression(arch):
    """Table 9: exact output match through the compressed PD boundary."""
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = M.make_inputs(cfg, SHAPE, seq=16)
    prompt = {k: v for k, v in batch.items() if k != "labels"}

    # calibrate on this model's actual cache exponents (paper §3.3)
    _, state0 = M.prefill(params, prompt, cfg, max_seq=24)
    cb = _kv_codebook(state0.cache)

    eng_c = DisaggregatedEngine(cfg, params, cb, compress=True)
    eng_n = DisaggregatedEngine(cfg, params, cb, compress=False)
    out_c = eng_c.generate(prompt, num_steps=6, max_seq=24)
    out_n = eng_n.generate(prompt, num_steps=6, max_seq=24)

    np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_n))
    assert eng_c.stats.codec_ok
    # compression actually reduced the wire bytes (bf16 leaves exist)
    has_bf16 = any(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(state0.cache))
    if has_bf16:
        assert eng_c.stats.wire_bytes < eng_c.stats.raw_cache_bytes
        assert eng_n.stats.wire_bytes == eng_n.stats.raw_cache_bytes


def test_overflow_falls_back_to_raw_and_stays_lossless():
    """Adversarial distribution + tiny escape capacity: the per-tensor raw
    fallback must keep the generation identical (unconditional losslessness;
    DESIGN.md §2) while wire accounting charges raw bytes."""
    cfg = get_config("smollm-135m").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = M.make_inputs(cfg, SHAPE, seq=16)
    prompt = {k: v for k, v in batch.items() if k != "labels"}

    # deliberately mis-calibrated codebook: most exponents escape
    bad_cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(16)))
    eng_c = DisaggregatedEngine(cfg, params, bad_cb, compress=True, cap=4)
    eng_n = DisaggregatedEngine(cfg, params, bad_cb, compress=False)
    out_c = eng_c.generate(prompt, num_steps=6, max_seq=24)
    out_n = eng_n.generate(prompt, num_steps=6, max_seq=24)

    np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_n))
    assert not eng_c.stats.codec_ok          # overflow was detected
    # fallback shipped raw: no byte reduction on the overflowed tensors
    assert eng_c.stats.wire_bytes >= eng_c.stats.raw_cache_bytes


def test_fp32_state_compression_bit_exact():
    """Beyond-paper fp32 codec (hi/lo split): SSM/RG-LRU recurrent states are
    fp32, which the paper's bf16-only codec skips entirely.  The hi u16 half
    has the BF16 bit layout, so the same codebook compresses it losslessly."""
    rng = np.random.default_rng(3)
    cache = {"ssm": jnp.asarray(rng.normal(size=(4, 2, 8, 16, 32)), jnp.float32),
             "k": jnp.asarray(rng.normal(size=(4, 2, 64, 2, 16)), jnp.bfloat16)}
    cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(115, 131)))
    tc = TransferConfig(codebook=cb, layout="global", compress_fp32=True,
                        global_budget=0.05)
    sess = TransferPlan.build(cache, tc).session()
    comp, raw = sess.transfer_compressed(cache)
    assert "ssm#hi" in comp and "ssm#lo" in raw   # split happened
    out = decode_leaves(comp, raw, cache)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(out)):
        w = jnp.uint32 if a.dtype == jnp.float32 else jnp.uint16
        np.testing.assert_array_equal(
            np.asarray(jax.lax.bitcast_convert_type(a, w)),
            np.asarray(jax.lax.bitcast_convert_type(b, w)))
    # wire accounting: fp32 leaf now ships < raw bytes
    assert sess.last_stats.wire_bytes < raw_wire_bytes(cache)


def test_cache_roundtrip_bit_exact_all_leaves():
    cfg = get_config("llama3.2-3b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    prompt = {k: v for k, v in M.make_inputs(cfg, SHAPE, seq=16).items()
              if k != "labels"}
    _, state = M.prefill(params, prompt, cfg, max_seq=16)
    cb = _kv_codebook(state.cache)
    tc = TransferConfig(codebook=cb)
    back = TransferPlan.build(state.cache, tc).session().transfer(state.cache)
    for a, b in zip(jax.tree.leaves(state.cache), jax.tree.leaves(back)):
        if a.dtype == jnp.bfloat16:
            np.testing.assert_array_equal(
                np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16)),
                np.asarray(jax.lax.bitcast_convert_type(b, jnp.uint16)))
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_wire_bytes_close_to_four_thirds():
    cfg = get_config("llama3.2-3b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    prompt = {k: v for k, v in M.make_inputs(cfg, SHAPE, seq=16).items()
              if k != "labels"}
    _, state = M.prefill(params, prompt, cfg, max_seq=16)
    cb = _kv_codebook(state.cache)
    sess = TransferPlan.build(state.cache, TransferConfig(codebook=cb)).session()
    sess.transfer(state.cache)
    wire = sess.last_stats.wire_bytes
    rawb = raw_wire_bytes(state.cache)
    assert 1.2 < rawb / wire <= 4 / 3 + 1e-6


def test_transfer_report_matches_paper_structure():
    # paper Fig. 4 at 64K: compressed transfer dominates, codec is minor
    # (paper reports 92.9% / 5.7% / 1.4%; our additive model with the paper's
    # own throughput+bandwidth constants gives ~80/15/4 — same structure)
    raw = 1.75e9
    # RoCE 4x200G regime: transfer dominates, codec visible but minor
    p_fast = CodecProfile(g_enc=613.3e9, g_dec=2181.8e9, ratio=1.324, link_bw=87.5e9)
    rep = transfer_report(raw, raw / 1.324, p_fast)
    assert rep.speedup > 1.0
    assert rep.t_transfer / rep.t_splitzip > 0.75
    assert (rep.t_encode + rep.t_decode) / rep.t_splitzip < 0.25
    # 100GbE-class inter-cluster regime: codec fully amortized, speedup ≈ ρ
    p_slow = CodecProfile(g_enc=613.3e9, g_dec=2181.8e9, ratio=1.324, link_bw=12.5e9)
    rep2 = transfer_report(raw, raw / 1.324, p_slow)
    assert rep2.speedup > 1.25
    assert rep2.t_transfer / rep2.t_splitzip > 0.95


class TestScheduler:
    def _cfg(self, compress):
        return SchedulerConfig(
            kv_bytes_per_token=2 * 32 * 8 * 128 * 2,
            profile=CodecProfile(g_enc=613.3e9, g_dec=2181.8e9, ratio=1.324,
                                 link_bw=12.5e9),
            compress=compress,
        )

    def _run(self, compress, n=32, prompt=16384):
        s = DisaggregatedScheduler(self._cfg(compress))
        for i in range(n):
            s.submit(Request(rid=i, arrival=i * 1e-3, prompt_len=prompt,
                             max_new_tokens=32))
        return summarize(s.run())

    def test_compression_improves_ttft_and_throughput_when_link_bound(self):
        with_c = self._run(True)
        without = self._run(False)
        assert with_c["mean_ttft_s"] < without["mean_ttft_s"]
        assert with_c["throughput_req_s"] >= without["throughput_req_s"]

    def test_all_requests_complete(self):
        out = self._run(True, n=10)
        assert out["n"] == 10


KV_BYTES_TOK = 2 * 32 * 8 * 128 * 2
PROF = CodecProfile(g_enc=613.3e9, g_dec=2181.8e9, ratio=1.324, link_bw=25e9)


class TestEventDrivenScheduler:
    """ISSUE 4 invariants suite: link-occupancy conservation, FIFO
    serialization, decode-aware TTFT, plan-aware vs legacy charging,
    event-queue determinism."""

    def _cfg(self, **kw):
        base = dict(kv_bytes_per_token=KV_BYTES_TOK, profile=PROF,
                    compress=True)
        base.update(kw)
        return SchedulerConfig(**base)

    def _run(self, cfg, reqs):
        s = DisaggregatedScheduler(cfg)
        for r in reqs:
            s.submit(r)
        return s, s.run()

    def test_link_occupied_exactly_once_no_double_charge(self):
        """Regression: the old drain loop re-iterated decode-blocked requests
        every pass, advancing t_link and overwriting transfer_done.  With one
        decode slot and slow decode, every request must still occupy the link
        exactly once, back-to-back."""
        cfg = self._cfg(max_decode_slots=1, decode_time_per_step=0.05)
        reqs = [Request(rid=i, arrival=0.0, prompt_len=16384, max_new_tokens=8)
                for i in range(6)]
        s, done = self._run(cfg, reqs)
        assert len(done) == 6
        ivs = sorted((r.link_start, r.transfer_done) for r in done)
        durs = [b - a for a, b in ivs]
        for (a0, b0), (a1, b1) in zip(ivs, ivs[1:]):
            assert a1 >= b0 - 1e-12          # never overlapping
        # conservation: total occupancy == sum of the single charges; equal
        # prompts => equal charges; the backlog never inflated the link
        assert s.link_busy_s == pytest.approx(sum(durs))
        assert max(durs) == pytest.approx(min(durs))
        assert ivs[-1][1] - ivs[0][0] == pytest.approx(sum(durs))

    def test_fifo_link_serialization(self):
        cfg = self._cfg(max_prefill_batch=2)
        reqs = [Request(rid=i, arrival=i * 1e-3, prompt_len=8192,
                        max_new_tokens=4) for i in range(8)]
        s, done = self._run(cfg, reqs)
        order = sorted(done, key=lambda r: r.link_start)
        pf = [r.prefill_done for r in order]
        assert pf == sorted(pf)              # FIFO by prefill completion
        for a, b in zip(order, order[1:]):
            assert b.link_start >= a.transfer_done - 1e-12

    def test_ttft_waits_for_decode_worker(self):
        """Regression: first_token_time used to be transfer_done + one step,
        ignoring decode-worker occupancy.  With a single busy slot the second
        request's first token must wait for the slot AND the step boundary."""
        cfg = SchedulerConfig(max_decode_slots=1, decode_time_per_step=1.0,
                              prefill_time_per_token=0.0, profile=None)
        a = Request(rid=0, arrival=0.0, prompt_len=4, max_new_tokens=3)
        b = Request(rid=1, arrival=0.0, prompt_len=4, max_new_tokens=2)
        _, done = self._run(cfg, [a, b])
        by = {r.rid: r for r in done}
        assert by[0].first_token_time == pytest.approx(1.0)
        assert by[0].finish_time == pytest.approx(3.0)
        # b's transfer finished at t=0, but the only slot is busy until t=3:
        # first token at 4.0, NOT transfer_done + decode_time_per_step = 1.0
        assert by[1].transfer_done == pytest.approx(0.0)
        assert by[1].first_token_time == pytest.approx(4.0)
        assert by[1].finish_time == pytest.approx(5.0)

    def test_zero_new_tokens_terminates(self):
        """Regression: max_new_tokens <= 0 made steps == 0 in the old stage-3
        drain and the loop never terminated; such budgets are clamped to one
        decoded token (TTFT needs a first token)."""
        for bad in (0, -3):
            s, done = self._run(self._cfg(), [
                Request(rid=0, arrival=0.0, prompt_len=1024,
                        max_new_tokens=bad)])
            assert len(done) == 1
            assert done[0].tokens_out == 1
            assert done[0].finish_time > done[0].transfer_done

    def test_plan_built_once_per_bucket_and_reused(self):
        cfg = self._cfg(bucket_tokens=1024)
        reqs = [Request(rid=i, arrival=0.0, prompt_len=pl, max_new_tokens=2)
                for i, pl in enumerate([1000, 1024, 512, 4096])]
        s, done = self._run(cfg, reqs)
        assert len(done) == 4
        assert set(s.plans) == {1024, 4096}  # 1000/1024/512 share one plan
        assert all(isinstance(p, TransferPlan) for p in s.plans.values())

    def test_plan_aware_matches_legacy_when_chunks_equal(self):
        """Acceptance: plan-aware charging must agree EXACTLY with the legacy
        equal-chunk model when the plan's segments are equal-sized (and with
        the additive/native accounting at tensor granularity)."""
        bytes_ = 16384 * KV_BYTES_TOK        # stream divides evenly: 8 equal
        req = lambda: Request(rid=0, arrival=0.0, prompt_len=16384,
                              max_new_tokens=1)
        s, done = self._run(self._cfg(n_chunks=8), [req()])
        plan = s.plans[16384]
        assert len({seg.n_elements for seg in plan.segments}) == 1
        dur = done[0].transfer_done - done[0].link_start
        assert dur == pytest.approx(pipelined_transfer_time(bytes_, PROF, 8),
                                    rel=1e-12)
        _, done = self._run(self._cfg(n_chunks=1), [req()])
        dur = done[0].transfer_done - done[0].link_start
        assert dur == pytest.approx(additive_transfer_time(bytes_, PROF),
                                    rel=1e-12)
        _, done = self._run(self._cfg(compress=False), [req()])
        dur = done[0].transfer_done - done[0].link_start
        assert dur == pytest.approx(native_transfer_time(bytes_, PROF),
                                    rel=1e-12)

    def test_plan_estimate_diverges_with_short_tail_segment(self):
        """Acceptance: when chunk alignment produces a short last segment the
        flowshop over ACTUAL sizes must diverge from the equal-chunk model."""
        cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(112, 128)))
        p = PROF
        # 2560 elements, 2 chunks: ceil-split 1280 aligns up to 2048 =>
        # segments [2048, 512] — unequal
        plan = TransferPlan.build(
            {"kv": jax.ShapeDtypeStruct((2560,), jnp.bfloat16)},
            TransferConfig(codebook=cb, n_chunks=2))
        assert [seg.n_elements for seg in plan.segments] == [2048, 512]
        est = plan.estimate_time(p)
        legacy = pipelined_transfer_time(2.0 * 2560, p, 2)
        assert abs(est - legacy) / legacy > 1e-9
        # equal segments reduce to the legacy model exactly
        plan_eq = TransferPlan.build(
            {"kv": jax.ShapeDtypeStruct((4096,), jnp.bfloat16)},
            TransferConfig(codebook=cb, n_chunks=2))
        assert plan_eq.estimate_time(p) == pytest.approx(
            pipelined_transfer_time(2.0 * 4096, p, 2), rel=1e-12)

    def test_overflow_expectation_inflates_charge(self):
        """Expected capacity-schedule retries / raw fallbacks make the charge
        strictly larger — extra encode attempts, fallback at full link cost."""
        cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(112, 128)))
        plan = TransferPlan.build(
            {"kv": jax.ShapeDtypeStruct((8192,), jnp.bfloat16)},
            TransferConfig(codebook=cb, n_chunks=4))
        attempts, raw_frac = plan.expected_attempts(0.3)
        k = len(plan.schedule_for(plan.segments[0].n_elements,
                                  plan.segments[0].cap))
        assert attempts == pytest.approx(sum(0.3 ** i for i in range(k)))
        assert raw_frac == pytest.approx(0.3 ** k)
        assert plan.estimate_time(PROF, overflow_p=0.3) > plan.estimate_time(PROF)
        # and the scheduler passes it through to the charged duration
        req = lambda: Request(rid=0, arrival=0.0, prompt_len=16384,
                              max_new_tokens=1)
        _, base = self._run(self._cfg(n_chunks=4), [req()])
        _, slow = self._run(self._cfg(n_chunks=4, overflow_p=0.5), [req()])
        assert (slow[0].transfer_done - slow[0].link_start) > \
            (base[0].transfer_done - base[0].link_start)

    def test_event_queue_determinism_under_interleaved_arrivals(self):
        """Identical request sets submitted in any order produce identical
        per-request timings (queues are rid-tie-broken, same-timestamp events
        fully drain before dispatch)."""
        rng = random.Random(7)

        def make():
            arrivals = [0.0, 0.0, 0.0, 1e-3, 1e-3, 2e-3, 2e-3, 2e-3, 5e-3,
                        5e-3, 8e-3, 8e-3]
            return [Request(rid=i, arrival=a, prompt_len=4096 * (1 + i % 3),
                            max_new_tokens=2 + i % 4)
                    for i, a in enumerate(arrivals)]

        def snap(order):
            cfg = self._cfg(max_prefill_batch=3, max_decode_slots=2,
                            decode_time_per_step=1e-3)
            _, done = self._run(cfg, order)
            return {r.rid: (r.prefill_done, r.link_start, r.transfer_done,
                            r.admit_time, r.first_token_time, r.finish_time)
                    for r in done}

        base = snap(make())
        for _ in range(3):
            order = make()
            rng.shuffle(order)
            assert snap(order) == base

    def test_p99_nearest_rank(self):
        """Regression: the floor index int(0.99 * (n-1)) underestimated the
        tail; nearest-rank (ceil) picks the true max for n=10 distinct TTFTs."""
        done = [Request(rid=i, arrival=0.0, prompt_len=1, max_new_tokens=1,
                        first_token_time=float(i + 1), finish_time=10.0,
                        tokens_out=1) for i in range(10)]
        out = summarize(done)
        assert out["p99_ttft_s"] == 10.0     # old floor index gave 9.0
        # n=100: nearest rank = 99th value
        done = [Request(rid=i, arrival=0.0, prompt_len=1, max_new_tokens=1,
                        first_token_time=float(i + 1), finish_time=100.0,
                        tokens_out=1) for i in range(100)]
        assert summarize(done)["p99_ttft_s"] == 99.0

    def test_zero_decode_slots_fails_loudly(self):
        """Misconfigurations that strand requests (admission can never
        happen) must raise, not return a silently partial done list."""
        s = DisaggregatedScheduler(self._cfg(max_decode_slots=0))
        s.submit(Request(rid=0, arrival=0.0, prompt_len=1024,
                         max_new_tokens=1))
        with pytest.raises(RuntimeError, match="never completed"):
            s.run()

    def test_engine_plan_requires_kv_bytes_per_token(self):
        """A pre-built plan with the default kv_bytes_per_token == 0 would
        silently charge every prompt length the plan's build-time bytes."""
        cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(112, 128)))
        plan = TransferPlan.build(
            {"kv": jax.ShapeDtypeStruct((4096,), jnp.bfloat16)},
            TransferConfig(codebook=cb))
        with pytest.raises(ValueError, match="kv_bytes_per_token"):
            DisaggregatedScheduler(SchedulerConfig(plan=plan, profile=PROF))

    def test_fp8_sidecar_raw_fallback_charged_at_full_link(self):
        """overflow_p must degrade the fp8 sidecar's wire cost too: the
        schedule-exhausted fraction ships raw at full link bandwidth."""
        cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(112, 128)))
        plan = TransferPlan.build(
            {"a": jax.ShapeDtypeStruct((4096,), jnp.float8_e5m2)},
            TransferConfig(codebook=cb))
        est = plan.estimate_time(PROF, overflow_p=1.0)
        assert est > plan.estimate_time(PROF)
        assert est >= 4096 / PROF.link_bw   # full link cost, no ratio

    def test_engine_hands_plan_to_scheduler(self):
        """DisaggregatedEngine.scheduler_config: the scheduler charges through
        the SAME TransferPlan object the engine's session executes."""
        cfg = get_config("smollm-135m").reduced()
        cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(112, 128)))
        eng = DisaggregatedEngine(cfg, None, cb, compress=True,
                                  profile=PROF)
        cache = {"k": jnp.zeros((2, 1, 8, 2, 16), jnp.bfloat16),
                 "v": jnp.zeros((2, 1, 8, 2, 16), jnp.bfloat16)}
        eng._session_for(cache)              # resolves the plan once
        sc = eng.scheduler_config(kv_bytes_per_token=KV_BYTES_TOK)
        assert sc.plan is eng.plan and sc.profile is PROF
        s, done = self._run(sc, [Request(rid=0, arrival=0.0, prompt_len=16384,
                                         max_new_tokens=2)])
        assert not s.plans                   # no bucket plans: engine's used
        dur = done[0].transfer_done - done[0].link_start
        # tensor-granularity plan, pure-bf16 cache: additive accounting scaled
        # to this prompt's bytes
        assert dur == pytest.approx(
            additive_transfer_time(16384 * KV_BYTES_TOK, PROF), rel=1e-9)
