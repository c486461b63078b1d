"""The MLA mixture-of-experts family (DeepSeek-V3 layout) in the benchmark.

At tiny widths on the CPU: the plain reference against the program's
prefill and cached decode, its weight recipe, the configuration's mapping,
the counts by hand, the two MoE metrics on a synthetic trace and counters,
and a tiny cell through the harness, where the program passes, the float8
control does not, and each fault planted in the expert layer turns
``correct`` false.
"""

import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, program, seeds
from bench.gap_survey import FAULTS, summary
from bench.harness import run_cell
from bench.reference import common, mla_moe
from bench.spec import load_cell, load_module

REPO = Path(__file__).resolve().parents[2]
SEED = 2 ** 32 + 1234567
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# a dense prefix layer, YaRN, a value head (24) wider than hidden / heads
# (16), 16 experts in 4 groups with the best 2 kept, 4 a token, 4 held from
# expert 4, one shared
TINY = {"source": "a tiny DeepSeek-V3-like model for the CPU",
        "family": "mla_moe", "model_type": "tiny", "hidden_size": 64,
        "intermediate_size": 96, "num_attention_heads": 4,
        "num_key_value_heads": 4, "num_hidden_layers": 3, "vocab_size": 256,
        "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 24, "rope_theta": 10000,
        "rms_norm_eps": 1e-6,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 16,
                         "type": "yarn"},
        "first_k_dense_replace": 1, "moe_intermediate_size": 32,
        "n_routed_experts": 4, "router_experts": 16, "held_expert_offset": 4,
        "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "reduced": [],
        "assumed": {"e_score_correction_bias_std": 0.001}}
# the same with a correction bias wide enough to change picks
BIASED = dict(TINY, assumed={"e_score_correction_bias_std": 0.1})
PROMPT, STEPS = 24, 8


def _params(conf):
    from repro.models import model as M
    cfg = program.arch_config(conf)
    return cfg, jax.jit(lambda k: M.init_params(cfg, k))(
        seeds.key(SEED, "weights"))


def _served_logits(cfg, params):
    """Prefill logits at every prompt position, then STEPS greedy steps
    through the program's cache; returns (tokens, logits)."""
    from repro.models import model as M
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, PROMPT), 0,
                              TINY["vocab_size"], jnp.int32)
    full = jax.jit(lambda p, t: M.forward(p, {"tokens": t}, cfg)[0])(
        params, toks)
    _, state = jax.jit(lambda p, t: M.prefill(
        p, {"tokens": t}, cfg, max_seq=PROMPT + STEPS))(params, toks)
    step = jax.jit(lambda p, t, s: M.decode_step(p, t, s, cfg))
    out, cur, seq = [full], full[:, -1].argmax(-1), [toks]
    for _ in range(STEPS):
        seq.append(cur[:, None])
        logits, state = step(params, cur[:, None], state)
        out.append(logits[:, None])
        cur = logits.argmax(-1)
    return (np.asarray(jnp.concatenate(seq, axis=1)),
            np.asarray(jnp.concatenate(out, axis=1).astype(jnp.float32)))


def test_reference_matches_prefill_and_decode():
    """The program through prefill and 8 cached decode steps against the
    reference's full forward. In float32 at the highest precision it is the
    same arithmetic, summed in another order (1e-6 of the largest logit
    here): within 1e-4. As served, in bf16, rounding now and then flips a
    pick at the top-k or group boundary, which moves that token by a whole
    expert's share, so the typical position is held: its median error
    within 4% (three layers of bf16 activations), and the float8 control's
    at least four times the program's. The exact comparison runs again with
    a correction bias wide enough to change picks."""
    for conf in (TINY, BIASED):
        cfg, params = _params(conf)
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        with jax.default_matmul_precision("highest"):
            tokens, exact = _served_logits(cfg, p32)
        ref = common.logits_at(mla_moe, conf, SEED, tokens, first=0,
                               count=tokens.shape[1])["f32"]
        scale = np.abs(ref).max()
        assert np.abs(exact - ref).max() / scale < 1e-4
    cfg, params = _params(TINY)
    tokens, got = _served_logits(cfg, params)
    ref = common.logits_at(mla_moe, TINY, SEED, tokens, first=0,
                           count=tokens.shape[1], modes=("f32", "fp8"))
    scale = np.abs(ref["f32"]).max()
    err = np.median(np.abs(got - ref["f32"]).max(-1)) / scale
    assert err < 0.04, err
    assert np.median(np.abs(got[:, PROMPT:] - ref["f32"][:, PROMPT:]).max(-1)) \
        / scale < 0.04
    assert np.median(np.abs(ref["fp8"] - ref["f32"]).max(-1)) / scale > 4 * err


def test_reference_draws_the_programs_weights():
    _, params = _params(TINY)
    w = jax.jit(lambda k: mla_moe.layer_weights(TINY, k, 2))(
        seeds.key(SEED, "weights"))
    ffn = params["layers"]["ffn"]                  # layer 2 is MoE layer 1
    f = TINY["moe_intermediate_size"]
    for x in range(TINY["n_routed_experts"]):
        gu = ffn["w_gate_up"][1, x].astype(jnp.float32)
        assert bool(jnp.array_equal(w["experts"][x]["w_gate"], gu[:, :f]))
        assert bool(jnp.array_equal(w["experts"][x]["w_up"], gu[:, f:]))
        assert bool(jnp.array_equal(
            w["experts"][x]["w_down"], ffn["w_down"][1, x].astype(jnp.float32)))
    assert bool(jnp.array_equal(w["router"], ffn["router"][1]))
    assert bool(jnp.array_equal(w["bias"], ffn["router_bias"][1]))
    assert bool(jnp.array_equal(
        w["shared"]["w_down"], ffn["shared"]["w_down"][1].astype(jnp.float32)))
    assert bool(w["is_moe"])
    d = jax.jit(lambda k: mla_moe.layer_weights(TINY, k, 0))(
        seeds.key(SEED, "weights"))
    assert not bool(d["is_moe"])
    assert bool(jnp.array_equal(
        d["mlp"]["w_up"],
        params["dense_layers"]["ffn"]["w_up"][0].astype(jnp.float32)))
    assert bool(jnp.array_equal(
        d["wkv_b"], params["dense_layers"]["attn"]["wkv_b"][0].astype(
            jnp.float32)))


def test_config_maps_onto_the_program():
    conf = json.loads((REPO / "bench" / "configs" / "deepseek-v3-l8.json")
                      .read_text())
    cfg = program.arch_config(conf)
    assert cfg.moe.num_experts == 256 and cfg.moe.held == 8
    assert cfg.mla.v_head_dim == 128 and cfg.mla.yarn.factor == 40
    # softmax scale: 192^-1/2 times (0.1 ln 40 + 1)^2
    assert cfg.mla.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
    assert cfg.mla.softmax_scale == pytest.approx(mla_moe.softmax_scale(conf))
    # the reference counts the parameters the program holds
    assert cfg.param_count() == (
        mla_moe.layer_params(conf, dense=True) + 7 * mla_moe.layer_params(conf)
        + 2 * conf["vocab_size"] * conf["hidden_size"])
    # about 9.85 GB of bf16 weights and a float32 router
    assert 9.8e9 < mla_moe.weight_bytes(conf, touched=7 * 8) \
        + 2 * conf["vocab_size"] * conf["hidden_size"] < 9.9e9


def test_yarn_frequencies_match_the_program():
    from repro.models.layers import rope_frequencies
    cfg = program.arch_config(TINY)
    np.testing.assert_allclose(
        rope_frequencies(8, 10000.0, cfg.mla.yarn), mla_moe.inv_freq(TINY),
        rtol=1e-12)


SMALL = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 4,
         "kv_lora_rank": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
         "v_head_dim": 3, "intermediate_size": 16, "vocab_size": 32,
         "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "moe_intermediate_size": 4, "n_routed_experts": 2,
         "router_experts": 8, "num_experts_per_tok": 2,
         "n_shared_experts": 1}


def test_counts_by_hand():
    # attention: wq_a 8*4, wq_b 4*2*4, wkv_a 8*6, wkv_b 4*2*(2 + 3),
    # wo 2*3*8 = 32 + 32 + 48 + 40 + 48
    assert mla_moe.attn_params(SMALL) == 200
    assert mla_moe.layer_params(SMALL, dense=True) == 200 + 3 * 8 * 16
    # MoE layer: 2 held + 1 shared experts of 3*8*4, router 8*8, bias 8
    assert mla_moe.layer_params(SMALL) == 200 + 3 * 96 + 64 + 8
    # a step's bytes: bf16 dense layer 584 and norms 2*8 + 4 + 4, two MoE
    # layers' attention and shared expert 200 + 96 and norms, 3 touched
    # experts, head 256 and final norm 8; float32 routers 2 * 72
    assert mla_moe.weight_bytes(SMALL, touched=3) == \
        2 * (584 + 24 + 2 * (296 + 24) + 3 * 96 + 256 + 8) + 4 * 2 * 72
    # one row, 4 tokens: products 2 * (584 + 2 * (200 + 96 + 64)) a token,
    # causal pairs 10 at 2*2*(2 + 2 + 3) a layer, head 2*8*32; 5 pairs
    assert mla_moe.prefill_flops(SMALL, 1, 4, pairs=5) == \
        2 * 1304 * 4 + 3 * 10 * 28 + 512 + 5 * 2 * 96
    # a decode step over 5 tokens: absorbed products 2*(32 + 32 + 48 + 2*2*4
    # + 2*4*3 + 2*3*8) = 400 and scores 2*2*(4 + 2 + 4)*5 = 200 a layer,
    # dense MLP 2*384, shared experts and routers 2*2*(96 + 64), head 512;
    # 2 pairs; bytes: 3 touched experts and 5 latent tokens of 3*(4 + 2)*2
    flops, nbytes = mla_moe.decode_step(SMALL, 1, 5, pairs=2, touched=3)
    assert flops == 3 * 600 + 768 + 640 + 512 + 2 * 192
    assert nbytes == mla_moe.weight_bytes(SMALL, touched=3) + 5 * 36
    # the expert kernel: 2 pairs of 2*96 FLOPs; 3 weight loads of 96 bf16
    # values, each pair's row, hidden (twice) and output: 4*(8 + 4) B
    assert mla_moe.expert_kernel_step(SMALL, 2, 3) == (384, 3 * 192 + 96)
    # without the counters no routed expert counts
    assert mla_moe.decode_step(SMALL, 1, 5) == (
        flops - 384, nbytes - 3 * 192)


class Trace:
    """Stands in for a reduced trace: one kernel's events."""

    def __init__(self, name, seconds, events):
        self.name, self.seconds, self.events = name, seconds, events

    def op_time(self, match):
        return (self.seconds, self.events) if match(self.name) else (0.0, 0)


def _reader(name):
    return load_module(REPO / "bench" / "metrics" / f"{name}.py")


def _ctx(counters, trace=None):
    return SimpleNamespace(
        conf=SMALL, fam=mla_moe, peak=PEAK, counters=counters,
        trace=trace or Trace("moe_grouped_matmul.3", 1e-6, 28),
        batches=[SimpleNamespace(traced=True), SimpleNamespace(traced=False)],
        gen=SimpleNamespace(batch=4, prompt_tokens=16, new_tokens=10))


def test_expert_roofline_on_a_synthetic_trace():
    # 2 batches ran: 40 pairs and 6 touched loads a batch
    ctx = _ctx({"prefill_calls": 2, "moe_pairs": 80,
                "moe_experts_touched": 12, "decode_tokens": 80,
                "moe_decode_pairs": 30})
    flops, nbytes = mla_moe.expert_kernel_step(SMALL, 40, 6)
    least, _ = counts.roofline_s(flops, nbytes, PEAK)
    assert _reader("moe.expert_roofline").read(ctx) == \
        pytest.approx(100 * least / 1e-6)
    # no kernel in the trace, or no counters (the parent): nothing to read
    assert _reader("moe.expert_roofline").read(
        _ctx(ctx.counters, Trace("fusion.1", 1.0, 1))) is None
    assert _reader("moe.expert_roofline").read(
        _ctx({"prefill_calls": 2})) is None


def test_pairs_per_expert_on_synthetic_counters():
    # 80 tokens of batch 4: 20 steps; 2 MoE layers, 2 held experts
    ctx = _ctx({"decode_tokens": 80, "moe_decode_pairs": 30})
    assert _reader("moe.pairs_per_expert").read(ctx) == 30 / (20 * 2 * 2)
    assert _reader("moe.pairs_per_expert").read(
        _ctx({"decode_tokens": 80})) is None


# -- a tiny cell through the harness ----------------------------------------

CELL = "tiny-ds.tiny-decode"
MIX = {"loop": "closed", "batch": 2, "prompt_tokens": PROMPT,
       "new_tokens": STEPS, "transfer": "compressed", "resident": "raw",
       "trace_batches": 1, "check_requests": 2}
# CPU readings at this size, one batch: the program 0.0, the float8 control
# 0.235, the planted faults 0.097 (expert dropped) and 0.113 (groups not
# limited)
LIMITS = {"logit_gap": {"limit": 0.05}, "transfer_bits": {"limit": 0}}
# the cell of the benchmark whose metrics the tiny cell reports
MOE_CELL = "deepseek-v3-l8.moe-decode"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for kind, name, body in (("configs", "tiny-ds", TINY),
                             ("traffic", "tiny-decode", MIX),
                             ("limits", CELL, LIMITS)):
        (root / "bench" / kind / f"{name}.json").write_text(json.dumps(body))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-ds", "source": TINY["source"],
                             "file": "bench/configs/tiny-ds.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-ds",
                               "traffic": "tiny-decode", "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if MOE_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root, traced=False, modes=("f32",)):
    """One run whose window holds one batch (``seconds`` 0), so the checked
    requests are that batch's two rows whatever the machine's speed: a
    fault is caught only where it flips a served token, and a window of
    some seconds would sample other rows on a busier machine."""
    return run_cell(load_cell(CELL, root), SEED, 0.0, traced,
                    t_start=time.perf_counter(), modes=modes, peak=PEAK,
                    log=lambda msg: None)


def test_moe_cell_is_correct_and_control_fails(root):
    res = run(root, modes=("f32", "fp8"))
    assert res["correct"] is True
    assert res["checks"]["transfer_bits"]["value"] == 0
    counters = res["diagnostics"]["counters"]
    assert counters["moe_decode_pairs"] > 0
    assert counters["moe_pairs"] > counters["moe_decode_pairs"]
    assert res["diagnostics"]["control_gap_fp8"] > \
        res["checks"]["logit_gap"]["limit"]


def test_traced_moe_run_reads_the_expert_metrics(root):
    res = run(root, traced=True)
    assert res["correct"] is True
    metrics = res["metrics"]
    # a CPU trace has no TPU plane: the kernel's time is read on the chip
    # only (the reader itself on a synthetic trace above)
    assert "moe.expert_roofline" not in metrics
    # 2 tokens a step through 2 MoE layers, 4 picks each of which 4 of 16
    # experts are held here: about 2 * 4 * 4 / 16 = 2 pairs a layer and
    # step, over 4 held experts
    assert 0 < metrics["moe.pairs_per_expert"]["value"] <= 2.0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_expert_layer_fails(root, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    assert run(root)["correct"] is False


def test_gap_survey_numbers():
    gaps = np.array([[0.0, 0.5, 0.0, 0.0], [0.0, 0.0, 2.0, 0.0]])
    assert summary(gaps) == {"widest": 2.0, "mean": 2.5 / 8,
                             "disagree": 2 / 8, "positions": 8}
