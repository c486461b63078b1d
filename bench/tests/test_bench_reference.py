"""The plain float32 reference against the program's prefill and decode.

At tiny widths on the CPU, from the same seed: the reference draws its own
weights by the program's recipe, so the two must agree to the rounding of
the program's bf16 activations (two layers of it: within 4% of the largest
logit), while the float8 control is held to be further off than that.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, seeds
from bench.reference import common, gqa, mla

TINY = {
    "gqa": {"family": "gqa", "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-6},
    "mla": {"family": "mla", "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "num_hidden_layers": 2, "vocab_size": 256, "q_lora_rank": 32,
            "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-5},
}
FAMILY = {"gqa": gqa, "mla": mla}
SEED = 2 ** 31 + 17
PROMPT, STEPS = 24, 4
# bf16 rounding of every activation over two layers, against float32
TOL = 0.04


def program_logits(conf):
    """Prefill logits at every prompt position, then STEPS greedy decode
    steps through the program's cache; returns (tokens, logits)."""
    from repro.models import model as M
    cfg = program.arch_config(conf)
    params = jax.jit(lambda k: M.init_params(cfg, k))(
        seeds.key(SEED, "weights"))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, PROMPT), 0,
                              conf["vocab_size"], jnp.int32)
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
    logits = jnp.concatenate(out, axis=1).astype(jnp.float32)
    return np.asarray(jnp.concatenate(seq, axis=1)), np.asarray(logits)


@pytest.mark.parametrize("family", ["gqa", "mla"])
def test_reference_matches_prefill_and_decode(family):
    conf = TINY[family]
    tokens, got = program_logits(conf)
    ref = common.logits_at(FAMILY[family], conf, SEED, tokens, first=0,
                           count=tokens.shape[1], modes=("f32", "fp8"))
    scale = np.abs(ref["f32"]).max()
    err = np.abs(got - ref["f32"]).max() / scale
    assert err < TOL, err
    # decode positions alone (prefill is checked by the same bound above)
    assert np.abs(got[:, PROMPT:] - ref["f32"][:, PROMPT:]).max() / scale \
        < TOL
    assert np.abs(ref["fp8"] - ref["f32"]).max() / scale > err


def test_reference_draws_the_programs_weights():
    from repro.models import model as M
    conf = TINY["gqa"]
    cfg = program.arch_config(conf)
    params = jax.jit(lambda k: M.init_params(cfg, k))(
        seeds.key(SEED, "weights"))
    w = jax.jit(lambda k: gqa.layer_weights(conf, k, 1))(
        seeds.key(SEED, "weights"))
    got = params["layers"]["ffn"]["w_down"][1].astype(jnp.float32)
    assert bool(jnp.array_equal(w["w_down"], got))


@pytest.mark.parametrize("name", ["qwen3-32b-l8", "minicpm3-4b"])
def test_configs_map_onto_the_program(name):
    root = Path(__file__).resolve().parents[1]
    conf = json.loads((root / "configs" / f"{name}.json").read_text())
    cfg = program.arch_config(conf)
    fam = FAMILY[conf["family"]]
    assert cfg.num_layers == conf["num_hidden_layers"]
    assert cfg.d_model == conf["hidden_size"]
    # the reference counts the parameters the program holds
    assert cfg.param_count() == pytest.approx(
        cfg.num_layers * fam.layer_params(conf)
        + 2 * conf["vocab_size"] * conf["hidden_size"], rel=1e-9)
