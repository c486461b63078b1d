"""The harness end to end on the CPU, at a tiny size.

A cell is added the way a later change adds one: a copy of ``bench/`` and
``BENCHMARK.json`` gains a configuration file, a traffic file, a limits file
and a per-layer metric's reader, and no existing file is edited; the
harness finds them by name. The harness's look for a chip is skipped (the
CPU has no row in the peaks table, so a stand-in is passed), the rest of a
run is driven as on the chip: the program passes its check, the float8
control does not, and each fault planted under the timed path turns
``correct`` false.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.harness import run_cell
from bench.spec import load_cell

REPO = Path(__file__).resolve().parents[2]
CELL = "tiny-gqa.tiny-long"
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 101
CONFIG = {"source": "a tiny GQA model for the CPU", "family": "gqa",
          "model_type": "tiny", "hidden_size": 64, "intermediate_size": 128,
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
          "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
          "rms_norm_eps": 1e-6, "reduced": []}
MIX = {"loop": "closed", "batch": 2, "prompt_tokens": 24, "new_tokens": 4,
       "transfer": "compressed", "resident": "raw", "trace_batches": 1,
       "check_requests": 4}
# CPU readings at this size over 16 seeds: program at most 0.0038, the
# float8 control at least 0.0130 (0.0454 on SEED)
LIMITS = {"logit_gap": {"limit": 0.01}, "transfer_bits": {"limit": 0}}
READER = '''"""engine.decode_ms: host time of engine.decode, mean per batch."""


def read(ctx):
    return sum(b.times["decode"] for b in ctx.batches) / len(ctx.batches) * 1e3
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for kind, name, body in (("configs", "tiny-gqa", CONFIG),
                             ("traffic", "tiny-long", MIX),
                             ("limits", CELL, LIMITS)):
        (root / "bench" / kind / f"{name}.json").write_text(json.dumps(body))
    (root / "bench" / "metrics" / "engine.decode_ms.py").write_text(READER)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-gqa", "source": CONFIG["source"],
                             "file": "bench/configs/tiny-gqa.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-gqa",
                               "traffic": "tiny-long", "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"]:
        m.setdefault("workloads", []).append(CELL)
    bench["per_layer"].append({
        "name": "engine.decode_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "engine", "moves": "tpot_ms",
        "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root, traced=False, modes=("f32",)):
    cell = load_cell(CELL, root)
    return run_cell(cell, SEED, 0.2, traced, t_start=time.perf_counter(),
                    modes=modes, peak=PEAK, log=lambda msg: None)


def test_cli_needs_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         "qwen3-32b-l8.long-input", "--seed", str(2 ** 31 + 5), "--seconds",
         "1", "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_new_cell_found_by_name_and_control_fails(root):
    res = run(root, modes=("f32", "fp8"))
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {
        "ttft_p50_ms", "ttft_p90_ms", "tpot_ms", "output_tokens_per_s",
        "resident_tpot_ms", "resident_output_tokens_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["diagnostics"]["compiles_in_window"] == 0
    limit = res["checks"]["logit_gap"]["limit"]
    assert res["diagnostics"]["control_gap_fp8"] > limit


def test_traced_run_reads_the_new_metric(root):
    res = run(root, traced=True)
    assert res["correct"] is True
    assert res["metrics"]["engine.decode_ms"]["value"] > 0
    assert res["metrics"]["engine.decode_ms"]["unit"] == "ms"
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _alter_tokens(monkeypatch):
    from repro.serving import decode
    real = decode.decode_loop

    def altered(*args, **kw):
        toks, state = real(*args, **kw)
        return (toks + 1) % CONFIG["vocab_size"], state
    monkeypatch.setattr(decode, "decode_loop", altered)


def _state_unchanged(monkeypatch):
    from repro.models import model
    real = model.decode_step

    def stale(params, tokens, state, cfg):
        return real(params, tokens, state, cfg)[0], state
    monkeypatch.setattr(model, "decode_step", stale)


def _flip_a_bit(monkeypatch):
    from repro.serving.session import TransferSession
    real = TransferSession.transfer

    def flipped(self, cache, *args, **kw):
        out = real(self, cache, *args, **kw)
        key = sorted(out)[0]
        u = jax.lax.bitcast_convert_type(out[key], jnp.uint16)
        u = u.at[(0,) * u.ndim].set(u[(0,) * u.ndim] ^ 1)
        return dict(out, **{key: jax.lax.bitcast_convert_type(
            u, out[key].dtype)})
    monkeypatch.setattr(TransferSession, "transfer", flipped)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _flip_a_bit],
                         ids=["token-altered", "state-unchanged",
                              "transfer-bit"])
def test_fault_under_the_timed_path_fails(root, monkeypatch, fault):
    fault(monkeypatch)
    res = run(root)
    assert res["correct"] is False
