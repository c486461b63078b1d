"""A compressed-resident MLA cell through the harness on the CPU, at a tiny
size with the benchmark's latent geometry.

The latent (256 wide) and its rope key (32 wide) give 64-token pages, as
MiniCPM3-4B's do: a prompt of 120 tokens is admitted as one full page of
each leaf and a tail, and the twelve decode steps fill the tail and flush it
into a second page. A copy of ``bench/`` and ``BENCHMARK.json``
gains the cell's configuration, traffic and limits files and its entries,
as a later change would add them. The program passes its check, the float8
control does not, and each fault planted in the resident path turns
``correct`` false.
"""

import dataclasses
import json
import shutil
import time
from pathlib import Path

import pytest

from bench.harness import run_cell
from bench.spec import load_cell

REPO = Path(__file__).resolve().parents[2]
CELL = "tiny-mla.tiny-resident"
RESIDENT_CELL = "qwen3-32b-l8.resident-decode"
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 33 + 41
CONFIG = {"source": "a tiny MLA model for the CPU", "family": "mla",
          "model_type": "tiny", "hidden_size": 64, "intermediate_size": 128,
          "num_attention_heads": 4, "num_key_value_heads": 4,
          "num_hidden_layers": 2, "vocab_size": 256, "q_lora_rank": 32,
          "kv_lora_rank": 256, "qk_nope_head_dim": 16,
          "qk_rope_head_dim": 32, "rope_theta": 10000.0,
          "rms_norm_eps": 1e-5, "reduced": []}
MIX = {"loop": "closed", "batch": 2, "prompt_tokens": 120, "new_tokens": 12,
       "transfer": "compressed", "resident": "compressed",
       "trace_batches": 1, "check_requests": 4}
# CPU readings at this size: the program about 0.003, the float8 control
# about 0.05
LIMITS = {"logit_gap": {"limit": 0.01}, "transfer_bits": {"limit": 0}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for kind, name, body in (("configs", "tiny-mla", CONFIG),
                             ("traffic", "tiny-resident", MIX),
                             ("limits", CELL, LIMITS)):
        (root / "bench" / kind / f"{name}.json").write_text(json.dumps(body))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-mla", "source": CONFIG["source"],
                             "file": "bench/configs/tiny-mla.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-mla",
                               "traffic": "tiny-resident", "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if RESIDENT_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root, traced=False, modes=("f32",)):
    return run_cell(load_cell(CELL, root), SEED, 0.2, traced,
                    t_start=time.perf_counter(), modes=modes, peak=PEAK,
                    log=lambda msg: None)


def test_resident_mla_cell_is_correct_and_control_fails(root):
    res = run(root, modes=("f32", "fp8"))
    assert res["correct"] is True
    assert res["checks"]["transfer_bits"]["value"] == 0
    assert set(res["metrics"]) == {"resident_tpot_ms",
                                   "resident_output_tokens_per_s", "setup_s"}
    counters = res["diagnostics"]["counters"]
    assert counters["resident_demotions"] == 0
    # one boundary flush a batch, warm-up included
    assert counters["resident_page_flushes"] == counters["resident_admits"]
    limit = res["checks"]["logit_gap"]["limit"]
    assert res["diagnostics"]["control_gap_fp8"] > limit


def test_traced_resident_mla_run_reads_the_pool_counters(root):
    res = run(root, traced=True)
    assert res["correct"] is True
    # 12 steps, one `ok` read at the one page-boundary flush
    assert res["metrics"]["resident.host_reads"]["value"] == \
        pytest.approx(1 / 12)
    assert res["metrics"]["engine.resident_admit_ms"]["value"] > 0


def _tokens_altered(monkeypatch):
    from repro.serving import decode
    real = decode.resident_decode_loop

    def altered(*args, **kw):
        toks, state, demoted = real(*args, **kw)
        return (toks + 1) % CONFIG["vocab_size"], state, demoted
    monkeypatch.setattr(decode, "resident_decode_loop", altered)


def _step_state_unchanged(monkeypatch):
    from repro.serving import decode
    real = decode._resident_step

    def stale(params, tok, state, cfg, interpret):
        logits, _, _ = real(params, tok, state, cfg, interpret)
        return logits, {k: leaf.tail for k, leaf in state.leaves.items()}, \
            state.cache_len
    monkeypatch.setattr(decode, "_resident_step", stale)


def _flush_dropped(monkeypatch):
    from repro.models.kvpool import KVPool
    monkeypatch.setattr(KVPool, "flush_full_tails",
                        lambda self, state, lens=None: state)


def _page_bit_flipped(monkeypatch):
    from repro.models.kvpool import KVPool
    real = KVPool.admit_from_wire

    def flipped(self, *args, **kw):
        st = real(self, *args, **kw)
        key = sorted(st.leaves)[0]
        leaf = st.leaves[key]
        page = leaf.page_table[0, 0, 0]          # layer 0, row 0, page 0
        sm = leaf.sign_mantissa.at[page, 0, 0].set(
            leaf.sign_mantissa[page, 0, 0] ^ 1)
        return dataclasses.replace(st, leaves=dict(
            st.leaves, **{key: dataclasses.replace(leaf, sign_mantissa=sm)}))
    monkeypatch.setattr(KVPool, "admit_from_wire", flipped)


@pytest.mark.parametrize("fault", [_tokens_altered, _step_state_unchanged,
                                   _flush_dropped, _page_bit_flipped],
                         ids=["token-altered", "step-state-unchanged",
                              "flush-dropped", "page-bit"])
def test_fault_in_the_resident_path_fails(root, monkeypatch, fault):
    fault(monkeypatch)
    assert run(root)["correct"] is False
