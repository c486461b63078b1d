"""``prefill_attn_roofline``: the fused prefill attention kernel's share of its
roofline. Its FLOPs are the second difference of each family's
``prefill_flops`` in the sequence, times the causal pairs; its bytes are q +
k + v + o, the kernel's own count (``flash_attention.hbm_bytes``)."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import counts
from bench.reference import gqa, mla, mla_moe
from bench.spec import load_module
from bench.tests.test_bench_counts import PEAK
from repro.kernels.flash_attention import hbm_bytes

REPO = Path(__file__).resolve().parents[2]
READER = load_module(REPO / "bench" / "metrics" / "prefill_attn_roofline.py")

# cell -> (family, configuration, batch, prompt tokens, per-pair FLOPs of one
# row and layer by hand, (H, Hkv, d, dv) of the kernel's call)
CELLS = {
    "qwen3-32b-l8.long-input": (gqa, "qwen3-32b-l8", 4, 2048,
                                2 * 2 * 64 * 128, (64, 8, 128, 128)),
    "minicpm3-4b.long-input": (mla, "minicpm3-4b", 2, 4096,
                               2 * 40 * (64 + 32 + 64), (40, 40, 96, 64)),
    "deepseek-v3-l8.moe-decode": (mla_moe, "deepseek-v3-l8", 8, 512,
                                  2 * 128 * (128 + 64 + 128),
                                  (128, 128, 192, 128)),
}


def _conf(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("cell", list(CELLS))
def test_attention_count_by_hand(cell):
    fam, name, batch, seq, per_pair, (h, hkv, d, dv) = CELLS[cell]
    conf = _conf(name)
    layers = conf["num_hidden_layers"]
    assert READER.pair_flops(fam, conf, batch, seq) == \
        batch * layers * per_pair
    flops, nbytes = READER.prefill_attention(fam, conf, batch, seq)
    assert flops == batch * layers * per_pair * seq * (seq + 1) / 2
    assert nbytes == layers * hbm_bytes(batch, seq, seq, h, hkv, d, dv)


class Trace:
    """Stands in for a reduced trace: one operation's events."""

    def __init__(self, name, seconds):
        self.name, self.seconds = name, seconds

    def op_time(self, match):
        return (self.seconds, 1) if match(self.name) else (0.0, 0)


def _ctx(cell, op, seconds):
    fam, name, batch, seq, _, _ = CELLS[cell]
    return SimpleNamespace(
        trace=Trace(op, seconds), conf=_conf(name), fam=fam, peak=PEAK,
        gen=SimpleNamespace(batch=batch, prompt_tokens=seq),
        batches=[SimpleNamespace(traced=True), SimpleNamespace(traced=False)])


def test_no_reading_without_the_kernel():
    # the XLA scan's fusions, as on a parent without the kernel
    assert READER.read(_ctx("minicpm3-4b.long-input", "fusion.164", 1.4)) \
        is None


@pytest.mark.parametrize("cell", list(CELLS))
def test_reading_at_peak_time(cell):
    """A kernel as fast as the chip's peak allows reads 100%, twice as slow
    50%: never over 100 for a kernel that does the counted work."""
    fam, name, batch, seq, _, _ = CELLS[cell]
    flops, nbytes = READER.prefill_attention(fam, _conf(name), batch, seq)
    least, _ = counts.roofline_s(flops, nbytes, PEAK)
    at_peak = READER.read(_ctx(cell, "flash_attention.3", least))
    assert at_peak == pytest.approx(100.0, rel=1e-12) and at_peak <= 100.0
    assert READER.read(_ctx(cell, "flash_attention", 2 * least)) == \
        pytest.approx(50.0, rel=1e-12)
