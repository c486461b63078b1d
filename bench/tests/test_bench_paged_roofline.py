"""``paged_attn_roofline`` through each family's paged kernel: the kernel's
name and widths come from ``bench/reference/<family>.py``.

The MLA kernel (``paged_mla_attention``) works in the absorbed form: each
head scores its latent query against the latent page (kv_lora_rank wide)
and its rope query against the rope page, and sums the probabilities over
the latent page, so 2·H·(2·kv_lora_rank + rope) FLOPs a token. The GQA
reading through the family's declaration equals the formula it was read by
before, on the same context.
"""

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import counts
from bench.reference import gqa, mla
from bench.spec import load_module
from bench.tests.test_bench_counts import GQA, MLA, PEAK
from bench.trace import matcher

READER = load_module(Path(__file__).resolve().parents[1] / "metrics"
                     / "paged_attn_roofline.py")


def test_mla_paged_count_by_hand():
    # 2 heads, latent 4, rope 2: queries 4 + 2 wide, values 4 wide
    heads, q_dim, v_dim = mla.paged_dims(MLA)
    assert (heads, q_dim, v_dim) == (2, 6, 4)
    flops, nbytes = counts.paged_attention_step(
        rows=1, heads=heads, head_dim=q_dim, dv=v_dim, full_pages=[2],
        tokens_per_page=8, page_bytes_kv=100)
    # 16 tokens at 2*2*(2*4 + 2) FLOPs; bytes: two pages of both leaves,
    # bf16 latent and rope queries 2*(4 + 2)*2, f32 partials 2*(4 + 2)*4
    assert flops == 16 * 2 * 2 * (2 * 4 + 2)
    assert nbytes == 200 + 24 + 48


def test_gqa_paged_dims_and_kernels():
    assert gqa.paged_dims(GQA) == (2, 4, 4)
    assert (gqa.paged_kernel, mla.paged_kernel) == (
        "paged_gqa_attention", "paged_mla_attention")


class Trace:
    """Stands in for a reduced trace: one kernel's events."""

    def __init__(self, name, seconds, events):
        self.name, self.seconds, self.events = name, seconds, events

    def op_time(self, match):
        if match(self.name):
            return self.seconds, self.events
        return 0.0, 0


def _ctx(fam, conf, kernel, *, leaves):
    layers = conf["num_hidden_layers"] = 3
    gen = SimpleNamespace(batch=4, prompt_tokens=40, new_tokens=10)
    geom = SimpleNamespace(tokens_per_page=16, leaves=[
        SimpleNamespace(page_elems=16 * m, escape_cap=8) for m in leaves])
    return SimpleNamespace(
        trace=Trace(f"{kernel}.9", 2e-3, layers * gen.new_tokens), conf=conf,
        gen=gen, geom=geom, fam=fam, peak=PEAK)


def _formula_of_the_gqa_reader(ctx):
    """The GQA reading as it was taken before the families declared it."""
    g, c, gen = ctx.geom, ctx.conf, ctx.gen
    t, events = ctx.trace.op_time(matcher("paged_gqa_attention"))
    kv_page = sum(counts.page_bytes(lg.page_elems, lg.escape_cap)
                  for lg in g.leaves)
    layers = c["num_hidden_layers"]
    flops = nbytes = 0.0
    for k in range(events // layers):
        f, b = counts.paged_attention_step(
            rows=gen.batch, heads=c["num_attention_heads"],
            head_dim=c["head_dim"], dv=c["head_dim"],
            full_pages=[(gen.prompt_tokens + k % gen.new_tokens)
                        // g.tokens_per_page] * gen.batch,
            tokens_per_page=g.tokens_per_page, page_bytes_kv=kv_page)
        flops += f * layers
        nbytes += b * layers
    return 100.0 * counts.roofline_s(flops, nbytes, PEAK)[0] / t


def test_gqa_reading_unchanged():
    ctx = _ctx(gqa, dict(GQA), "paged_gqa_attention", leaves=[4, 4])
    got = READER.read(ctx)
    assert got is not None and got > 0
    assert got == _formula_of_the_gqa_reader(ctx)


def test_mla_reading_by_hand():
    ctx = _ctx(mla, dict(MLA), "paged_mla_attention", leaves=[4, 2])
    # steps 0-9 at 40-49 tokens: 2 full pages of 16 a row on steps 0-7,
    # 3 on steps 8-9; 4 rows, 3 layers
    pages = (8 * 2 + 2 * 3) * 4 * 3
    flops = pages * 16 * 2 * 2 * (2 * 4 + 2)
    page = counts.page_bytes(64, 8) + counts.page_bytes(32, 8)
    nbytes = pages * page + 10 * 3 * (4 * 2 * 6 * 2 + 4 * 2 * (4 + 2) * 4)
    least = max(flops / PEAK["bf16_flops_per_s"],
                nbytes / PEAK["hbm_bytes_per_s"])
    assert READER.read(ctx) == pytest.approx(100.0 * least / 2e-3,
                                             rel=1e-12)


def test_no_reading_without_the_familys_kernel():
    # the GQA kernel's events are not the MLA family's kernel
    ctx = _ctx(mla, dict(MLA), "paged_gqa_attention", leaves=[4, 2])
    assert READER.read(ctx) is None
    # a family that declares no paged kernel, and a raw-resident run
    ctx = _ctx(SimpleNamespace(), dict(GQA), "paged_gqa_attention",
               leaves=[4, 4])
    assert READER.read(ctx) is None
    ctx = _ctx(gqa, dict(GQA), "paged_gqa_attention", leaves=[4, 4])
    ctx.geom = None
    assert READER.read(ctx) is None
