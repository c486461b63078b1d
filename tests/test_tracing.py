"""The program's own spans and host-read counters on the served path.

Under ``jax.profiler.trace`` the engine's stages, the transfer's per-leaf
encode and decode, the pool's admission and every resident decode step and
flush record ``sz.*`` host spans on the caller's thread, each inside its
parent; every device-to-host read on those paths sits in a ``sz.host_read``
span and is counted in ``EngineStats``. The counts are pinned against hand
counts from the code, and the profiler changes neither the served tokens nor
any counter.
"""

import dataclasses
import glob
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import get_config
from repro.core import codebook as cbm
from repro.core import spans
from repro.models import model as M
from repro.serving.engine import DisaggregatedEngine
from repro.serving.session import decode_leaves, encode_leaves
from repro.serving.plan import TransferConfig, TransferPlan

ARCHS = ["smollm-135m", "minicpm3-4b"]        # a GQA and an MLA cache
PAGE_BYTES = 2048
STEPS = 4
CALLER = "test.caller"


@dataclasses.dataclass
class Ev:
    name: str
    start: float
    end: float
    line: tuple
    stats: dict

    def inside(self, other: "Ev") -> bool:
        return other.start <= self.start and self.end <= other.end


def _host_events(logdir):
    path = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith((spans.PREFIX, CALLER)):
                    out.append(Ev(e.name, e.start_ns, e.end_ns,
                                  (plane.name, li), dict(e.stats)))
    return out


def _calibrated(cache):
    bits = np.concatenate(
        [np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16)).ravel()
         for x in jax.tree.leaves(cache) if x.dtype == jnp.bfloat16])
    return cbm.calibrate([bits], k=16)


@pytest.fixture(scope="module", params=ARCHS)
def model(request):
    """A tiny model whose prompts end two tokens short of the second page
    boundary: admission maps one full page a row, and the second decode step
    fills the next page, so the first flush falls inside the run."""
    cfg = get_config(request.param).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    cb0 = cbm.Codebook(fmt="bf16", exponents=tuple(range(112, 128)))
    tp = DisaggregatedEngine(cfg, params, cb0, resident="compressed",
                             page_bytes=PAGE_BYTES).resident_tokens_per_page()
    prompt = 2 * tp - 2
    rng = np.random.default_rng(3)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, prompt)), jnp.int32)}
    max_seq = -(-(prompt + 1 + STEPS) // tp) * tp
    _, st = M.prefill(params, batch, cfg, max_seq=max_seq)
    return dict(cfg=cfg, params=params, cb=_calibrated(st.cache), tp=tp,
                prompt=prompt, batch=batch, max_seq=max_seq,
                leaves=len(st.cache))


def _serve(model, resident):
    eng = DisaggregatedEngine(model["cfg"], model["params"], model["cb"],
                              resident=resident, page_bytes=PAGE_BYTES)
    pre = eng.prefill(model["batch"], max_seq=model["max_seq"])
    got = eng.transfer(pre.state)
    toks = jax.block_until_ready(eng.decode(pre.first_token, got, STEPS))
    pool = eng._pool
    lens = None if pool is None else (pool.lens,
                                      np.asarray(pool.state.cache_len))
    return np.asarray(toks), dataclasses.asdict(eng.stats), lens


@pytest.fixture(scope="module", params=["raw", "compressed"])
def served(request, model):
    """One batch served with the profiler off, then the same batch on a
    fresh engine with it on (the programs compiled by then)."""
    plain = _serve(model, request.param)[:2]
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            with jax.profiler.TraceAnnotation(CALLER):
                *traced, lens = _serve(model, request.param)
        events = _host_events(d)
    return dict(model, resident=request.param, traced=tuple(traced),
                plain=plain, lens=lens, events=events)


def _sz(events):
    return [e for e in events if e.name.startswith(spans.PREFIX)]


def _one(events, name):
    found = [e for e in events if e.name == name]
    assert len(found) == 1, (name, len(found))
    return found[0]


def test_profiler_changes_no_token_and_no_counter(served):
    toks, stats = served["traced"]
    toks0, stats0 = served["plain"]
    np.testing.assert_array_equal(toks, toks0)
    assert stats == stats0
    assert stats["resident_demotions"] == 0


def test_spans_sit_on_the_callers_thread(served):
    caller = _one(served["events"], CALLER)
    sz = _sz(served["events"])
    assert sz and all(e.line == caller.line for e in sz)
    assert not any(e.name.startswith("bench.") for e in served["events"])


def test_stage_spans_nest_in_the_caller(served):
    ev = served["events"]
    caller = _one(ev, CALLER)
    for stage in ("sz.prefill", "sz.transfer", "sz.decode"):
        e = _one(ev, stage)
        assert e.inside(caller) and e.stats["batch"] == 0
    assert _one(ev, "sz.prefill").end <= _one(ev, "sz.transfer").start
    assert _one(ev, "sz.transfer").end <= _one(ev, "sz.decode").start


def test_transfer_children_nest_in_the_transfer(served):
    ev = served["events"]
    transfer = _one(ev, "sz.transfer")
    enc = [e for e in ev if e.name == "sz.transfer.encode"]
    assert len(enc) == served["leaves"]
    assert sorted(e.stats["key"] for e in enc) == _leaf_keys(served)
    assert all(e.inside(transfer) for e in enc)
    dec = [e for e in ev if e.name == "sz.transfer.decode"]
    admit = [e for e in ev if e.name == "sz.resident.admit"]
    if served["resident"] == "raw":
        assert len(dec) == served["leaves"] and not admit
        assert all(e.inside(transfer) for e in dec)
    else:
        assert not dec and len(admit) == 1 and admit[0].inside(transfer)


def _leaf_keys(served):
    return ["ckv", "krope"] if served["cfg"].mla is not None else ["k", "v"]


def test_one_step_and_one_flush_span_per_step(served):
    ev = served["events"]
    decode = _one(ev, "sz.decode")
    steps = [e for e in ev if e.name == "sz.resident.step"]
    flushes = [e for e in ev if e.name == "sz.resident.flush"]
    if served["resident"] == "raw":
        assert not steps and not flushes
        return
    assert [e.stats["step"] for e in steps] == list(range(STEPS))
    assert len(flushes) == STEPS
    assert all(e.inside(decode) for e in steps + flushes)
    # a step's flush follows it and ends before the next step opens
    order = sorted(steps + flushes, key=lambda e: e.start)
    assert [e.name for e in order] == ["sz.resident.step",
                                       "sz.resident.flush"] * STEPS


def test_transfer_host_reads_hand_count(served):
    """Per splitzip leaf the session reads ``ok`` and the wire bytes; the
    engine reads the cache length once; the pool's admission reads the
    cache length and, per leaf with full pages, its page escape counts."""
    _, stats = served["traced"]
    n = served["leaves"]
    want = 2 * n + 1
    if served["resident"] == "compressed":
        want += 1 + n
    assert stats["transfer_calls"] == 1
    assert stats["transfer_host_reads"] == want
    ev = served["events"]
    transfer = _one(ev, "sz.transfer")
    reads = [e for e in ev if e.name == "sz.host_read" and e.inside(transfer)]
    assert len(reads) == want
    assert {e.stats["what"] for e in reads} >= {"ok", "wire_bytes",
                                                "cache_len"}


def test_resident_host_reads_hand_count(served):
    """The flush decides from the lengths and page map the host keeps, so a
    step that fills no tail page reads nothing; a flush that maps a filled
    tail page reads one ``ok`` flag for all leaves. The host's lengths end
    equal to the device's."""
    _, stats = served["traced"]
    if served["resident"] == "raw":
        assert stats["resident_steps"] == stats["resident_host_reads"] == 0
        assert stats["resident_page_flushes"] == 0
        return
    flushing = sum((served["prompt"] + 1 + i) % served["tp"] == 0
                   for i in range(STEPS))
    assert flushing == 1
    want = flushing
    assert stats["resident_steps"] == STEPS
    assert stats["resident_page_flushes"] == flushing
    assert stats["resident_host_reads"] == want
    host_lens, device_lens = served["lens"]
    np.testing.assert_array_equal(host_lens, device_lens)
    np.testing.assert_array_equal(host_lens, served["prompt"] + STEPS)
    ev = served["events"]
    decode = _one(ev, "sz.decode")
    reads = [e for e in ev if e.name == "sz.host_read" and e.inside(decode)]
    assert len(reads) == want
    assert {e.stats["what"] for e in reads} == {"ok"}
    flushes = [e for e in ev if e.name == "sz.resident.flush"]
    assert all(any(r.inside(f) for f in flushes) for r in reads)


def test_host_read_counts_device_values_only():
    class Stats:
        host_reads = 0

    s = Stats()
    assert spans.host_read(jnp.asarray(True), "ok", s, bool) is True
    assert spans.host_read(3.0, "wire_bytes", s, float) == 3.0
    np.testing.assert_array_equal(spans.host_read(jnp.arange(3), "x", s),
                                  np.arange(3))
    assert s.host_reads == 2
    assert spans.host_read(jnp.asarray(2), "x", None, int) == 2


def test_no_span_while_tracing():
    """The mesh executor decodes inside ``shard_map``: a decode traced under
    ``jit`` opens no span, the eager one opens one per leaf."""
    cache = {"k": jnp.ones((1, 1, 8, 2, 64), jnp.bfloat16),
             "v": jnp.full((1, 1, 8, 2, 64), 2.0, jnp.bfloat16)}
    cb = cbm.Codebook(fmt="bf16", exponents=tuple(range(112, 128)))
    plan = TransferPlan.build(cache, TransferConfig(codebook=cb))
    comp, raw = encode_leaves(plan, cache, scheduled=False)
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            with jax.profiler.TraceAnnotation(CALLER):
                out = jax.jit(lambda c, r: decode_leaves(c, r, cache))(
                    comp, raw)
                jax.block_until_ready(out)
        traced = _host_events(d)
    assert not [e for e in traced if e.name == "sz.transfer.decode"]
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            with jax.profiler.TraceAnnotation(CALLER):
                jax.block_until_ready(decode_leaves(comp, raw, cache))
        eager = _host_events(d)
    assert len([e for e in eager if e.name == "sz.transfer.decode"]) == 2
