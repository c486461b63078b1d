"""Device idle inside the program's own spans, and the counter ratios.

``Reduced`` windows built by hand check the arithmetic of ``bench/spans.py``
(nested spans count once, a busy span reads 0, a trace without the spans
reads None); the five readers that use it read None from a run of a program
without spans or counters; a ``bench.transfer`` span recorded on a TPU v5e
with the program's spans (``bench/trim_trace.py``) gives ``transfer.idle_ms``.
"""

from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from bench import spans, trace
from bench.spec import load_module

MS = 1_000_000            # ns
ROOT = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "qwen3-32b-l8.long-input.sz-spans.transfer.textproto"
READERS = ["transfer.idle_ms", "transfer.host_reads",
           "resident.flush_idle_ms", "resident.step_idle_ms",
           "resident.host_reads"]


def _reduced(host, ops, chips=1):
    """A 10 ms window with the given host events and, on every chip, the
    given device operations (intervals in ms)."""
    ms = lambda evs: [(n, s * MS, e * MS) for n, s, e in evs]
    return trace.Reduced(
        window=(0, 10 * MS),
        ops={f"/device:TPU:{i}": ms(ops) for i in range(chips)},
        spans=[("bench.window", 0, 10 * MS)],
        host=[("bench.window", 0, 10 * MS)] + ms(host))


def _reader(name):
    return load_module(ROOT / "metrics" / f"{name}.py")


def test_nested_spans_count_once():
    # outer [0, 6], inner [1, 3] inside it, and a sibling [8, 9]; the device
    # runs [2, 4] and [8.5, 12] (clipped to the window by reduce, here 10)
    red = _reduced(host=[("sz.transfer", 0, 6), ("sz.transfer", 1, 3),
                         ("sz.transfer", 8, 9)],
                   ops=[("fusion.1", 2, 4), ("fusion.2", 8.5, 10)])
    # merged spans [0, 6] and [8, 9]: 7 ms, of which 2 + 0.5 busy
    assert spans.idle_s(red, "sz.transfer") == pytest.approx(4.5e-3)
    assert spans.count(red, "sz.transfer") == 3
    assert spans.idle_ms_per(red, "sz.transfer", "sz.transfer") == \
        pytest.approx(1.5)


def test_busy_span_reads_zero_and_chips_average():
    red = _reduced(host=[("sz.resident.step", 1, 2),
                         ("sz.resident.flush", 2, 5)],
                   ops=[("fusion.1", 0, 3)], chips=2)
    assert spans.idle_s(red, "sz.resident.step") == 0.0
    # the flush [2, 5] is busy for 1 ms on each chip
    assert spans.idle_ms_per(red, "sz.resident.flush",
                             "sz.resident.step") == pytest.approx(2.0)


def test_without_spans_reads_none():
    red = _reduced(host=[("PjitFunction(step)", 1, 2)],
                   ops=[("fusion.1", 0, 3)])
    assert spans.idle_s(red, "sz.transfer") is None
    assert spans.idle_ms_per(red, "sz.transfer", "sz.transfer") is None
    # spans, but no step to divide by
    red = _reduced(host=[("sz.resident.flush", 1, 2)], ops=[])
    assert spans.idle_ms_per(red, "sz.resident.flush",
                             "sz.resident.step") is None


@pytest.mark.parametrize("counters,want", [
    ({"transfer_host_reads": 10, "transfer_calls": 2}, 5.0),
    ({"transfer_host_reads": 0, "transfer_calls": 0}, None),
    ({"prefill_calls": 2}, None),
])
def test_counter_ratio(counters, want):
    assert spans.ratio(counters, "transfer_host_reads",
                       "transfer_calls") == want


@pytest.mark.parametrize("name", READERS)
def test_reader_without_the_programs_spans_reads_none(name):
    """A program without the spans and counters (an earlier commit) runs
    every reader to None, without raising."""
    ctx = SimpleNamespace(
        trace=_reduced(host=[("bench.transfer", 0, 5)],
                       ops=[("fusion.1", 0, 3)]),
        counters={"prefill_calls": 2, "decode_tokens": 64})
    assert _reader(name).read(ctx) is None


def test_readers_with_spans_and_counters():
    red = _reduced(host=[("sz.transfer", 0, 2),
                         ("sz.resident.step", 2, 3),
                         ("sz.resident.flush", 3, 5),
                         ("sz.resident.step", 5, 6),
                         ("sz.resident.flush", 6, 8)],
                   ops=[("fusion.1", 1, 2.5), ("fusion.2", 4, 5.5)])
    ctx = SimpleNamespace(trace=red, counters={
        "transfer_calls": 2, "transfer_host_reads": 10,
        "resident_steps": 256, "resident_host_reads": 544})
    got = {n: _reader(n).read(ctx) for n in READERS}
    assert got == {"transfer.idle_ms": pytest.approx(1.0),
                   "transfer.host_reads": 5.0,
                   # flush idle 1 + 2 ms, step idle 0.5 + 0.5 ms, 2 steps
                   "resident.flush_idle_ms": pytest.approx(1.5),
                   "resident.step_idle_ms": pytest.approx(0.5),
                   "resident.host_reads": 2.125}


def test_recorded_transfer_idle():
    """A ``bench.transfer`` span of the qwen3-32b long-input cell recorded
    on a TPU v5e with the program's spans: one ``sz.transfer`` inside it,
    with its per-leaf encode and decode spans; each of the five
    device-to-host reads in it (``np.asarray(jax.Array)``) sits in its own
    ``sz.host_read``; ``transfer.idle_ms`` reads 9.95 ms of the span's
    23.6 ms, nearly all the window's idle."""
    red = trace.reduce(ProfileData.from_text_proto(RECORDED.read_text()))
    names = [n for n, _, _ in red.host]
    assert names.count("sz.transfer") == 1
    assert names.count("sz.transfer.encode") == 2
    assert names.count("sz.transfer.decode") == 2
    (_, s, e), = [ev for ev in red.host if ev[0] == "sz.transfer"]
    reads = [(a, z) for n, a, z in red.host
             if n == "np.asarray(jax.Array)" and s <= a and z <= e]
    spans_ = [(a, z) for n, a, z in red.host if n == "sz.host_read"]
    assert len(reads) == len(spans_) == 5
    assert all(sum(a0 <= a and z <= z0 for a, z in reads) == 1
               for a0, z0 in spans_)
    idle = _reader("transfer.idle_ms").read(SimpleNamespace(trace=red))
    assert idle == pytest.approx(9.946523)
    window_idle = (red.window_s - red.busy_s) * 1e3
    assert 0.99 * window_idle < idle <= window_idle < (e - s) * 1e-6
