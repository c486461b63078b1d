"""The reduction from a profiler trace to busy, idle, kernel time and gaps.

A synthetic trace checks each number against a hand count; the traces
recorded on a TPU v5e (one ``bench.transfer`` span of each cell, trimmed by
``bench/trim_trace.py``) check that the reduction finds the device ops, the
harness's spans and the codec kernels in a real one.
"""

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import trace

MS = 1_000_000            # ns
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000000 }
    events { metadata_id: 2 offset_ps: 7000000000 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 7500000000 duration_ps: 100000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 10000000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "encode_fused" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 1000000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 6000000000 }
    events { metadata_id: 3 offset_ps: 6000000000 duration_ps: 4000000000 }
    events { metadata_id: 4 offset_ps: 5500000000 duration_ps: 1000000000 } }
  lines { id: 2 name: "other" timestamp_ns: 1000000000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 10000000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.prefill" } }
  event_metadata { key: 3 value { id: 3 name: "bench.transfer" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(step)" } } }
"""
DATA = Path(__file__).resolve().parent / "data"


def test_synthetic_trace_by_hand():
    red = trace.reduce(ProfileData.from_text_proto(SYNTHETIC))
    assert red.chips == 1
    assert red.window_s == pytest.approx(10e-3)
    # [0, 5] and [7, 8] ms; the nested 7.5 ms op adds nothing
    assert red.busy_s == pytest.approx(6e-3)
    assert red.busy_in(["prefill"]) == pytest.approx(5e-3)
    assert red.busy_in(["transfer"]) == pytest.approx(1e-3)
    assert red.busy_in(["prefill", "transfer"]) == pytest.approx(6e-3)
    t, n = red.op_time(trace.matcher("encode_fused"))
    assert (t, n) == (pytest.approx(1.1e-3), 2)
    assert red.top_ops(1) == [["prefill:fusion.1", pytest.approx(5e-3)]]
    # the 10 ms module line is not an op; the nested op's 0.1 ms is taken
    # from its parent's own time
    assert dict(red.top_ops(5))["transfer:encode_fused"] == \
        pytest.approx(1.0e-3)
    # gaps [5, 7] and [8, 10] ms; at 6 ms the host was in the transfer span
    # and, on the spans' thread, in a jitted call (the other line is not
    # the harness's thread)
    gaps = red.idle_gaps(5)
    assert [g[1] for g in gaps] == [pytest.approx(2e-3)] * 2
    assert gaps[0][0] == "transfer/PjitFunction(step)"
    assert gaps[1][0] == "transfer"


def test_window_is_required():
    with pytest.raises(ValueError):
        trace.reduce(ProfileData.from_text_proto(
            SYNTHETIC.replace('"bench.window"', '"bench.other"')))


def test_op_names_and_self_time():
    assert trace.op_name("%fusion.114 = bf16[4] fusion(%a), kind=k") == \
        "fusion.114"
    assert trace.matcher("encode_fused")("encode_fused.3")
    assert not trace.matcher("encode_fused")("copy.2")
    # a loop of 10 around ops of 3 and 4: the loop's own time is 3
    own = trace.self_times([("while.1", 0, 10), ("a", 1, 4), ("b", 5, 9)])
    assert own == {"while.1": 3, "a": 3, "b": 4}


def test_merge_and_overlap():
    merged = trace.merge([(5, 6), (0, 2), (1, 3), (3, 4)])
    assert merged == [(0, 4), (5, 6)]
    assert trace.overlap(merged, 1, 5.5) == pytest.approx(3.5)


@pytest.mark.parametrize("path", sorted(DATA.glob("*.textproto")),
                         ids=lambda p: p.stem)
def test_recorded_transfer_span(path):
    red = trace.reduce(ProfileData.from_text_proto(path.read_text()))
    assert red.chips == 1
    assert 0 < red.busy_s <= red.window_s
    assert red.busy_in(["transfer"]) == pytest.approx(red.busy_s)
    t, n = red.op_time(trace.matcher("encode_fused"))
    assert t > 0 and n > 0
    assert red.idle_gaps(3)[0][0].startswith("transfer")
    assert all(name.startswith("transfer:") for name, _ in red.top_ops(5))
