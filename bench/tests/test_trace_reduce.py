"""The trace reduction on a hand-made trace and on a small one recorded on
a TPU v5e by bench/record_trace.py."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).parent / "data" / "small_trace.json"


def _hand():
    return {"device": {"/device:TPU:0": [("a", 0, 10), ("b", 5, 20),
                                         ("c", 30, 38)]},
            "spans": [("bench.window", 0, 50), ("bench.engine_step", 0, 25),
                      ("bench.refill", 26, 45)]}


def test_busy_and_spans():
    red = trace_reduce.Reduced(_hand(), align=False)
    assert red.window() == (0, 50)
    assert red.busy_ns(0, 50) == 28          # [0, 20] and [30, 38]
    assert red.busy_ns(0, 25) == 20
    assert red.busy_ns(15, 35) == 10
    assert red.spans_named("bench.engine_step", 0, 50) == [(0, 25)]


def test_top_ops_and_idle_gaps():
    red = trace_reduce.Reduced(_hand(), align=False)
    top = red.top_ops(0, 50)
    assert [n for n, _ in top] == ["b", "a", "c"]
    assert [s for _, s in top] == pytest.approx([15e-9, 10e-9, 8e-9])
    # idle [20, 30] is mid-engine-step; [38, 50] is mid-refill
    gaps = red.idle_gaps(0, 50)
    assert [n for n, _ in gaps] == ["bench.refill", "bench.engine_step"]
    assert [s for _, s in gaps] == pytest.approx([12e-9, 10e-9])


def test_traced_steps_pair_in_order():
    red = trace_reduce.Reduced(_hand(), align=False)
    steps = [{"traced": False}, {"traced": True, "i": 1}]
    pairs = trace_reduce.traced_steps(red, "bench.engine_step", steps)
    assert len(pairs) == 1
    assert pairs[0][0]["i"] == 1
    assert pairs[0][1:] == pytest.approx((25e-9, 20e-9))


def test_align_moves_device_ops_into_their_spans():
    # each step's program ran 1.2 ms before its span on the trace's clock
    ms = 1_000_000
    spans = [("bench.window", 0, 30 * ms)]
    ops = []
    for k in range(3):
        t = 10 * k * ms
        spans.append(("bench.engine_step", t, t + 2 * ms))
        ops.append(("op", t + 0.3 * ms - 1.2 * ms, t + 1.5 * ms - 1.2 * ms))
    red = trace_reduce.Reduced({"device": {"/device:TPU:0": ops},
                                "spans": spans})
    # ops span [-0.9, +0.3] ms around their span's start: any shift in
    # [0.9, 1.7] ms puts each inside its 2 ms span; the middle is taken
    assert red.shifts[0] == pytest.approx(1.3 * ms, abs=0.01 * ms)
    for a, b in red.spans_named("bench.engine_step"):
        assert red.busy_ns(a, b) == pytest.approx(1.2 * ms)


@pytest.mark.skipif(not DATA.exists(), reason="no recorded trace")
def test_recorded_trace():
    ev = json.loads(DATA.read_text())
    red = trace_reduce.Reduced(ev)
    a, b = red.window()
    steps = red.spans_named("bench.engine_step", a, b)
    assert len(steps) == 3
    busy = red.busy_ns(a, b)
    # three matmul programs inside their spans; host-only refills between
    assert 0 < busy < b - a
    inside = sum(red.busy_ns(s, e) for s, e in steps)
    assert inside > 0.9 * busy
    gaps = dict(red.idle_gaps(a, b))
    assert max(gaps, key=gaps.get) == "bench.refill"
    assert red.top_ops(a, b)


def test_alignment_counts_work_near_span_edges():
    red = trace_reduce.Reduced(_hand(), align=False)
    got = red.alignment(0, 50, margin_ns=5)
    # busy 28; inside the engine step [0, 25]: 20; within 5 of its end: [30, 30]
    assert got["busy_s"] == pytest.approx(28e-9)
    assert got["inside_steps"] == pytest.approx(20 / 28)
    assert got["near_edges_s"] == pytest.approx(0.0)
    assert red.alignment(0, 50, margin_ns=8)["near_edges_s"] == pytest.approx(3e-9)
