"""The readers of the multiplexer's clock (`engine.long_steps`,
`mux.throttled_pct`) and bench/program_spans.py's arithmetic, on hand-made
logs and traces, and the script itself at a CPU size."""
import json
import time
from types import SimpleNamespace

import pytest

from bench import harness, program_spans, trace_reduce
from repro.core.multiplexer import Step

READERS = harness._metric_readers(["engine.long_steps", "mux.throttled_pct"])
ARCH = {"d_model": 64}


def _timeline():
    # online steps of 20 ms and one of 110 ms, idle quanta, an offline
    # step; the last idle quantum crosses the window's end at 1.0 s
    return [("online", 0.0, 0.02, 3), ("idle", 0.02, 0.12),
            ("offline", 0.12, 0.42), ("online", 0.42, 0.53, 2),
            ("online", 0.53, 0.55, 1), ("online", 0.55, 0.57, 1),
            ("idle", 0.57, 0.97), ("idle", 0.97, 1.07),
            ("online", 1.07, 1.5, 4)]


def test_throttled_pct_counts_idle_quanta_clipped_at_the_window():
    read = READERS["mux.throttled_pct"]
    rec = {"window_s": 1.0, "timeline": _timeline(), "offline_arch": ARCH}
    assert read(rec) == pytest.approx(100.0 * (0.10 + 0.40 + 0.03))
    assert read(dict(rec, offline_arch=None)) is None


def test_long_steps_count_steps_over_five_medians_inside_the_window():
    read = READERS["engine.long_steps"]
    rec = {"window_s": 1.0, "timeline": _timeline(), "offline_arch": ARCH}
    # median of 20, 110, 20, 20 ms is 20 ms: the 110 ms step alone is long;
    # the 430 ms step began after the window
    assert read(rec) == 1
    assert read(dict(rec, window_s=2.0)) == 2
    assert read(dict(rec, timeline=[("idle", 0.0, 1.0)])) is None
    assert read(dict(rec, offline_arch=None)) is None


def test_idle_inside_spans_on_a_hand_made_trace():
    events = {"device": {"/device:TPU:0": [("decode", 1_000, 19_000),
                                           ("copy", 19_500, 20_000)]},
              "spans": [("bench.window", 0, 30_000),
                        ("bench.engine_step", 0, 22_000)]}
    red = trace_reduce.Reduced(events, align=False)
    spans = [("engine.admit", 0, 200), ("engine.launch", 200, 1_500),
             ("engine.readback", 1_500, 21_000), ("engine.sample", 21_000, 21_800),
             ("mux.control", 22_500, 23_000)]
    got = {n: program_spans.idle_ns(
        red, program_spans.within(spans, n, *red.window()))
        for n, *_ in spans}
    # launch: 200-1000 idle; readback: 19000-19500 and 20000-21000 idle
    assert got == {"engine.admit": 200, "engine.launch": 800,
                   "engine.readback": 1_500, "engine.sample": 800,
                   "mux.control": 500}
    assert program_spans.within(spans, "engine.sample", 0, 21_500) == []
    # the step's idle time is all inside the four engine spans but 200 ns
    step_idle = program_spans.idle_ns(red, [(0, 22_000)])
    assert step_idle - sum(got[n] for n in program_spans.ENGINE_SPANS) == 200


def test_split_steps_and_long_steps():
    calls = []
    for dt_read in (0.018, 0.018, 0.130, 0.018):
        calls += [("engine.admit", 1e-5), ("engine.launch", 1e-3),
                  ("engine.readback", dt_read), ("engine.sample", 1e-4)]
    parts = program_spans.split_steps(calls)
    assert len(parts) == 4 and parts[2]["engine.readback"] == 0.130
    steps = [Step("online", 0.0, 0.02, 8, 0.5), Step("offline", 0.02, 0.25),
             Step("online", 0.25, 0.27, 8, 0.5), Step("online", 0.27, 0.40, 8, 0.0),
             Step("idle", 0.40, 0.41), Step("online", 0.41, 0.43, 1, 0.0),
             Step("idle", 0.43, 0.44), Step("offline", 0.44, 0.67)]
    (long,) = program_spans.long_steps(steps, parts, 1.0)
    assert long["step"] == 3 and long["held_by"] == "engine.readback"
    assert long["ms"] == pytest.approx(130.0)
    assert long["duty_after"] == 0.0
    assert long["idle_before_offline_ms"] == pytest.approx(20.0)
    assert program_spans.long_steps(steps, parts, 0.2) == []
    with pytest.raises(ValueError):
        program_spans.long_steps(steps, parts[:3], 1.0)


def test_threads_during_sums_partial_overlaps():
    ev = lambda n, s, d: SimpleNamespace(name=n, start_ns=s, duration_ns=d)  # noqa: E731
    plane = SimpleNamespace(name="/host:CPU", lines=[
        SimpleNamespace(name="python", events=[ev("outer", 0, 1_000_000)]),
        SimpleNamespace(name="runtime", events=[ev("ReadSyncFlag", 100, 300),
                                                ev("D2H", 350, 200),
                                                ev("ReadSyncFlag", 700, 100)])])
    (got,) = program_spans.threads_during([plane], [(200, 500)])
    # "outer" covers the interval and is left out
    assert got == [("runtime | ReadSyncFlag", pytest.approx(2e-4)),
                   ("runtime | D2H", pytest.approx(1.5e-4))]


def test_the_script_at_a_cpu_size(tmp_path, capsys):
    from repro.core.multiplexer import Multiplexer
    run, reduce_trace = Multiplexer.run, harness._reduce_trace
    out = tmp_path / "spans.json"
    t0 = time.perf_counter()
    assert program_spans.main(["--cpu-size", "--seed", "5", "--seconds", "2",
                               "--trace", "1", "--out", str(out)]) == 0
    assert time.perf_counter() - t0 < 300
    # the wrapped functions are the originals again
    assert (Multiplexer.run, harness._reduce_trace) == (run, reduce_trace)
    res = json.loads(out.read_text())
    assert res["result"]["correct"]
    assert res["engine_steps"] > 0
    assert set(res["spans"]) == set(program_spans.ENGINE_SPANS)
    assert res["traced"]["spans"]["engine.readback"]["calls"] > 0
    assert {"engine.long_steps", "mux.throttled_pct"} <= set(
        res["result"]["metrics"])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last.startswith("SPANS ")
