"""The program's own records of a run against the yardstick's rebuild, at a
CPU size: the multiplexer's `steps` are the clock the harness rebuilds from
the loop's calls, its `requests` give the latencies and queue waits of
`stats.serve_records`, and the engine's spans count its steps."""
import time

import pytest

from bench import harness, stats
from bench.tests.small import SMALL_SEED, small_cell

ENGINE_SPANS = ("engine.admit", "engine.launch", "engine.readback",
                "engine.sample")


@pytest.fixture(scope="module")
def run_records():
    """One small run, with the program's multiplexer and engine and the
    harness's timeline and request records kept aside."""
    from repro.core.multiplexer import Multiplexer
    mp = pytest.MonkeyPatch()
    kept: dict = {}
    mux_run, replay, serve = Multiplexer.run, stats.replay, stats.serve_records

    def keep_run(self, *a, **kw):
        kept["mux"] = self
        kept["engine"] = self.online_fn.engine
        kept["engine_calls"] = dict(self.online_fn.engine.phases.calls)
        return mux_run(self, *a, **kw)

    def keep_replay(events):
        kept["timeline"] = replay(events)
        return kept["timeline"]

    def keep_serve(*a):
        kept["serve"] = serve(*a)
        return kept["serve"]

    mp.setattr(Multiplexer, "run", keep_run)
    mp.setattr(stats, "replay", keep_replay)
    mp.setattr(stats, "serve_records", keep_serve)
    try:
        out = harness.run(small_cell(), SMALL_SEED, 2.0, False,
                          t_start=time.perf_counter(), require_tpu=False)
    finally:
        mp.undo()
    assert out["result"]["correct"], out["checks"]
    return kept


def test_program_steps_are_the_rebuilt_clock(run_records):
    mux, timeline = run_records["mux"], run_records["timeline"]
    mine = [(s.kind, s.start, s.end) + ((s.batch,) if s.kind == "online"
                                         else ())
            for s in mux.steps]
    assert mine == timeline
    assert {s.kind for s in mux.steps} == {"online", "offline", "idle"}


def test_program_requests_give_the_yardstick_records(run_records):
    mux = run_records["mux"]
    recs, faults = run_records["serve"]
    assert faults == []
    served = [r for r in mux.requests if r.done is not None]
    assert len(served) == len(recs) > 0
    for r, y in zip(served, recs):
        step = mux.steps[r.step]
        assert (r.arrival, r.done, r.latency) == (y["arrival"], y["done"],
                                                  y["latency"])
        assert (step.start, step.end) == (y["step_start"], y["step_end"])
        assert step.start - r.arrival == y["queue_wait"]


def test_engine_spans_count_the_window_steps(run_records):
    mux, engine = run_records["mux"], run_records["engine"]
    before = run_records["engine_calls"]
    n = sum(s.kind == "online" for s in mux.steps)
    # the harness keeps every slot busy, so each online step runs the decode
    for name in ENGINE_SPANS:
        assert engine.phases.calls[name] - before.get(name, 0) == n
    assert mux.phases.calls["mux.control"] == len(mux.steps) + n


def test_clock_readers_read_the_program_steps(run_records):
    mux, timeline = run_records["mux"], run_records["timeline"]
    readers = harness._metric_readers(["engine.long_steps",
                                       "mux.throttled_pct"])
    mine = [(s.kind, s.start, s.end) for s in mux.steps]
    for name, read in readers.items():
        got = [read({"window_s": 2.0, "timeline": tl, "offline_arch": {}})
               for tl in (timeline, mine)]
        assert got[0] is not None and got[0] == got[1], name
    assert 0.0 < readers["mux.throttled_pct"](
        {"window_s": 2.0, "timeline": mine, "offline_arch": {}}) < 100.0
