"""On-device multiplexer: SLO protection, quota, graceful exit, eviction."""
import signal

import numpy as np
import pytest

from repro.core.errors import ErrorKind, GracefulExit
from repro.core.multiplexer import Multiplexer, MuxConfig
from repro.core.protection import QuotaExceeded


def make_mux(slo=1.2, couple=0.35, base=0.010, off=0.020, **kw):
    mux_holder = {}

    def online_fn(bs):
        duty = mux_holder["m"].throttle.duty
        return base * (1.0 + couple * duty)

    m = Multiplexer(online_fn, lambda: off, base, off, MuxConfig(slo_slowdown=slo, **kw))
    mux_holder["m"] = m
    return m


def arrivals(qps, n, seed=0):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / qps, n)).tolist()


def test_slo_respected_under_load():
    m = make_mux(slo=1.2, couple=0.5)
    s = m.run(arrivals(40, 600), 20.0)
    assert s.served == 600
    # average online step slowdown stays near the SLO bound
    assert s.p50_ms <= 1.35 * s.base_ms * 2   # incl. queueing slack
    assert s.offline_steps > 0
    assert 0.0 < s.offline_duty < 1.0


def test_more_load_less_offline():
    lo = make_mux().run(arrivals(10, 100), 12.0)
    hi = make_mux().run(arrivals(90, 1080), 12.0)
    assert lo.oversold > hi.oversold


def test_quota_rejects_oversized_offline():
    with pytest.raises(QuotaExceeded):
        Multiplexer(lambda b: 0.01, lambda: 0.02, 0.01, 0.02,
                    MuxConfig(device_bytes=1000, quota_frac=0.4),
                    offline_state_bytes=500)


def test_offline_only_runs_when_idle_budget_allows():
    # zero arrivals: offline free-runs at the PID's initial duty
    m = make_mux()
    s = m.run([], 5.0, max_offline_steps=10)
    assert s.offline_steps == 10
    assert s.served == 0


def test_eviction_on_persistent_violation():
    # online step always 5x base: PID can't save it -> SysMonitor-style evict
    m = Multiplexer(lambda b: 0.05, lambda: 0.02, 0.01, 0.02,
                    MuxConfig(slo_slowdown=1.2, evict_after_violations=10))
    s = m.run(arrivals(50, 300), 10.0)
    assert s.evicted
    # eviction takes the offline job away, not the service
    assert s.served == 300 and all(r.done is not None for r in m.requests)
    assert m.throttle.frozen
    assert s.slo_violations == 300            # still counted after eviction
    # every request is late (0.05 s > the 0.048 s budget): the tenth one
    # served reached the limit, and no offline step starts after its step
    tenth = sorted(m.requests, key=lambda r: (r.step, r.request_id))[9]
    assert all(st.kind != "offline" for st in m.steps[tenth.step + 1:])


def _frozen_run(freeze):
    """A run whose offline job is frozen mid-run by `freeze` ("evict": the
    SLO guard, "graceful": SIGTERM through GracefulExit, None: no freeze),
    with the calls the loop makes logged in order as the benchmark logs
    them.  Returns the multiplexer, its stats, the arrivals, the log and
    the number of steps when the throttle froze."""
    # a budget a short queue wait already misses: late requests pile up
    # while the measured slowdown stays under the SLO and offline runs on
    cfg = MuxConfig(latency_budget_s=0.015,
                    evict_after_violations=30 if freeze == "evict" else 10**9)
    log, at = [], []

    def online_fn(bs):
        if (freeze == "graceful" and not at and m.steps
                and m.steps[-1].end >= 1.0):
            signal.raise_signal(signal.SIGTERM)
        dt = 0.010 * (1.0 + 0.35 * m.throttle.duty)
        log.append(("online", dt, bs))
        return dt

    def offline_fn():
        log.append(("offline", 0.020))
        return 0.020

    m = Multiplexer(online_fn, offline_fn, 0.010, 0.020, cfg)
    m.graceful = GracefulExit()
    gate, freeze_fn = m.throttle.should_launch, m.throttle.freeze

    def observed_gate(quantum=1.0):
        ok = gate(quantum)
        log.append(("gate", quantum))
        return ok

    def observed_freeze():
        at.append(len(m.steps))
        freeze_fn()

    m.throttle.should_launch, m.throttle.freeze = observed_gate, observed_freeze
    arr = arrivals(40, 160, seed=1)
    s = m.run(arr, 5.0)
    return m, s, arr, log, at[0] if at else None


@pytest.mark.parametrize("freeze", ["evict", "graceful"])
def test_a_frozen_offline_job_leaves_serving_and_the_clock_whole(freeze):
    m, s, arr, log, n = _frozen_run(freeze)
    assert n is not None and m.throttle.frozen
    assert s.evicted == (freeze == "evict")
    assert (m.graceful.triggered == ErrorKind.SIGTERM) == (freeze == "graceful")
    assert s.served == len(arr) and all(r.done is not None for r in m.requests)
    # offline ran before the freeze and would have run on without it
    assert any(st.kind == "offline" for st in m.steps[:n])
    assert all(st.kind != "offline" for st in m.steps[n:])
    ctrl = _frozen_run(None)[0]
    assert any(st.kind == "offline" for st in ctrl.steps[n:])
    # the clock rebuilt from the logged calls: each gate call that no
    # offline step follows is an idle quantum
    t, clock = 0.0, []
    for i, ev in enumerate(log):
        if ev[0] == "gate":
            if i + 1 < len(log) and log[i + 1][0] == "offline":
                continue
            ev = ("idle", ev[1], 0)
        start, t = t, t + ev[1]
        clock.append((ev[0], start, t, ev[2] if ev[0] == "online" else 0))
    assert clock == [(st.kind, st.start, st.end, st.batch) for st in m.steps]
    assert m.steps[0].start == 0.0
    assert all(b.start == a.end for a, b in zip(m.steps, m.steps[1:]))
    assert m.steps[-2].end < 5.0 <= m.steps[-1].end


def test_offline_duty_is_offline_time_over_the_clock():
    m = make_mux()
    s = m.run(arrivals(20, 100), 6.0)
    off = sum(st.end - st.start for st in m.steps if st.kind == "offline")
    assert s.offline_steps > 0
    assert s.offline_duty == pytest.approx(off / m.steps[-1].end, rel=1e-12)


def test_equal_arrival_times_are_served_in_id_order():
    arr = [0.0, 0.0, 0.0, 0.05, 0.05, 0.05, 0.05, 0.05, 0.05, 0.05, 0.05,
           0.05, 0.3, 0.3]
    m = make_mux()
    s = m.run(arr, 1.0)
    assert s.served == len(arr)
    served = sorted(m.requests, key=lambda r: (r.done, r.step))
    assert [r.request_id for r in served] == list(range(len(arr)))
    # nine requests at 0.05 leave one for the next step (max_batch 8)
    assert len({r.step for r in m.requests if r.arrival == 0.05}) == 2


def test_mux_config_has_no_telemetry_interval():
    with pytest.raises(TypeError):
        MuxConfig(telemetry_interval_s=0.1)


@pytest.mark.parametrize("qps,horizon,max_off", [(40, 8.0, None),
                                                 (0, 2.0, 5),
                                                 (150, 4.0, None)])
def test_steps_tile_the_clock(qps, horizon, max_off):
    m = make_mux()
    arr = arrivals(qps, int(qps * horizon * 0.8)) if qps else []
    s = m.run(arr, horizon, max_offline_steps=max_off)
    assert m.steps and m.steps[0].start == 0.0
    for a, b in zip(m.steps, m.steps[1:]):
        assert b.start == a.end
    assert m.steps[-2].end < horizon <= m.steps[-1].end
    kinds = {st.kind for st in m.steps}
    assert kinds <= {"online", "offline", "idle"}
    assert sum(st.kind == "offline" for st in m.steps) == s.offline_steps
    online = [st for st in m.steps if st.kind == "online"]
    assert sum(st.batch for st in online) == s.served
    assert all(0.0 < st.batch <= m.cfg.max_batch for st in online)
    assert all(0.0 <= st.duty <= 0.95 for st in m.steps)


def test_requests_once_in_arrival_order_done_at_their_step_end():
    arr = arrivals(60, 400, seed=3)
    m = make_mux()
    s = m.run(arr, 8.0)
    assert [r.request_id for r in m.requests] == list(range(len(arr)))
    assert [r.arrival for r in m.requests] == sorted(arr)
    served = [r for r in m.requests if r.done is not None]
    assert len(served) == s.served
    for r in served:
        st = m.steps[r.step]
        assert st.kind == "online"
        assert r.done == st.end and r.arrival <= st.start
    assert [r.latency for r in served] == m._latencies
    # each online step served exactly its batch
    per_step = {}
    for r in served:
        per_step[r.step] = per_step.get(r.step, 0) + 1
    assert per_step == {k: st.batch for k, st in enumerate(m.steps)
                        if st.kind == "online"}


def test_loop_control_is_one_span_per_part():
    m = make_mux()
    m.run(arrivals(40, 200), 5.0)
    n_online = sum(st.kind == "online" for st in m.steps)
    # one span per iteration, and one more after each online step (the PID
    # update and violation count follow the step)
    assert m.phases.calls["mux.control"] == len(m.steps) + n_online
    assert set(m.phases.calls) == {"mux.control"}
