"""The yardstick's arithmetic against hand cases."""
import numpy as np
import pytest

from bench import harness, stats


@pytest.mark.parametrize("q", [0, 25, 50, 90, 99, 100])
def test_percentile_matches_numpy(q):
    x = np.random.default_rng(0).exponential(size=1001).tolist()
    assert stats.percentile(x, q) == pytest.approx(np.percentile(x, q), rel=1e-12)


def test_percentile_hand_cases():
    assert stats.percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert stats.percentile([1.0, 2.0], 50) == 1.5
    assert stats.percentile(list(range(101)), 99) == 99.0
    assert stats.percentile([5.0], 99) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_loop_iterations_and_replay():
    log = [("gate", 0.01), ("online", 0.02, 2), ("gate", 0.01),
           ("offline", 0.2), ("gate", 0.01), ("online", 0.02, 1)]
    it = harness.loop_iterations(log)
    assert it == [("idle", 0.01), ("online", 0.02, 2), ("offline", 0.2),
                  ("idle", 0.01), ("online", 0.02, 1)]
    tl = stats.replay(it)
    assert [round(e[2], 6) for e in tl] == [0.01, 0.03, 0.23, 0.24, 0.26]


def _timeline():
    return stats.replay([("idle", 0.01), ("online", 0.02, 2),
                         ("offline", 0.2), ("online", 0.02, 1)])


def test_serve_records_queue_wait():
    # arrivals at 0.005 and 0.008 wait for the step at 0.01; the third
    # (0.1) waits out the offline step, which ends at 0.23
    arr = [0.005, 0.008, 0.1]
    lat = [0.03 - 0.005, 0.03 - 0.008, 0.25 - 0.1]
    recs, faults = stats.serve_records(arr, lat, _timeline())
    assert faults == []
    assert [round(r["queue_wait"], 9) for r in recs] == [0.005, 0.002, 0.13]
    # sorted waits 0.002, 0.005, 0.13: the 99th lies 0.98 of the way
    # from the second to the third
    assert stats.percentile([r["queue_wait"] for r in recs], 99) \
        == pytest.approx(0.005 + 0.98 * 0.125, rel=1e-9)


@pytest.mark.parametrize("arr,lat,what", [
    ([0.005, 0.008, 0.1], [0.025, 0.022], "latencies for"),       # one lost
    ([0.005, 0.008, 0.24], [0.025, 0.022, 0.01], "after its step"),  # early
    ([0.005, 0.008, 0.1], [0.025, 0.022, 0.14], "its step ends"),  # wrong time
])
def test_serve_records_faults(arr, lat, what):
    _, faults = stats.serve_records(arr, lat, _timeline())
    assert any(what in f for f in faults), faults


def test_online_p50_reader():
    read = harness._metric_readers(["mux.online_p50_ms"])["mux.online_p50_ms"]
    recs = [{"latency": x} for x in (0.03, 0.01, 0.02)]
    assert read({"requests": recs}) == pytest.approx(20.0)
    assert read({"requests": []}) is None
