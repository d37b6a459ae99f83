"""Seeded arrival schedules: rate, burstiness, and what a seed changes."""
import numpy as np
import pytest

from bench import arrivals, inputs


def _gaps(sched):
    return np.diff(np.concatenate([[0.0], sched]))


@pytest.mark.parametrize("process,cv", [({"kind": "poisson"}, 1.0),
                                        ({"kind": "gamma", "cv": 2.0}, 2.0)])
def test_rate_and_cv(process, cv):
    sched = arrivals.schedule(process, 100.0, 30.0, inputs.rng(7, inputs.ARRIVALS))
    g = _gaps(sched)
    assert len(sched) == 3000
    assert sched[-1] == pytest.approx(30.0)
    assert all(b > a for a, b in zip(sched, sched[1:]))
    assert g.mean() == pytest.approx(0.01, rel=1e-9)
    # stratified quantiles: the CV is the law's, less its far tail
    assert g.std() / g.mean() == pytest.approx(cv, rel=0.1)


def test_same_seed_same_schedule_other_seed_same_gaps():
    p = {"kind": "gamma", "cv": 2.0}
    a = arrivals.schedule(p, 50.0, 10.0, inputs.rng(2**33 + 5, inputs.ARRIVALS))
    b = arrivals.schedule(p, 50.0, 10.0, inputs.rng(2**33 + 5, inputs.ARRIVALS))
    c = arrivals.schedule(p, 50.0, 10.0, inputs.rng(2**33 + 6, inputs.ARRIVALS))
    assert a == b
    assert a != c
    np.testing.assert_allclose(np.sort(_gaps(a)), np.sort(_gaps(c)), rtol=1e-6)


def test_unknown_process_is_an_error():
    with pytest.raises(ValueError):
        arrivals.gap_quantiles({"kind": "uniform"}, 10)


def test_seed_words_take_large_seeds():
    w = inputs.seed_words(2**40 + 3, 1)
    assert w.dtype == np.uint32 and w.shape == (2,)
    assert not np.array_equal(w, inputs.seed_words(2**40 + 4, 1))
