"""Open-loop arrival schedules from a traffic file and a seed.

Every seed gets the same multiset of inter-arrival gaps in another order:
the gaps are the distribution's quantiles at (i + 0.5) / n, scaled so that
the n arrivals span the window exactly, and the seed shuffles them.  So two
seeds offer the same amount of work with the same burstiness, and differ
only in where the bursts fall.
"""
from __future__ import annotations

import numpy as np

# quantiles of a Gamma law are read from one large sorted sample drawn
# with this fixed seed (numpy has no Gamma inverse CDF); the seed of a run
# never changes them
_GAMMA_SAMPLE_SEED = 20240131
_GAMMA_SAMPLE_SIZE = 400_000


def gap_quantiles(process: dict, n: int) -> np.ndarray:
    """n stratified inter-arrival gaps of unit mean for `process`
    ({"kind": "poisson"} or {"kind": "gamma", "cv": c})."""
    u = (np.arange(n) + 0.5) / n
    kind = process["kind"]
    if kind == "poisson":
        g = -np.log1p(-u)
    elif kind == "gamma":
        shape = 1.0 / float(process["cv"]) ** 2
        rng = np.random.default_rng(_GAMMA_SAMPLE_SEED)
        sample = np.sort(rng.gamma(shape, 1.0 / shape, _GAMMA_SAMPLE_SIZE))
        g = np.quantile(sample, u)
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    # distinct arrival times: the multiplexer's queue cannot order two
    # requests that arrive at the same instant
    g = np.maximum(g, 1e-6 * g.mean())
    return g / g.mean()


def schedule(process: dict, rate: float, seconds: float,
             rng: np.random.Generator) -> list[float]:
    """Arrival times in (0, seconds]: round(rate * seconds) arrivals whose
    gaps are the stratified quantiles in the order the seed draws."""
    n = max(1, int(round(rate * seconds)))
    gaps = rng.permutation(gap_quantiles(process, n))
    t = np.cumsum(gaps)
    return (t * (seconds / t[-1])).tolist()
