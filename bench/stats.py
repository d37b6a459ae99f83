"""The yardstick's arithmetic: percentiles, the multiplexer's clock rebuilt
from its step log, per-request queue waits, and the accounting check.

Kept apart from the program's `MuxStats` so that no change to the program
moves it; the harness checks the program's own p50/p99 against it.
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) with linear interpolation between order
    statistics, as numpy's default method computes it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def replay(events: list[tuple]) -> list[tuple]:
    """The multiplexer's clock, rebuilt from its loop's iterations in order:
    ("online", dt, batch), ("offline", dt) or ("idle", quantum).  Returns
    (kind, start, end, *rest) for each, with the clock at 0 at the start."""
    t, out = 0.0, []
    for ev in events:
        start = t
        t += ev[1]
        out.append((ev[0], start, t) + tuple(ev[2:]))
    return out


def serve_records(arrivals: list[float], latencies: list[float],
                  timeline: list[tuple]) -> tuple[list[dict], list[str]]:
    """Pair each served request with the online step that served it.

    The multiplexer serves FIFO, so the k-th latency is the k-th arrival's
    and the online steps take them in batches of their logged size.  Returns
    the records (arrival, done, latency, step start/end, queue wait) and the
    list of accounting faults: a request served twice or never logged, out
    of order, before it arrived, or at a time the step log does not give.
    """
    faults: list[str] = []
    arr = sorted(arrivals)
    steps = [ev for ev in timeline if ev[0] == "online"]
    n_batched = sum(ev[3] for ev in steps)
    if n_batched != len(latencies):
        faults.append(f"{len(latencies)} latencies for {n_batched} batched "
                      "requests")
    if len(latencies) > len(arr):
        faults.append(f"{len(latencies)} served of {len(arr)} arrivals")
    recs, k = [], 0
    for _, start, end, batch in steps:
        for _ in range(batch):
            if k >= min(len(latencies), len(arr)):
                break
            a, lat = arr[k], latencies[k]
            done = a + lat
            tol = 1e-9 * max(1.0, abs(end))
            if abs(done - end) > tol:
                faults.append(f"request {k}: done at {done:.9f}, its step "
                              f"ends at {end:.9f}")
            if a > start + tol:
                faults.append(f"request {k}: arrived at {a:.9f}, after its "
                              f"step began at {start:.9f}")
            recs.append({"arrival": a, "done": done, "latency": lat,
                         "step_start": start, "step_end": end,
                         "queue_wait": start - a})
            k += 1
    for prev, cur in zip(recs, recs[1:]):
        if cur["done"] < prev["done"]:
            faults.append("requests completed out of arrival order")
            break
    return recs, faults
