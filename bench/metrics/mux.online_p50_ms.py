"""Median latency over all requests of the window: arrival to the end of
the engine step that served it.  Where half the requests queue behind an
offline step, the median sits where the two groups meet and swings with
where arrivals fall against those steps, so it is read here, per layer,
beside the end-to-end p99."""
from bench import stats


def read(rec):
    lat = [r["latency"] * 1e3 for r in rec["requests"]]
    return stats.percentile(lat, 50) if lat else None
