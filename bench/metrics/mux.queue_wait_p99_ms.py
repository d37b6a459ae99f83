"""99th percentile over requests of the wait before the engine step that
served them began (latency less that step's duration)."""
from bench import stats


def read(rec):
    waits = [r["queue_wait"] * 1e3 for r in rec["requests"]]
    return stats.percentile(waits, 99) if waits else None
