"""Online steps that began inside the window and lasted more than
LONG times the window's median online step, on the multiplexer's clock.
The PID reads such a step as a slowdown of LONG or more and holds the
offline duty down for seconds after it."""
from bench import stats

LONG = 5.0


def read(rec):
    if rec["offline_arch"] is None:
        return None
    dts = [end - start for kind, start, end, *_ in rec["timeline"]
           if kind == "online" and start < rec["window_s"]]
    if not dts:
        return None
    limit = LONG * stats.percentile(dts, 50)
    return sum(dt > limit for dt in dts)
