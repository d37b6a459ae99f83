"""Roofline share of the whole decode step: over traced engine steps, the
least time the chip needs for the step (bench/flops.py: the larger of
needed FLOPs over peak and needed bytes over HBM bandwidth, with the keys
and values of live positions only) over the device's busy time inside the
step's span."""
from bench import flops, trace_reduce


def read(rec):
    if rec["trace"] is None or rec["peak"] is None:
        return None
    pairs = trace_reduce.traced_steps(rec["trace"], "bench.engine_step",
                                      rec["online_steps"])
    need = sum(flops.least_time(*flops.decode_step(rec["online_arch"],
                                                   r["live"]), rec["peak"])
               for r, _, _ in pairs)
    return flops.roofline_share(need, sum(busy for *_, busy in pairs))
