"""Mean over traced engine steps of the step's span less the device's busy
time inside it: admission, inputs to the device, logits to the host and
the per-slot Python, as far as the device sits idle for them."""
from bench import trace_reduce


def read(rec):
    if rec["trace"] is None:
        return None
    pairs = trace_reduce.traced_steps(rec["trace"], "bench.engine_step",
                                      rec["online_steps"])
    if not pairs:
        return None
    return 1e3 * sum(span - busy for _, span, busy in pairs) / len(pairs)
