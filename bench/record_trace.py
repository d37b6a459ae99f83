#!/usr/bin/env python3
"""Record the small trace that bench/tests/test_trace_reduce.py reads.

    python3 bench/record_trace.py --out bench/tests/data/small_trace.json

On the chip: a few jitted programs inside `bench.` spans under the JAX
profiler, reduced by trace_reduce.load to the device's operations and the
host spans, written as JSON.  Also prints the planes and lines the trace
holds, and the reduction's numbers, for a look by hand.
"""
import argparse
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from bench import harness, trace_reduce
    harness.require_chips(1)
    mm = jax.jit(lambda a, b: jnp.tanh(a @ b) @ b)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    mm(x, x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                mm(x, x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.refill"):
                sum(range(200000))          # host work, the device idle
    jax.profiler.stop_trace()
    path = str(next(Path(tmp).rglob("*.xplane.pb")))
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", plane.name, lines)
    ev = trace_reduce.load(path)
    red = trace_reduce.Reduced(ev)
    a, b = red.window()
    print("window_s", (b - a) * 1e-9, "busy_s", red.busy_ns(a, b) * 1e-9)
    print("engine steps", [(s * 1e-9, (e - s) * 1e-9, red.busy_ns(s, e) * 1e-9)
                           for s, e in red.spans_named("bench.engine_step")])
    print("top ops", red.top_ops(a, b))
    print("idle", red.idle_gaps(a, b))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(ev, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
