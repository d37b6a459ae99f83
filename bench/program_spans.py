#!/usr/bin/env python3
"""One run of a cell with the program's own records read out.

    python3 bench/program_spans.py --workload danube-xlstm.steady \\
        --seed 7 --seconds 20 --trace 1 [--whole-window] [--look] \\
        --out chiprun_out/spans_7.json

The harness runs the cell as `bench/run.py` does.  This script wraps two
things from outside, and edits neither: `Multiplexer.run`, to reset the
engine's and the multiplexer's `PhaseProfiler`s where the window starts and
to log every engine span of every step, and the harness's trace reduction,
to keep the program's host spans (`engine.*`, `mux.*`) beside the
benchmark's `bench.*` ones.  It writes to `--out`, as JSON:

  spans       per engine span, ms a step over the window's online steps,
              and its longest call (`PhaseProfiler.longest`)
  long_steps  each online step of the window over LONG x the median step:
              its span times, the span that held it, the duty the PID left
              after it, and the idle quanta before the next offline step
  tracing     mean and median engine step inside and outside the trace
  traced      (a traced run) the device's idle time inside each program
              span per traced engine step, its sum over the four engine
              spans beside `engine.host_ms`, the long steps inside the
              trace with the device's busy time per span, and what the
              host's threads did during each of them
  look        (--look) the lines of a device plane, the stats an "XLA Ops"
              event carries, and whether a `jax.named_scope` reaches an op

`--whole-window` traces the whole loop instead of the harness's 8 s.  The
last line of standard output is "SPANS " and the main numbers as JSON.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

PREFIXES = ("engine.", "mux.")
ENGINE_SPANS = ("engine.admit", "engine.launch", "engine.readback",
                "engine.sample")
LONG = 5.0            # as bench/metrics/engine.long_steps.py


def host_spans(planes, prefixes=PREFIXES) -> list:
    """[(name, start_ns, end_ns)] of the host events whose names start with
    one of `prefixes`, sorted by start."""
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for plane in planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(prefixes)]
    return sorted(out, key=lambda s: s[1])


def within(spans, name: str, a: float, b: float) -> list:
    """(start, end) of the spans called `name` wholly inside [a, b]."""
    return [(s, e) for n, s, e in spans if n == name and s >= a and e <= b]


def idle_ns(red, intervals) -> float:
    """The device's idle time inside `intervals` (ns), by `red`'s device
    timeline on the host's clock."""
    return sum((e - s) - red.busy_ns(s, e) for s, e in intervals)


def split_steps(calls: list) -> list[dict]:
    """Per engine step {span: seconds}, from the engine's span calls in
    order; each step begins with `engine.admit`."""
    steps: list[dict] = []
    for name, dt in calls:
        if name == "engine.admit" or not steps:
            steps.append({})
        steps[-1][name] = steps[-1].get(name, 0.0) + dt
    return steps


def long_steps(steps, parts: list[dict], window_s: float) -> list[dict]:
    """The window's online steps over LONG x its median online step, each
    with its engine spans (ms), the rest of the step outside them, the span
    that held it, the duty after it, and the idle quanta (ms) before the
    next offline step.  `parts` are the engine's per-step spans, one for
    each online step of `steps` in order."""
    online = [k for k, s in enumerate(steps) if s.kind == "online"]
    if len(online) != len(parts):
        raise ValueError(f"{len(parts)} engine steps for {len(online)} "
                         "online steps")
    inside = [(k, p) for k, p in zip(online, parts)
              if steps[k].start < window_s]
    if not inside:
        return []
    limit = LONG * statistics.median(steps[k].end - steps[k].start
                                     for k, _ in inside)
    out = []
    for k, p in inside:
        s = steps[k]
        if s.end - s.start <= limit:
            continue
        d = {"step": k, "start_s": s.start, "ms": 1e3 * (s.end - s.start)}
        d.update({n: 1e3 * p.get(n, 0.0) for n in ENGINE_SPANS})
        d["rest"] = d["ms"] - sum(d[n] for n in ENGINE_SPANS)
        d["held_by"] = max(ENGINE_SPANS + ("rest",), key=d.get)
        d["duty_after"] = s.duty
        idle = 0.0
        for nxt in steps[k + 1:]:
            if nxt.kind == "offline":
                break
            if nxt.kind == "idle":
                idle += nxt.end - nxt.start
        d["idle_before_offline_ms"] = 1e3 * idle
        out.append(d)
    return out


def threads_during(planes, intervals, top: int = 8) -> list:
    """For each (start, end): the host events that overlap it without
    covering it, summed by "thread | event", longest first, in ms."""
    agg: list[dict] = [{} for _ in intervals]
    if not intervals:
        return []
    lo, hi = min(a for a, _ in intervals), max(b for _, b in intervals)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t <= lo or s >= hi:
                    continue
                for i, (a, b) in enumerate(intervals):
                    if s < b and t > a and not (s <= a and t >= b):
                        key = f"{line.name[:40]} | {e.name[:70]}"
                        agg[i][key] = agg[i].get(key, 0.0) + (
                            min(t, b) - max(s, a)) * 1e-6
    return [sorted(g.items(), key=lambda kv: -kv[1])[:top] for g in agg]


def traced(red, pd, spans, n_bench_steps: int) -> dict:
    """Idle time inside the program's spans, per traced engine step, and
    the traced long steps.  `pd` is the trace's `ProfileData`, whose
    `planes` can be walked once per access."""
    a, b = red.window()
    steps = red.spans_named("bench.engine_step", a, b)
    n = len(steps)
    host_ms = 1e-6 * idle_ns(red, steps) / n
    per = {}
    for name in ENGINE_SPANS + ("mux.control",):
        sp = within(spans, name, a, b)
        per[name] = {"idle_ms_per_step": 1e-6 * idle_ns(red, sp) / n,
                     "span_ms_per_step": 1e-6 * sum(e - s for s, e in sp) / n,
                     "calls": len(sp)}
    four = sum(per[x]["idle_ms_per_step"] for x in ENGINE_SPANS)
    durs = sorted(e - s for s, e in steps)
    limit = LONG * durs[len(durs) // 2]
    long = [(s, e) for s, e in steps if e - s > limit]
    modules = _modules(pd.planes, red)
    rows = []
    for s, e in long:
        parts = [(x, 1e-6 * (t - r), 1e-6 * red.busy_ns(r, t))
                 for x, r, t in spans if x in ENGINE_SPANS and r >= s and t <= e]
        mods = [(m, 1e-6 * (r - s), 1e-6 * (t - r))
                for m, r, t in modules if t > s and r < e]
        rows.append({"ms": 1e-6 * (e - s), "busy_ms": 1e-6 * red.busy_ns(s, e),
                     "spans": parts, "modules": mods})
    for row, thr in zip(rows, threads_during(pd.planes, long)):
        row["threads"] = thr
    return {"engine_steps": n, "bench_steps_traced": n_bench_steps,
            "engine.host_ms": host_ms, "spans": per,
            "four_engine_spans_idle_ms": four,
            "attributed_share": four / host_ms if host_ms else None,
            "idle_gaps": red.idle_gaps(a, b),
            "shift_ms": [x * 1e-6 for x in red.shifts],
            "device_idle_s": 1e-9 * ((b - a) - red.busy_ns(a, b)),
            "window_s": 1e-9 * (b - a), "long_steps": rows}


def _modules(planes, red) -> list:
    """[(module, start, end)] of the first device plane's "XLA Modules"
    line, moved onto the host's clock by its shift."""
    from bench import trace_reduce
    shift = red.shifts[0] if red.shifts else 0
    for plane in planes:
        if trace_reduce._DEVICE_PLANE.match(plane.name):
            return [(e.name.split("(")[0], e.start_ns + shift,
                     e.start_ns + e.duration_ns + shift)
                    for line in plane.lines if line.name == "XLA Modules"
                    for e in line.events]
    return []


def ops_look(planes) -> dict:
    """The lines of the first device plane, the stats its events carry per
    line, and the name of one KV-cache copy op."""
    from bench import trace_reduce
    for plane in planes:
        if not trace_reduce._DEVICE_PLANE.match(plane.name):
            continue
        stats, copy = {}, None
        for line in plane.lines:
            names = set()
            for k, e in enumerate(line.events):
                if k < 2000:
                    names.update(str(kv[0]) for kv in e.stats)
                if copy is None and line.name == "XLA Ops" and "copy" in e.name:
                    copy = e.name[:160]
            stats[line.name] = sorted(names)
        return {"plane": plane.name, "line_stats": stats, "a_copy_op": copy}
    return {}


def named_scope_look() -> dict:
    """Whether a `jax.named_scope` around a cache update reaches the name
    or the stats of the device's operations in a trace."""
    import tempfile

    import jax
    import jax.numpy as jnp

    def update(cache, new, pos):
        with jax.named_scope("kv_write"):
            return jax.lax.dynamic_update_slice(cache, new, (0, pos, 0))

    f = jax.jit(update, donate_argnums=(0,))
    c = jnp.zeros((8, 2048, 640), jnp.bfloat16)
    x = jnp.ones((8, 1, 640), jnp.bfloat16)
    c = jax.block_until_ready(f(c, x, 3))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for i in range(3):
        c = f(c, x, i)
    jax.block_until_ready(c)
    jax.profiler.stop_trace()
    pd = jax.profiler.ProfileData.from_file(
        str(next(Path(tmp).rglob("*.xplane.pb"))))
    events = [(line.name, e.name, [str(kv) for kv in e.stats])
              for plane in pd.planes if plane.name.startswith("/device:")
              for line in plane.lines for e in line.events]
    return {"events": [list(ev) for ev in events[:12]],
            "scope_in_name": any("kv_write" in n for _, n, _ in events),
            "scope_in_stats": any("kv_write" in s for *_, st in events
                                  for s in st)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="danube-xlstm.steady")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--whole-window", action="store_true")
    ap.add_argument("--look", action="store_true")
    ap.add_argument("--cpu-size", action="store_true",
                    help="the harness's small cell, on any device")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax

    from bench import harness, spec
    from repro.core.multiplexer import Multiplexer

    kept: dict = {"calls": []}
    mux_run, reduce_trace = Multiplexer.run, harness._reduce_trace

    def run(self, *a, **kw):
        engine = self.online_fn.engine
        engine.phases.reset()
        self.phases.reset()
        add = engine.phases.add

        def logged(name, dt):
            add(name, dt)
            kept["calls"].append((name, dt))

        engine.phases.add = logged
        kept.update(mux=self, engine=engine, online=self.online_fn)
        return mux_run(self, *a, **kw)

    def reduce_and_keep(tmp, result):
        red = reduce_trace(tmp, result)
        path = next(Path(tmp).rglob("*.xplane.pb"))
        kept["pd"] = jax.profiler.ProfileData.from_file(str(path))
        kept["red"] = red
        return red

    if args.cpu_size:
        from bench.tests.small import small_cell
        cell, tpu = small_cell(), False
    else:
        cell, tpu = spec.cell(args.workload), True
        harness.enable_compile_cache()
    lead = harness.TRACE_LEAD_S, harness.TRACE_S
    Multiplexer.run = run
    harness._reduce_trace = reduce_and_keep
    if args.whole_window:
        harness.TRACE_LEAD_S, harness.TRACE_S = 0.0, 1e9
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START, require_tpu=tpu)
    finally:
        Multiplexer.run, harness._reduce_trace = mux_run, reduce_trace
        harness.TRACE_LEAD_S, harness.TRACE_S = lead
    mux, engine, online = kept["mux"], kept["engine"], kept["online"]
    w = args.seconds
    parts = split_steps(kept["calls"])
    win = [p for p, s in zip(parts, [s for s in mux.steps
                                     if s.kind == "online"]) if s.start < w]
    res = {"workload": cell["name"], "seed": args.seed, "trace": args.trace,
           "whole_window": args.whole_window, "result": out["result"],
           "engine_steps": len(win), "evicted": mux.stats.evicted}
    res["spans"] = {n: {"ms_per_step": 1e3 * sum(p.get(n, 0.0) for p in win)
                        / len(win),
                        "longest_ms": 1e3 * engine.phases.longest.get(n, 0.0)}
                    for n in ENGINE_SPANS}
    res["engine.launch_ms"] = (res["spans"]["engine.admit"]["ms_per_step"]
                               + res["spans"]["engine.launch"]["ms_per_step"])
    res["engine.sample_ms"] = res["spans"]["engine.sample"]["ms_per_step"]
    res["mux.control_ms_per_iter"] = (1e3 * mux.phases.total("mux.control")
                                      / len(mux.steps))
    res["mux.throttled_pct"] = 100.0 * sum(
        max(0.0, min(s.end, w) - s.start) for s in mux.steps
        if s.kind == "idle") / w
    res["long_steps"] = long_steps(mux.steps, parts, w)
    res["longest_offline_ms"] = sorted(1e3 * (s.end - s.start)
                                       for s in mux.steps
                                       if s.kind == "offline")[-3:]
    tracing = {}
    for key, on in (("traced", True), ("untraced", False)):
        dts = [s["dt"] * 1e3 for s in online.steps if s["traced"] == on]
        if dts:
            tracing[key] = {"steps": len(dts), "mean_ms": statistics.mean(dts),
                            "median_ms": statistics.median(dts)}
    res["tracing"] = tracing
    if "red" in kept:
        spans = host_spans(kept["pd"].planes)
        res["traced"] = traced(kept["red"], kept["pd"], spans,
                               sum(s["traced"] for s in online.steps))
        if args.look:
            res["look"] = ops_look(kept["pd"].planes)
    if args.look:
        res.setdefault("look", {})["named_scope"] = named_scope_look()
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(res, indent=1, default=str))
    brief = {k: res[k] for k in ("seed", "trace", "engine.launch_ms",
                                 "engine.sample_ms", "mux.throttled_pct",
                                 "mux.control_ms_per_iter", "evicted")}
    brief["correct"] = out["result"]["correct"]
    brief["readback_ms"] = res["spans"]["engine.readback"]["ms_per_step"]
    brief["tracing"] = tracing
    brief["long"] = [(round(d["ms"], 1), d["held_by"], round(d["duty_after"], 3),
                      round(d["idle_before_offline_ms"], 1))
                     for d in res["long_steps"]]
    if "traced" in res:
        t = res["traced"]
        brief.update({k: t[k] for k in ("engine.host_ms",
                                        "four_engine_spans_idle_ms",
                                        "attributed_share")})
        brief["idle_ms"] = {n: round(v["idle_ms_per_step"], 4)
                            for n, v in t["spans"].items()}
        brief["traced_long"] = [(round(r["ms"], 1), round(r["busy_ms"], 2),
                                 max(r["spans"], key=lambda x: x[1])[0]
                                 if r["spans"] else None)
                                for r in t["long_steps"]]
    brief["metrics"] = {k: v["value"] for k, v in
                        out["result"]["metrics"].items()}
    print("SPANS " + json.dumps(brief), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
