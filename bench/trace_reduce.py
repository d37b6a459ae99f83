"""Profiler trace -> device busy time, per-span device time, breakdown.

`load(path)` reads a `.xplane.pb` with `jax.profiler.ProfileData` into two
lists on the trace's one clock: the device's operations (the "XLA Ops"
line of each `/device:TPU:n` plane) and the benchmark's own host spans
(`TraceAnnotation`s whose names start with `bench.`).  `Reduced` answers
the questions the metrics ask of them.  The rest of this module works on
those lists alone, so it is tested on a small recorded trace kept as JSON.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
BETWEEN_SPANS = "host:outside-bench-spans"


def load(path: str) -> dict:
    """{"device": {plane: [(name, start_ns, end_ns)]}, "spans":
    [(name, start_ns, end_ns)]}, sorted by start."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    device: dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            device[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"device": device, "spans": sorted(spans, key=lambda s: s[1])}


def _plane(ops):
    s, e = _union(ops)
    cum = [0]
    for a, b in zip(s, e):
        cum.append(cum[-1] + (b - a))
    return (s, e, cum, ops)


def _covered(plane, x: float) -> float:
    s, e, cum, _ = plane
    i = bisect.bisect_right(s, x) - 1
    if i < 0:
        return 0.0
    return cum[i] + min(x, e[i]) - s[i]


def _inside(plane, spans, shift: float) -> float:
    """Device time inside `spans` with the device's clock moved by shift."""
    return sum(_covered(plane, b - shift) - _covered(plane, a - shift)
               for a, b in spans)


def _best_shift(ops, spans) -> float:
    plane = _plane(ops)

    def best(grid):
        vals = [(_inside(plane, spans, d), d) for d in grid]
        top = max(v for v, _ in vals)
        ds = [d for v, d in vals if v >= top * (1 - 1e-9)]
        return (min(ds) + max(ds)) / 2

    d = best(range(-MAX_SHIFT_NS, MAX_SHIFT_NS + 1, _COARSE_NS))
    return best([d + k * _FINE_NS for k in
                 range(-_COARSE_NS // _FINE_NS, _COARSE_NS // _FINE_NS + 1)])


def _union(intervals) -> tuple[list, list]:
    starts, ends = [], []
    for _, a, b in sorted(intervals, key=lambda e: e[1]):
        if starts and a <= ends[-1]:
            ends[-1] = max(ends[-1], b)
        else:
            starts.append(a)
            ends.append(b)
    return starts, ends


# Each device plane's clock is offset from the host's by about a
# millisecond in v5e traces (a program's operations start before the host
# span that launched it).  The offset is found as the shift of the device's
# timeline that puts the most device time inside the step spans: searched
# over +-MAX_SHIFT_NS, coarse then fine, the middle of the best range taken.
STEP_SPANS = ("bench.engine_step", "bench.train_step")
MAX_SHIFT_NS = 5_000_000
_COARSE_NS, _FINE_NS = 20_000, 500


class Reduced:
    """Device busy time over any interval, for one trace.  Times in ns on
    the host's clock of the trace, the device's operations shifted onto it
    (see `align`); busy time is the union of the device's operations
    (averaged over the device planes)."""

    def __init__(self, events: dict, align: bool = True):
        self.spans = events["spans"]
        self.planes = []
        self.shifts = []
        steps = [(a, b) for n, a, b in self.spans if n in STEP_SPANS]
        for ops in events["device"].values():
            shift = _best_shift(ops, steps) if align and steps else 0
            ops = [(n, a + shift, b + shift) for n, a, b in ops]
            self.shifts.append(shift)
            self.planes.append(_plane(ops))

    def busy_ns(self, a: float, b: float) -> float:
        if not self.planes or b <= a:
            return 0.0
        return sum(_covered(p, b) - _covered(p, a)
                   for p in self.planes) / len(self.planes)

    def alignment(self, a: float, b: float, margin_ns: float = 1e6) -> dict:
        """How well the shifted device timeline fits the step spans inside
        [a, b]: the device's busy seconds, the share of them inside step
        spans, and the device seconds within `margin_ns` outside a step
        span's edges (the work a shift of that much would move across
        them)."""
        steps = [(s, e) for n, s, e in self.spans
                 if n in STEP_SPANS and s >= a and e <= b]
        inside = sum(self.busy_ns(s, e) for s, e in steps)
        edges = sum(self.busy_ns(max(a, s - margin_ns), s)
                    + self.busy_ns(e, min(b, e + margin_ns)) for s, e in steps)
        busy = self.busy_ns(a, b)
        return {"shift_ms": [x * 1e-6 for x in self.shifts],
                "busy_s": busy * 1e-9,
                "inside_steps": inside / busy if busy else 0.0,
                "near_edges_s": edges * 1e-9}

    def spans_named(self, name: str, a: float | None = None,
                    b: float | None = None) -> list[tuple[float, float]]:
        """(start, end) of the spans called `name`, optionally only those
        wholly inside [a, b]."""
        return [(s, e) for n, s, e in self.spans if n == name
                and (a is None or s >= a) and (b is None or e <= b)]

    def window(self, name: str = "bench.window") -> tuple[float, float] | None:
        w = self.spans_named(name)
        return w[0] if w else None

    def top_ops(self, a: float, b: float, n: int = 10) -> list:
        """[[op name, seconds]] of the device operations inside [a, b] that
        took the most time in all, averaged over the device planes."""
        tot: dict[str, float] = defaultdict(float)
        for *_, ops in self.planes:
            for name, s, e in ops:
                lo, hi = max(s, a), min(e, b)
                if hi > lo:
                    tot[name] += hi - lo
        k = max(len(self.planes), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / k * 1e-9] for name, ns in top]

    def idle_gaps(self, a: float, b: float, n: int = 10) -> list:
        """[[host span, seconds]]: the device's idle time inside [a, b],
        by the innermost benchmark span the host was in at each gap's
        middle, largest first (first device plane)."""
        if not self.planes:
            return []
        s, e, *_ = self.planes[0]
        gaps, t = [], a
        for x, y in zip(s, e):
            if y <= a:
                continue
            if x >= b:
                break
            if x > t:
                gaps.append((t, x))
            t = max(t, y)
        if t < b:
            gaps.append((t, b))
        spans = [sp for sp in self.spans if sp[0] != "bench.window"]
        starts = [sp[1] for sp in spans]
        tot: dict[str, float] = defaultdict(float)
        for g0, g1 in gaps:
            mid = (g0 + g1) / 2
            name = BETWEEN_SPANS
            # the innermost span is the latest-starting one that covers mid;
            # the benchmark's spans nest at most a few deep
            for nm, ss, ee in reversed(
                    spans[max(0, bisect.bisect_right(starts, mid) - 8):
                          bisect.bisect_right(starts, mid)]):
                if ee >= mid:
                    name = nm
                    break
            tot[name] += g1 - g0
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9] for name, ns in top]


def traced_steps(red: Reduced, span: str, steps: list[dict]) -> list:
    """[(step record, span seconds, device busy seconds in the span)] for
    the steps that ran while the profiler traced: the spans called `span`
    inside the traced window, paired in order with the step records marked
    `traced`."""
    win = red.window()
    if win is None:
        return []
    spans = red.spans_named(span, *win)
    recs = [s for s in steps if s.get("traced")]
    return [(r, (b - a) * 1e-9, red.busy_ns(a, b) * 1e-9)
            for r, (a, b) in zip(recs, spans)]
