#!/usr/bin/env python3
"""Read the numbers the check compares, over many seeds, in one process.

    python3 bench/limits.py --workload <cell> --seeds 1,2,3 [--controls 3]
                            [--seconds 12]

Each seed is a whole run of the cell at its own size and load (a shorter
window, long enough to finish the mix's longest requests), with the
program's readings; the first --controls seeds also read the float8
control and the half-batch fault.  One JSON line per seed, then a summary:
the largest program reading of each number (the lower reading a limit is
set above) and the smallest control and fault readings (the upper).  This
is how the limits in the configuration files were chosen; the benchmark's
own runs never run the control.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

def readings(out: dict) -> dict:
    """The program's compared numbers and every candidate the check
    reads, with the control's and the half-batch fault's beside them."""
    r = out["readings"]
    row = {"program": {n: v for n, v, _ in out["checks"]}}
    row["program"].update({k: v for k, v in r.get("train_program", {}).items()
                           if k != "excluded_leaves"})
    if "served" in r:
        row["program"].update(r["served"]["program"])
        if "fp8" in r["served"]:
            row["fp8"] = dict(r["served"]["fp8"])
    for key, name in (("train_fp8", "fp8"), ("train_half_batch", "half_batch")):
        if key in r:
            row.setdefault(name, {}).update(
                {k: v for k, v in r[key].items() if k != "excluded_leaves"})
    row["excluded_leaves"] = r.get("train_program", {}).get("excluded_leaves")
    row["served_tokens"] = r.get("served", {}).get("tokens")
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    from bench import harness, spec
    harness.enable_compile_cache()
    cell = spec.cell(args.workload)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = harness.run(cell, seed, args.seconds, False,
                          t_start=time.perf_counter(),
                          readings=i < args.controls)
        row = {"seed": seed, "correct": out["result"]["correct"],
               **readings(out)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del out
        gc.collect()
    summary = {"lower": {}, "upper": {}}
    for n in sorted({k for r in rows for k in r["program"]}):
        summary["lower"][n] = max(r["program"][n] for r in rows
                                  if n in r["program"])
        for kind in ("fp8", "half_batch"):
            got = [r[kind][n] for r in rows if n in r.get(kind, {})]
            if got:
                summary["upper"][f"{kind}.{n}"] = min(got)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
