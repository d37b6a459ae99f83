#!/usr/bin/env python3
"""The chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs a TPU with as many chips as
the cell asks for; without one it exits non-zero and prints no result.
The cell (`BENCHMARK.json` -> `workloads`) names a configuration file
(`bench/configs/`) and a traffic file (`bench/traffic/`).  With --trace 0
the result carries the cell's end-to-end metrics, with --trace 1 its
per-layer metrics (`bench/metrics/<name>.py`) and the profiler's
breakdown.  The last line of standard output is the result as JSON; the
numbers the check compared, each with its limit, are the last lines of
standard error and the result's last key.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    from bench import harness, spec
    import repro
    if [Path(p).resolve() for p in repro.__path__] != [ROOT / "src" / "repro"]:
        sys.exit(f"repro imported from {list(repro.__path__)}, not this "
                 "checkout")
    cell = spec.cell(args.workload)
    harness.enable_compile_cache()
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START)
    for name, value, limit in out["checks"]:
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
