#!/usr/bin/env python3
"""Measure a configuration's fixed numbers once, on the chip.

    python3 bench/calibrate.py --config <name> [--seed 0]

In one process: the engine step with no offline job (median of 64 steps
with every slot busy) and the offline step alone (median of 6 after the 3
set-up steps).  From them, the numbers the configuration file keeps fixed:
capacity_rps = max_batch / base step, and the multiplexer's quantum,
base step, offline step and latency budget (base + offline step), as
chip_smoke.py derived them.  Prints one JSON line.  A later benchmark PR
re-runs this on its parent to find the rates again.
"""
import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from bench import harness, spec
    harness.enable_compile_cache()
    dev = harness.require_chips(1)[0]
    conf = spec.load_json(spec.BENCH / "configs" / f"{args.config}.json")
    traffic = spec.load_json(spec.BENCH / "traffic" / "steady.json")
    t = time.perf_counter()
    online = harness.Online(conf["online"], traffic, args.seed)
    for _ in range(3):
        online(8)
    online.steps.clear()
    for _ in range(64):
        online(8)
    base = statistics.median(s["dt"] for s in online.steps)
    offline = harness.Offline(conf["offline"], args.seed)
    offline.first_steps()
    for _ in range(6):
        offline()
    off = statistics.median(s["dt"] for s in offline.steps)
    slots = conf["online"]["engine"]["num_slots"]
    print(json.dumps({
        "config": args.config, "device_kind": dev.device_kind,
        "base_step_s": base, "offline_step_s": off,
        "capacity_rps": slots / base, "latency_budget_s": base + off,
        "online_steps_s": [s["dt"] for s in online.steps],
        "offline_steps_s": [s["dt"] for s in offline.steps],
        "offline_state_bytes": offline.state_bytes,
        "peak_bytes_in_use": dev.memory_stats().get("peak_bytes_in_use"),
        "seconds": time.perf_counter() - t}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
