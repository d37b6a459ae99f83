"""Cells, configurations and traffic mixes, found by name.

A cell of `BENCHMARK.json` names a configuration and a traffic mix; each is
a JSON file of its own (`bench/configs/<config>.json`,
`bench/traffic/<traffic>.json`).  Nothing here knows any cell by name.
"""
from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    """The cell's entry, with its configuration and traffic files loaded."""
    spec = benchmark()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_file = {c["name"]: c for c in spec["configs"]}[w["config"]]["file"]
    out = dict(w, config_data=load_json(ROOT / cfg_file),
               traffic_data=load_json(BENCH / "traffic" / f"{w['traffic']}.json"))
    out["end_to_end"] = [m for m in spec["end_to_end"]
                         if name in m.get("workloads", [name])]
    out["per_layer"] = [m for m in spec["per_layer"]
                        if name in m.get("workloads", [name])]
    return out


def model_config(arch: dict):
    """The program's ModelConfig from a configuration file's `arch` block.
    Only the model's own numbers are set; implementation choices keep the
    program's defaults."""
    import jax.numpy as jnp
    from repro.models.model import ModelConfig
    kw = dict(arch)
    kw["pattern"] = tuple(tuple(p) for p in kw["pattern"])
    kw["dtype"] = jnp.dtype(kw["dtype"])
    return ModelConfig(**kw)
