"""PhaseProfiler as a span recorder: the longest call per phase, reset,
and each phase as a host span in a JAX profiler trace."""
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.obs import PhaseProfiler


def test_longest_call_per_phase():
    p = PhaseProfiler()
    for dt in (0.2, 0.7, 0.1):
        p.add("engine.readback", dt)
    p.add("engine.sample", 0.05)
    assert p.longest == {"engine.readback": 0.7, "engine.sample": 0.05}
    assert p.calls["engine.readback"] == 3


def test_reset_scopes_totals_to_what_follows():
    p = PhaseProfiler()
    p.add("mux.control", 5.0)
    p.reset()
    assert (p.totals, p.calls, p.longest) == ({}, {}, {})
    p.add("mux.control", 0.25)
    assert (p.total("mux.control"), p.calls["mux.control"],
            p.longest["mux.control"]) == (0.25, 1, 0.25)


def test_phases_are_host_spans_of_a_trace(tmp_path):
    p = PhaseProfiler()
    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with p.phase("engine.launch"):
            y = f(x)
        with p.phase("engine.readback"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    path = next(Path(tmp_path).rglob("*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    names = [e.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    assert names.count("engine.launch") == 2
    assert names.count("engine.readback") == 2
    assert p.calls == {"engine.launch": 2, "engine.readback": 2}
