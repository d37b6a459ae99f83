"""Continuous-batching engine: outputs must equal sequential whole-prompt
generation, under ragged admission and slot reuse."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import greedy_generate, init_params
from repro.serving.engine import EngineConfig, ServeRequest, ServingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("h2o-danube-1.8b", smoke=True),
                              dtype=jnp.float32, window=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def ref_generate(cfg, params, prompt, n_new):
    batch = {"tokens": jnp.asarray(prompt, jnp.int32)[None]}
    toks = greedy_generate(cfg, params, batch, steps=max(n_new - 1, 0))
    return [int(t) for t in np.asarray(toks[0])][:n_new]


def test_single_request_matches_sequential(setup):
    cfg, params = setup
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    eng = ServingEngine(cfg, params, EngineConfig(num_slots=4, kv_capacity=64))
    eng.submit(ServeRequest(0, prompt, max_new_tokens=6))
    eng.drain()
    assert len(eng.finished) == 1
    want = ref_generate(cfg, params, prompt, 6)
    assert eng.finished[0].output == want


def test_ragged_batch_matches_sequential(setup):
    """Multiple requests with different prompt lengths admitted together —
    per-slot positions keep every sequence independent."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    reqs = [ServeRequest(i, rng.integers(0, cfg.vocab_size,
                                         int(rng.integers(2, 9))).astype(np.int32),
                         max_new_tokens=int(rng.integers(2, 6)))
            for i in range(6)]
    eng = ServingEngine(cfg, params, EngineConfig(num_slots=3, kv_capacity=64))
    for r in reqs:
        eng.submit(r)
    eng.drain()
    assert len(eng.finished) == 6
    for r in reqs:
        want = ref_generate(cfg, params, r.prompt, r.max_new_tokens)
        assert r.output == want, f"request {r.request_id}"


def test_slot_reuse_and_fixed_shape(setup):
    cfg, params = setup
    rng = np.random.default_rng(2)
    eng = ServingEngine(cfg, params, EngineConfig(num_slots=2, kv_capacity=64))
    for i in range(5):
        eng.submit(ServeRequest(i, rng.integers(0, cfg.vocab_size, 3)
                                .astype(np.int32), max_new_tokens=3))
    eng.drain()
    assert len(eng.finished) == 5
    # one compiled program: decode was jitted once; steps bounded
    assert eng.steps < 5 * (3 + 3) + 10


SPANS = ("engine.admit", "engine.launch", "engine.readback", "engine.sample")


def test_each_step_records_its_four_spans(setup):
    cfg, params = setup
    rng = np.random.default_rng(3)
    eng = ServingEngine(cfg, params, EngineConfig(num_slots=2, kv_capacity=64))
    for i in range(3):
        eng.submit(ServeRequest(i, rng.integers(0, cfg.vocab_size, 4)
                                .astype(np.int32), max_new_tokens=3))
    n = 0
    while eng.waiting or eng.active_slots:
        assert eng.step() > 0
        n += 1
    assert n == eng.steps
    assert {k: eng.phases.calls[k] for k in SPANS} == {k: n for k in SPANS}
    for k in SPANS:
        assert 0.0 <= eng.phases.longest[k] <= eng.phases.totals[k]
    # a step with nothing to run admits and stops there
    assert eng.step() == 0
    assert eng.phases.calls["engine.admit"] == n + 1
    assert eng.phases.calls["engine.launch"] == n


def test_step_takes_no_clock_and_requests_carry_no_done_time(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, EngineConfig(num_slots=1, kv_capacity=64))
    with pytest.raises(TypeError):
        eng.step(now=1.0)
    req = ServeRequest(0, np.zeros(2, np.int32), max_new_tokens=1)
    assert "done_at" not in {f.name for f in dataclasses.fields(req)}


def test_decode_program_has_a_stable_name(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, EngineConfig(num_slots=1, kv_capacity=64))
    lowered = eng._decode.lower(eng.params, eng.cache,
                                jnp.asarray(eng.slot_tok),
                                jnp.asarray(eng.slot_pos))
    assert "jit_engine_decode" in lowered.as_text()
