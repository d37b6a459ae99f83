"""Continuous-batching engine: outputs must equal sequential whole-prompt
generation, under ragged admission and slot reuse."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import greedy_generate, init_params
from repro.models.model import forward
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


def test_dense_engine_has_no_moe_counts_and_decodes_plainly(setup):
    """A model with no MoE layer keeps no counters, and the engine's decode
    program is exactly a jitted decode forward with the cache donated."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, EngineConfig(num_slots=2, kv_capacity=64))
    assert eng.moe_counts() is None
    eng.reset_moe_counts()
    assert eng.moe_counts() is None

    def engine_decode(p, c, t, pos):      # named alike, so are the modules
        return forward(p, cfg, {"tokens": t}, mode="decode", cache=c, pos=pos)

    args = (eng.params, eng.cache, jnp.asarray(eng.slot_tok),
            jnp.asarray(eng.slot_pos))
    plain = jax.jit(engine_decode, donate_argnums=(1,))
    assert eng._decode.lower(*args).as_text() == plain.lower(*args).as_text()


# -- a fine-grained MoE decoder: granite-shaped (an MoE FFN in every layer,
# top-2 of 8 experts, no shared expert), in float32.  The capacity factor
# lets the sequential reference's prefill (a whole prompt through the
# grouped dispatch) drop no slot, as the engine's decode never does.

@pytest.fixture(scope="module")
def moe_setup():
    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    assert cfg.pattern == (("attn", "moe"),) and cfg.num_experts > cfg.top_k > 1
    cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                              moe_capacity_factor=float(cfg.num_experts))
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _forced(params):
    """The same weights with every router zeroed: all experts tie, and every
    token goes to experts 0..k-1."""
    blocks = tuple(dict(b, ffn=dict(b["ffn"],
                                    router=jnp.zeros_like(b["ffn"]["router"])))
                   for b in params["blocks"])
    return dict(params, blocks=blocks)


def _ragged(cfg, seed, n):
    rng = np.random.default_rng(seed)
    return [ServeRequest(i, rng.integers(0, cfg.vocab_size,
                                         int(rng.integers(2, 9))).astype(np.int32),
                         max_new_tokens=int(rng.integers(2, 6)))
            for i in range(n)]


def _drive(cfg, params, reqs, slots):
    """Serves `reqs` on `slots` slots; returns the engine and, per decode
    step, each row's token rows as its cache holds them after the step
    (row b: positions 0..pos[b]) with the step's logits."""
    eng = ServingEngine(cfg, params, EngineConfig(num_slots=slots,
                                                  kv_capacity=32))
    decode, hist, seen = eng._decode, [[] for _ in range(slots)], []

    def observed(p, c, t, pos):
        logits, c = decode(p, c, t, pos)
        t, pos = np.asarray(t)[:, 0], np.asarray(pos)
        for b in range(slots):
            hist[b] = hist[b][:pos[b]] + [int(t[b])]
        seen.append(([list(h) for h in hist], np.asarray(logits)))
        return logits, c

    eng._decode = observed
    for r in reqs:
        eng.submit(r)
    eng.drain()
    return eng, seen


def _reference(cfg):
    """The reference's step as a function of the rows of `_drive`: its
    logits at each row's last position, and per layer the experts it routes
    that position to: ((B, V), (L, B, k))."""
    from bench import reference
    a = dataclasses.asdict(cfg)
    a["pattern"] = [list(p) for p in cfg.pattern]

    @jax.jit
    def step(params, toks, last):
        at = jnp.arange(toks.shape[0])
        with jax.default_matmul_precision("highest"):
            logits = reference.logits(params, a, toks, "f32")
            # the reference's own layers, to read each one's routing
            ar = reference.Arith("f32")
            x = jnp.take(params["embed"], toks, 0) * np.sqrt(a["d_model"])
            routes = []
            for layer in range(a["num_layers"]):
                bp = jax.tree.map(lambda w: w[layer], params["blocks"][0])
                x = x + reference.attention(
                    bp["attn"], reference.rmsnorm(x, bp["norm1"]["scale"]),
                    a, ar)
                h = reference.rmsnorm(x, bp["norm2"]["scale"])
                routes.append(jax.lax.top_k(ar.mm(h, bp["ffn"]["router"]),
                                            a["top_k"])[1][at, last])
                x = x + reference.moe(bp["ffn"], h, a, ar)
        return logits[at, last], jnp.stack(routes)

    def run(params, rows):
        toks = np.zeros((len(rows), 32), np.int32)     # the engine's capacity
        for b, r in enumerate(rows):
            toks[b, :len(r)] = r
        got = step(params, jnp.asarray(toks),
                   jnp.asarray([len(r) - 1 for r in rows]))
        return tuple(np.asarray(g) for g in got)

    return run


def test_moe_engine_matches_sequential_under_ragged_admission_and_reuse(
        moe_setup):
    cfg, params = moe_setup
    reqs = _ragged(cfg, 11, 7)
    eng = ServingEngine(cfg, params, EngineConfig(num_slots=3, kv_capacity=64))
    for r in reqs:
        eng.submit(r)
    eng.drain()
    assert len(eng.finished) == len(reqs)
    for r in reqs:
        want = ref_generate(cfg, params, r.prompt, r.max_new_tokens)
        assert r.output == want, f"request {r.request_id}"


def test_moe_engine_step_logits_equal_reference(moe_setup):
    """Every decode step's logits, in every row, prompt positions included,
    against the benchmark's float32 reference over the row's whole
    sequence.  Both sides compute in float32 and differ by summation order
    alone (the program attends over the masked cache one position at a
    time and sums experts over capacity buffers): 1.2e-6 of the largest
    logit at most here.  1e-4 of it leaves room for other orders; serving
    one of a token's two experts moves logits by 0.7 of it or more."""
    cfg, params = moe_setup
    _, seen = _drive(cfg, params, _ragged(cfg, 12, 6), 3)
    reference = _reference(cfg)
    for i, (rows, logits) in enumerate(seen):
        want, _ = reference(params, rows)
        got = logits[:, :cfg.vocab_size]
        scale = float(np.abs(want).max())
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-4 * scale, err_msg=f"step {i}")


@pytest.mark.parametrize("routing", ["seeded", "forced"])
def test_moe_counts_equal_an_independent_count(moe_setup, routing):
    """`moe_counts()` per layer: the distinct experts each step's rows were
    routed to, counted from the reference's own routing; no slot dropped;
    every step counted.  Forced: every token on the same k experts."""
    cfg, params = moe_setup
    if routing == "forced":
        params = _forced(params)
    eng, seen = _drive(cfg, params, _ragged(cfg, 13, 6), 3)
    reference = _reference(cfg)
    hit = np.zeros(cfg.num_layers, np.int64)
    for rows, _ in seen:
        _, routes = reference(params, rows)
        hit += [len(np.unique(r)) for r in routes]
    got = eng.moe_counts()
    n = len(seen)
    assert n == eng.steps > 0
    np.testing.assert_array_equal(got["experts_hit"], hit)
    np.testing.assert_array_equal(got["dropped"], np.zeros(cfg.num_layers))
    np.testing.assert_array_equal(got["steps"], np.full(cfg.num_layers, n))
    if routing == "forced":
        np.testing.assert_array_equal(hit, np.full(cfg.num_layers,
                                                   cfg.top_k * n))
    eng.reset_moe_counts()
    assert all(not v.any() for v in eng.moe_counts().values())


def test_grouped_dispatch_counts_the_slots_it_drops(moe_setup):
    """Beyond one token a row the capacity factor bounds each expert's
    buffer: with every token forced onto experts 0..k-1 and capacity 4 of a
    row's 12 tokens, each of those experts drops 8 of its slots a row."""
    import repro.models.moe as M
    cfg, params = moe_setup
    cfg = dataclasses.replace(cfg, moe_capacity_factor=1.25)
    ffn = jax.tree.map(lambda w: w[0], _forced(params)["blocks"][0]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.d_model))
    y, aux = M.moe_ffn(ffn, x, cfg)
    y2, aux2, counts = M.moe_ffn(ffn, x, cfg, counts=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    assert float(aux) == float(aux2)
    cap = 4              # round(ceil(12 * 2 / 8) * 1.25)
    assert counts.tolist() == [cfg.top_k, 2 * cfg.top_k * (12 - cap)]
