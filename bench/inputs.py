"""Everything a run feeds the program, made from `--seed`: weights, the
engine's refill requests and the offline job's token batches.

Weights are made on the device in one jitted call, in the type they are
served in.  The program supplies only the layout of its parameter tree
(`jax.eval_shape` of its initialiser: names, shapes and dtypes, no values);
each leaf's values come from the seed by the rule for its name below.
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two 32-bit words for a JAX key, from any non-negative seed (it may
    not fit 32 or even 63 bits) and a stream number."""
    return np.random.SeedSequence([int(seed), stream]).generate_state(
        2, np.uint32)


def key(seed: int, stream: int):
    import jax
    return jax.random.wrap_key_data(seed_words(seed, stream), impl="threefry2x32")


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


# streams: one per kind of input, so that adding one moves no other
ONLINE_WEIGHTS, OFFLINE_WEIGHTS, ARRIVALS, REQUESTS, BATCHES, SAMPLE = range(6)

_ONES = ("scale", "gn_scale")
_ZEROS = ("conv_b", "b_i")
_FORGET_BIAS = 3.0            # forget gates open at the start (b_f)
_SMALL = ("w_i", "w_f")       # gate projections: std 0.02
_EMBED_STD = 0.02             # x * sqrt(d_model) then has unit scale


def _leaf(name: str, shape, dtype, k):
    import jax
    import jax.numpy as jnp
    if name in _ONES:
        return jnp.ones(shape, dtype)
    if name in _ZEROS:
        return jnp.zeros(shape, dtype)
    if name == "b_f":
        return jnp.full(shape, _FORGET_BIAS, dtype)
    if len(shape) < 2:
        raise ValueError(f"no rule for the vector leaf {name!r} {shape}")
    if name == "embed":
        std = _EMBED_STD
    elif name in _SMALL:
        std = 0.02
    else:
        std = 1.0 / np.sqrt(shape[-2])        # fan-in
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def make_params(cfg, seed: int, stream: int):
    """The model's weights for `seed`, on the default device."""
    import jax
    from repro.models import init_params
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    spec = [(_leaf_name(p), s.shape, s.dtype) for p, s in leaves]

    def build(k):
        return treedef.unflatten([
            _leaf(name, shape, dtype, jax.random.fold_in(k, i))
            for i, (name, shape, dtype) in enumerate(spec)])

    return jax.jit(build)(key(seed, stream))


def refill_request(r: np.random.Generator, traffic: dict, vocab: int):
    """(prompt tokens, new tokens) of one engine refill request."""
    lo, hi = traffic["prompt_tokens"]
    p = int(r.integers(lo, hi + 1))
    lo, hi = traffic["new_tokens"]
    n = int(r.integers(lo, hi + 1))
    return r.integers(0, vocab, p).astype(np.int32), n


_ZIPF_A = 1.1


class TokenBatches:
    """Training batches: token ids with a Zipf unigram law, every row its
    own draw, batch `i` a pure function of (seed, i)."""

    def __init__(self, seed: int, vocab: int, batch: int, seq: int):
        self.seed, self.batch, self.seq = seed, batch, seq
        p = np.arange(1, vocab + 1, dtype=np.float64) ** -_ZIPF_A
        self.cdf = np.cumsum(p / p.sum())

    def __call__(self, i: int) -> np.ndarray:
        r = np.random.default_rng(
            np.random.SeedSequence([int(self.seed), BATCHES, i]))
        u = r.random((self.batch, self.seq))
        return np.minimum(np.searchsorted(self.cdf, u),
                          len(self.cdf) - 1).astype(np.int32)
