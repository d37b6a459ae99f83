"""Whether a run's timed path produced the right answers.

Each number below is compared where the configuration file names it under
`limits`, with that limit (PERF.md gives the readings each limit was set
from, and why the others are read but not compared):

  mux_accounting_faults   every arrival served once, in order, never before
                          it arrived, at the time the step log gives; the
                          program's p50/p99 equal the yardstick's (limit 0)
  served_logit_gap        over a seeded sample of the engine's finished
                          requests (the longest among them), the widest gap
                          by which a served token's logit lies below the
                          reference's best at that position
  served_mean_gap, served_disagree_share
                          the mean of those gaps, and the share of
                          positions where the gap is not 0
  train_head_grad_gap     the worst gap of norms of the first gradient
                          before clipping, over the head's leaves (the
                          final norm and the LM head, which the loss
                          reaches without a backward pass through the
                          layer stack)
  train_change_leaf_gap, train_change_median
                          the worst and the median leaf's gap of norms of
                          the master weights' change after the first three
                          steps, leaving out leaves whose reference
                          gradient is under a thousandth of the median
                          leaf's (they move by round-off alone)
  train_loss1_gap, train_loss_gap, train_grad_raw_worst
                          the first loss, all three losses, and the first
                          gradient before clipping by the worst leaf

A gap of norms is |program - reference| over the larger of the leaf's
reference norm and the median leaf's among those compared.

`stand_in="fp8"` puts the control in the program's place: the served
tokens the reference computed with float8 matmul operands puts first, and
the reference's own training steps in that precision.  `readings=True`
also reads the control and the reference's step on half the rows beside
the program (bench/limits.py), without comparing them.
"""
from __future__ import annotations

import sys

import numpy as np

from bench import inputs, reference, spec

GRAD_FLOOR = 1e-3       # of the median leaf's reference gradient norm
# the leaves the loss reaches without a backward pass through the stack
HEAD_LEAVES = ("final_norm", "lm_head")


def _log(msg: str) -> None:
    print(f"[check] {msg}", file=sys.stderr, flush=True)


def sample_requests(finished: list, seed: int, n: int) -> list:
    """The longest finished request and n - 1 others drawn from the seed."""
    if not finished:
        return []
    reqs = sorted(finished, key=lambda r: r.request_id)
    longest = max(range(len(reqs)),
                  key=lambda i: (len(reqs[i].prompt) + len(reqs[i].output), -i))
    rest = [i for i in range(len(reqs)) if i != longest]
    r = inputs.rng(seed, inputs.SAMPLE)
    pick = r.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [reqs[longest]] + [reqs[rest[i]] for i in sorted(pick)]


def served_gaps(params, arch: dict, traffic: dict, reqs: list, n_rows: int,
                precs: tuple = ("f32",)) -> dict:
    """At every position where a served token was produced, the gap by
    which the token's reference logit lies below the reference's best:
    its widest (served_logit_gap), its mean (served_mean_gap) and the share
    of positions where it is not 0 (served_disagree_share), for the served
    tokens ("program") and for the tokens the reference at each other
    precision in `precs` puts first."""
    import jax
    import jax.numpy as jnp
    L = traffic["prompt_tokens"][1] + traffic["new_tokens"][1]
    toks = np.zeros((n_rows, L), np.int32)
    rows, pos, served = [], [], []
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)])
        toks[i, :len(seq)] = seq
        p0 = len(r.prompt) - 1
        for j, t in enumerate(r.output):
            rows.append(i)
            pos.append(p0 + j)
            served.append(int(t))
    # fixed shapes, so that every run finds the reference compiled
    M = n_rows * traffic["new_tokens"][1]
    n_tok = len(served)
    valid = np.arange(M) < n_tok
    rows, pos, served = (jnp.asarray(np.pad(np.array(x, np.int32),
                                            (0, M - n_tok)))
                         for x in (rows, pos, served))
    valid = jnp.asarray(valid)

    def summary(gap):
        g = jnp.where(valid, gap, 0.0)
        return {"served_logit_gap": jnp.where(valid, gap, -jnp.inf).max(),
                "served_mean_gap": g.sum() / n_tok,
                "served_disagree_share": jnp.sum(g > 0) / n_tok}

    @jax.jit
    def ref_gaps(p, t, rows, pos, served):
        lg = reference.logits(p, arch, t, "f32")[rows, pos]       # (M, V)
        best = lg.max(-1)
        gap = best - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
        return best, summary(gap), lg

    best, got, lg32 = ref_gaps(params, jnp.asarray(toks), rows, pos, served)
    out = {"program": {k: float(v) for k, v in got.items()}, "tokens": n_tok}
    for prec in precs:
        if prec == "f32":
            continue

        @jax.jit
        def ctrl_gaps(p, t, rows, pos, lg32, best):
            lg = reference.logits(p, arch, t, prec)[rows, pos]
            pick = jnp.argmax(lg, -1)
            return summary(
                best - jnp.take_along_axis(lg32, pick[:, None], -1)[:, 0])

        got = ctrl_gaps(params, jnp.asarray(toks), rows, pos, lg32, best)
        out[prec] = {k: float(v) for k, v in got.items()}
    return out


def train_reference(conf: dict, seed: int, prec: str = "f32",
                    rows: int | None = None) -> dict:
    """The reference's first three steps from the run's weights and
    batches: losses, the first gradient per leaf (clipped, as the optimizer
    gets it, and its global norm before clipping), and the change after
    the steps per leaf; `rows` keeps only the first rows of each batch."""
    import jax
    import jax.numpy as jnp
    arch, o = conf["arch"], conf["adamw"]
    cfg = spec.model_config(arch)
    p0 = inputs.make_params(cfg, seed, inputs.OFFLINE_WEIGHTS)
    batches = inputs.TokenBatches(seed, arch["vocab_size"], conf["batch"],
                                  conf["seq"])

    @jax.jit
    def start(p0):
        f = jax.tree.map(lambda w: w.astype(jnp.float32), p0)
        z = jax.tree.map(jnp.zeros_like, f)
        return f, z, jax.tree.map(jnp.zeros_like, f)

    def step(p, m, v, toks, k):
        with jax.default_matmul_precision("highest"):
            lval, g = jax.value_and_grad(reference.loss)(p, arch, toks, prec)
            p, m, v, gc = reference.adamw_step(p, m, v, g, k, o)
        return p, m, v, lval, reference.leaf_norms(gc), reference.leaf_norms(g)

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    p, m, v = start(p0)
    losses = []
    for k in range(3):
        toks = batches(k)[:rows]
        p, m, v, lval, gcn, graw = step(p, m, v, jnp.asarray(toks),
                                        jnp.float32(k + 1))
        losses.append(float(lval))
        if k == 0:
            g1 = np.asarray(gcn)
            gnorm1 = float(np.sqrt(np.sum(np.asarray(graw, np.float64) ** 2)))
    change = np.asarray(jax.jit(lambda p, q: reference.leaf_norms(
        jax.tree.map(lambda a, b: a - b.astype(jnp.float32), p, q)))(p, p0))
    names = [_leaf_name(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(p0)[0]]
    return {"losses": losses, "grad1": g1, "grad_norm1": gnorm1,
            "change3": change, "names": names}


def _leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def leaf_gaps(a, b) -> np.ndarray:
    """Per leaf: |a - b| / max(b, the median leaf's b)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(b, np.median(b))


def raw_grad(got: dict, clip: float) -> np.ndarray:
    """The first gradient per leaf before global-norm clipping: the
    optimizer's clipped gradient over its clipping factor."""
    return np.asarray(got["grad1"]) / min(1.0, clip / max(got["grad_norm1"], 1e-9))


def train_numbers(got: dict, ref: dict, clip: float) -> dict:
    """The training numbers of `got` (the program's first steps, or a
    stand-in's) against the reference `ref`."""
    g_ref = raw_grad(ref, clip)
    keep = g_ref >= GRAD_FLOOR * np.median(g_ref)
    head = np.array([n.split("/")[0] in HEAD_LEAVES for n in ref["names"]])
    losses = np.abs(np.asarray(got["losses"][:3]) - np.asarray(ref["losses"]))
    g_got = raw_grad(got, clip)
    change = leaf_gaps(np.asarray(got["change3"])[keep], ref["change3"][keep])
    return {
        "train_loss_gap": float(losses.max()),
        "train_loss1_gap": float(losses[0]),
        "train_grad_raw_worst": float(leaf_gaps(g_got, g_ref).max()),
        "train_head_grad_gap": float(leaf_gaps(g_got[head], g_ref[head]).max()),
        "train_change_leaf_gap": float(change.max()),
        "train_change_median": float(np.median(change)),
        "excluded_leaves": int((~keep).sum()),
    }


def run_checks(conf: dict, traffic: dict, seed: int, holder: dict,
               finished: list, train_snap: dict | None, acct_faults: list,
               stand_in: str | None = None, readings: bool = False) -> dict:
    """All of a run's comparisons.  `holder["params"]` (the online weights)
    is dropped once the served check is done, to make room for the
    training reference."""
    import jax
    lim = conf["limits"]
    checks = [("mux_accounting_faults", float(len(acct_faults)),
               float(lim["mux_accounting_faults"]))]
    for f in acct_faults[:10]:
        _log(f"accounting: {f}")
    out: dict = {}
    n = conf.get("check", {}).get("served_requests", 12)
    reqs = sample_requests(finished, seed, n)
    precs = ("f32", "fp8") if readings or stand_in else ("f32",)
    if reqs:
        with jax.default_matmul_precision("highest"):
            g = served_gaps(holder["params"], conf["online"]["arch"], traffic,
                            reqs, n, precs)
        _log(f"served: {len(reqs)} requests, {g['tokens']} tokens compared")
        out["served"] = g
    served = g[stand_in or "program"] if reqs else {}
    for k in sorted(lim):
        if k.startswith("served_"):
            checks.append((k, served.get(k, float("inf")), lim[k]))
    holder.pop("params", None)
    if train_snap is not None:
        o = conf["offline"]
        ref = train_reference(o, seed, "f32")
        clip = o["adamw"]["grad_clip"]
        got = train_reference(o, seed, stand_in) if stand_in else train_snap
        nums = train_numbers(got, ref, clip)
        _log(f"train: losses {got['losses']} vs reference "
             f"{ref['losses']}; {nums['excluded_leaves']} leaves below the "
             "gradient floor left out of the change")
        for k in sorted(lim):
            if k.startswith("train_"):
                checks.append((k, nums[k], lim[k]))
        out["train_program"] = nums
        if readings:
            out["train_fp8"] = train_numbers(
                train_reference(o, seed, "fp8"), ref, clip)
            out["train_half_batch"] = train_numbers(
                train_reference(o, seed, "f32", rows=o["batch"] // 2), ref,
                clip)
    return {"checks": checks, "readings": out}
