"""Mixture-of-Experts FFN with top-k routing and optional shared experts.

Two execution paths:
  * `moe_dense_dispatch` — baseline: every expert runs on every token and the
    result is combined with the (sparse) routing weights.  FLOP-inflated but
    trivially shardable; this is the paper-faithful baseline the roofline
    analysis starts from.
  * `moe_grouped_dispatch` — capacity-based gather/scatter dispatch: tokens are
    routed to per-expert buffers of capacity C = ceil(k*T/E)*cf (C = 1 at
    T = 1, which drops nothing), experts run only on their buffers.  This is
    the optimized path (§Perf).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sharding.context import constrain

from .layers import dense_init, _ACTS


def moe_init(key, cfg, dtype):
    d, dff = cfg.d_model, cfg.moe_d_ff
    E, S = cfg.num_experts, cfg.num_shared_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "w_gate": dense_init(ks[1], (E, d, dff), dtype),
        "w_up": dense_init(ks[2], (E, d, dff), dtype),
        "w_down": dense_init(ks[3], (E, dff, d), dtype),
    }
    if S > 0:
        from .layers import ffn_init
        p["shared"] = ffn_init(ks[4], d, dff * S, dtype)
    return p


def router_probs(params, x, cfg):
    """Top-k routing probabilities.  x: (B,S,d) -> (weights (B,S,k), idx (B,S,k),
    aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ params["router"])          # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.top_k)               # (B,S,k)
    if cfg.moe_renormalize:
        weights = weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss
    E = cfg.num_experts
    me = jnp.mean(probs, axis=(0, 1))                            # mean prob per expert
    ce = jnp.mean(
        (jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(-2) > 0).astype(jnp.float32),
        axis=(0, 1),
    )
    aux = E * jnp.sum(me * ce)
    return weights, idx, aux


def _expert_ffn(wp, x, act):
    g = _ACTS[act](jnp.einsum("ted,edf->tef", x, wp["w_gate"]))
    u = jnp.einsum("ted,edf->tef", x, wp["w_up"])
    return jnp.einsum("tef,efd->ted", g * u, wp["w_down"])


def moe_dense_dispatch(params, x, cfg):
    """Baseline: run all E experts on all tokens; combine by routing weights."""
    B, S, d = x.shape
    weights, idx, aux = router_probs(params, x, cfg)
    xt = x.reshape(B * S, 1, d)
    xe = jnp.broadcast_to(xt, (B * S, cfg.num_experts, d))
    ye = _expert_ffn(params, xe, cfg.ffn_act)                    # (T,E,d)
    comb = jnp.zeros((B * S, cfg.num_experts), x.dtype)
    comb = comb.at[jnp.arange(B * S)[:, None], idx.reshape(B * S, -1)].add(
        weights.reshape(B * S, -1).astype(x.dtype))
    y = jnp.einsum("ted,te->td", ye, comb).reshape(B, S, d)
    if "shared" in params:
        from .layers import ffn
        y = y + ffn(params["shared"], x, cfg.ffn_act)
    return y, aux


def moe_grouped_dispatch(params, x, cfg, capacity_factor: float = 1.25,
                         counts: bool = False):
    """Capacity-based grouped dispatch (production path, expert-parallel).

    Each batch row is a dispatch *group* (stays on its data shard).  Within a
    group, slots are sorted by expert id to compute in-expert positions in
    O(M log M) instead of the O(M·E) cumsum, scattered into per-expert
    capacity buffers, run through the expert FFN (experts sharded over the
    model axis = EP), and gathered back.  Slots beyond capacity are dropped
    (GShard/Switch semantics); at S = 1 (decode) none can be.

    With `counts`, also returns int32 [distinct experts the tokens of all
    rows were routed to, routed slots dropped].
    """
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    M = S * K
    weights, idx, aux = router_probs(params, x, cfg)             # (B,S,K)
    if S == 1:
        # decode: a token's top-k experts are distinct, so no expert takes
        # more than one of a row's slots, and capacity 1 drops none
        cap = 1
    else:
        cap = min(int(max(1, round(-(-M // E) * capacity_factor))), M)

    flat_idx = idx.reshape(B, M)                                 # expert of slot
    tok_of_slot = jnp.repeat(jnp.arange(S), K)                   # (M,)

    def group_positions(e_ids):
        order = jnp.argsort(e_ids, stable=True)
        ranks = jnp.zeros((M,), jnp.int32).at[order].set(jnp.arange(M, dtype=jnp.int32))
        sorted_e = e_ids[order]
        start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
        return ranks - start[e_ids]

    pos = jax.vmap(group_positions)(flat_idx)                    # (B,M)
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, cap - 1)

    def scatter_group(xg, e_ids, p, kp):
        vals = jnp.where(kp[:, None], xg[tok_of_slot], 0)
        return jnp.zeros((E, cap, d), x.dtype).at[e_ids, p].add(vals)

    buf = jax.vmap(scatter_group)(x, flat_idx, safe_pos, keep)   # (B,E,cap,d)
    buf = constrain(buf, "dp", "tp", None, None)
    yb = _expert_ffn_grouped(params, buf, cfg.ffn_act)           # (B,E,cap,d)
    yb = constrain(yb, "dp", "tp", None, None)

    def gather_group(ybg, e_ids, p):
        return ybg[e_ids, p]                                     # (M,d)

    g = jax.vmap(gather_group)(yb, flat_idx, safe_pos)           # (B,M,d)
    g = jnp.where(keep[..., None], g, 0).reshape(B, S, K, d)
    y = jnp.einsum("bskd,bsk->bsd", g, weights.astype(x.dtype))
    if "shared" in params:
        from .layers import ffn
        y = y + ffn(params["shared"], x, cfg.ffn_act)
    if not counts:
        return y.astype(x.dtype), aux
    hit = jnp.zeros((E,), jnp.int32).at[flat_idx.reshape(-1)].set(1).sum()
    return y.astype(x.dtype), aux, jnp.stack([hit, jnp.sum(~keep, dtype=jnp.int32)])


def _expert_ffn_grouped(wp, buf, act):
    g = _ACTS[act](constrain(jnp.einsum("becd,edf->becf", buf, wp["w_gate"]),
                             "dp", "tp", None, None))
    u = constrain(jnp.einsum("becd,edf->becf", buf, wp["w_up"]),
                  "dp", "tp", None, None)
    return jnp.einsum("becf,efd->becd", g * u, wp["w_down"])


def moe_a2a_dispatch(params, x, cfg, capacity_factor: float = 1.25):
    """Expert-parallel dispatch with explicit all-to-alls (shard_map).

    The §Perf optimization over `grouped`: GSPMD lowers the grouped gather
    /scatter across expert shards into partial-sum all-reduces of the full
    (tokens, d) slot tensor; here each token's slots move to their expert's
    shard and back with two all-to-alls over the model axis, so only routed
    capacity travels at (n−1)/n per direction.

    Falls back to `moe_grouped_dispatch` when no mesh with a model axis is
    installed (unit tests, single-device runs).
    """
    from repro.sharding.context import current_mesh
    mesh = current_mesh()
    if (mesh is None or "model" not in mesh.axis_names
            or cfg.num_experts % mesh.shape["model"] != 0):
        return moe_grouped_dispatch(params, x, cfg, capacity_factor)
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import dp_axes

    tp = mesh.shape["model"]
    dp = dp_axes(mesh)
    dp_spec = dp[0] if len(dp) == 1 else dp
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    E_loc = E // tp
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    B_loc = B // dp_size if (dp_size > 1 and B % dp_size == 0) else B
    batch_spec = dp_spec if (dp_size > 1 and B % dp_size == 0) else None
    if (B_loc * S) % tp != 0:
        return moe_grouped_dispatch(params, x, cfg, capacity_factor)
    M = B_loc * S * K // tp          # slots per device (token-parallel)
    cap = int(max(1, round(-(-M // E) * capacity_factor)))
    cap = min(cap, M)

    def local_moe(router_w, w_gate, w_up, w_down, shared, x_loc):
        b, s, _ = x_loc.shape
        # x_loc is replicated across the model axis: each model-rank routes
        # only its 1/tp slice of the tokens (token-parallel dispatch), so the
        # expert FLOPs stay at 1/(dp*tp) of the global work per device.
        rank = jax.lax.axis_index("model")
        T = b * s
        T_loc = T // tp
        xt_full = x_loc.reshape(T, d)
        xt = jax.lax.dynamic_slice_in_dim(xt_full, rank * T_loc, T_loc, 0)
        logits = xt.astype(jnp.float32) @ router_w
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = jax.lax.top_k(probs, K)                # (T, K)
        if cfg.moe_renormalize:
            weights = weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean((jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1) > 0)
                      .astype(jnp.float32), axis=0)
        aux = E * jnp.sum(me * ce)
        m = T_loc * K
        e_ids = idx.reshape(m)
        tok = jnp.repeat(jnp.arange(T_loc), K)
        order = jnp.argsort(e_ids, stable=True)
        ranks = jnp.zeros((m,), jnp.int32).at[order].set(
            jnp.arange(m, dtype=jnp.int32))
        start = jnp.searchsorted(e_ids[order], jnp.arange(E), side="left")
        pos = ranks - start[e_ids]
        keep = pos < cap
        safe = jnp.where(keep, pos, cap - 1)
        buf = jnp.zeros((E, cap, d), x_loc.dtype).at[e_ids, safe].add(
            jnp.where(keep[:, None], xt[tok], 0))
        # ---- a2a out: send expert-block i to model-shard i; receive every
        # shard's rows for MY local experts: (tp_src, E_loc, cap, d)
        buf = buf.reshape(tp, E_loc, cap, d)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0,
                                 tiled=True)
        buf = buf.transpose(1, 0, 2, 3).reshape(E_loc, tp * cap, d)
        g = _ACTS[cfg.ffn_act](jnp.einsum("ecd,edf->ecf", buf, w_gate))
        u = jnp.einsum("ecd,edf->ecf", buf, w_up)
        yb = jnp.einsum("ecf,efd->ecd", g * u, w_down)
        # ---- a2a back: return each shard's token rows to their owner
        yb = yb.reshape(E_loc, tp, cap, d).transpose(1, 0, 2, 3)
        yb = jax.lax.all_to_all(yb, "model", split_axis=0, concat_axis=0,
                                tiled=True)      # (tp_expert_owner, E_loc, cap, d)
        yb = yb.reshape(E, cap, d)
        got = yb[e_ids, safe]
        got = jnp.where(keep[:, None], got, 0).reshape(T_loc, K, d)
        y = jnp.einsum("tkd,tk->td", got, weights.astype(x_loc.dtype))
        if shared is not None:
            # shared experts also run token-parallel over the model axis
            sg = _ACTS[cfg.ffn_act](xt @ shared["w_gate"])
            y = y + (sg * (xt @ shared["w_up"])) @ shared["w_down"]
        # reassemble the full token dim across the model axis
        y = jax.lax.all_gather(y, "model", axis=0, tiled=True)
        y = y.reshape(b, s, d)
        return y.astype(x_loc.dtype), aux[None]

    shared = params.get("shared")
    shared_spec = (jax.tree.map(lambda _: P(), shared)
                   if shared is not None else None)
    fn = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), shared_spec,
                  P(batch_spec, None, None)),
        out_specs=(P(batch_spec, None, None), P(dp_spec if dp else None)),
        check_vma=False)
    y, aux = fn(params["router"], params["w_gate"], params["w_up"],
                params["w_down"], shared, x)
    return y, jnp.mean(aux)


def moe_ffn(params, x, cfg, counts: bool = False):
    """Dispatch-mode switch: cfg.moe_impl in {'dense','grouped','a2a'}.
    `counts` (grouped only) adds the dispatch's counts to what it returns."""
    impl = getattr(cfg, "moe_impl", "dense")
    if counts:
        if impl != "grouped":
            raise ValueError(f"MoE counts come from the grouped dispatch, "
                             f"not {impl!r}")
        return moe_grouped_dispatch(params, x, cfg, cfg.moe_capacity_factor,
                                    counts=True)
    if impl == "a2a":
        return moe_a2a_dispatch(params, x, cfg,
                                capacity_factor=cfg.moe_capacity_factor)
    if impl == "grouped":
        return moe_grouped_dispatch(params, x, cfg,
                                    capacity_factor=cfg.moe_capacity_factor)
    return moe_dense_dispatch(params, x, cfg)
