"""The plain reference: the configurations' models, their loss and AdamW,
written from the configuration files in straightforward `jax.numpy`.

It imports nothing of the program and takes nothing the program made: the
weights and inputs are the benchmark's own (`inputs.py`).  It reads the
weights by the names of the program's parameter tree, which is the
interface the two share.

Precision: "f32" computes in float32 with every matmul at HIGHEST; "high"
is the same with matmuls at HIGH (three bfloat16 passes), the witness of
how far rounding alone moves a number.  "fp8" is the control: every forward matmul operand is rounded to float8 e4m3
first (activations scaled per row, weights per tensor), with float32 sums
and a float32 backward pass; the rest is as in "f32".

What the models are (the program's equations, which this follows):
  embed x sqrt(d_model) -> [x + mixer(rmsnorm(x)); x + ffn(rmsnorm(x))] * L
  -> rmsnorm -> lm_head.  RMSNorm eps 1e-6.  Attention: GQA, RoPE (half
  split, theta), causal, sliding window, scale 1/sqrt(head_dim).  Dense FFN:
  SwiGLU.  MoE: softmax router in float32, top-k, weights renormalised, no
  capacity limit.  mLSTM: depthwise causal conv + SiLU feeding q, k, gates;
  v from the unconvolved branch; k / sqrt(dh); exponential input gate,
  sigmoid forget gate, normaliser max(|n.q|, 1) (the stabilised parallel
  form); per-head group norm (eps 1e-6); output x SiLU(z).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
HIGH = jax.lax.Precision.HIGH
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _q8(x, axis):
    """x rounded to float8 e4m3 under an absmax scale; the gradient passes
    straight through, so that the backward pass sums in float32."""
    s = jax.lax.stop_gradient(
        jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX)
    s = jnp.where(s == 0, 1.0, s)
    q = (x / s).astype(_F8).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


class Arith:
    """Matmuls at one precision."""

    def __init__(self, prec: str):
        if prec not in ("f32", "high", "fp8"):
            raise ValueError(prec)
        self.fp8 = prec == "fp8"
        self.precision = HIGH if prec == "high" else HIGHEST

    def act(self, x):
        return _q8(x, -1) if self.fp8 else x

    def weight(self, w):
        return _q8(w, None) if self.fp8 else w

    def mm(self, x, w):
        return jnp.matmul(self.act(x), self.weight(w), precision=self.precision)

    def ein(self, eq, a, b):
        if self.fp8:
            a, b = _q8(a, None), _q8(b, None)
        return jnp.einsum(eq, a, b, precision=self.precision)


def rmsnorm(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions[:, None].astype(jnp.float32) * inv          # (S, dh/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, h, a: dict, ar: Arith):
    B, S, _ = h.shape
    H, Hk, dh = a["num_heads"], a["num_kv_heads"], a["head_dim"]
    pos = jnp.arange(S)
    q = rope(ar.mm(h, p["w_q"]).reshape(B, S, H, dh), pos, a["rope_theta"])
    k = rope(ar.mm(h, p["w_k"]).reshape(B, S, Hk, dh), pos, a["rope_theta"])
    v = ar.mm(h, p["w_v"]).reshape(B, S, Hk, dh)
    k = jnp.repeat(k, H // Hk, axis=2)       # query head j reads kv head j // G
    v = jnp.repeat(v, H // Hk, axis=2)
    s = ar.ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    keep = pos[None, :] <= pos[:, None]
    if a.get("window"):
        keep &= pos[None, :] > pos[:, None] - a["window"]
    s = jnp.where(keep, s, -jnp.inf)
    o = ar.ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return ar.mm(o.reshape(B, S, H * dh), p["w_o"])


def swiglu(p, h, ar: Arith):
    return ar.mm(jax.nn.silu(ar.mm(h, p["w_gate"])) * ar.mm(h, p["w_up"]),
                 p["w_down"])


def moe(p, h, a: dict, ar: Arith):
    B, S, d = h.shape
    E, K = a["num_experts"], a["top_k"]
    x = h.reshape(B * S, d)
    probs = jax.nn.softmax(ar.mm(x, p["router"]), -1)
    w, idx = jax.lax.top_k(probs, K)
    w = w / jnp.sum(w, -1, keepdims=True)
    comb = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32) * w[..., None], 1)
    g = jax.nn.silu(ar.ein("td,edf->tef", x, p["w_gate"]))
    u = ar.ein("td,edf->tef", x, p["w_up"])
    y = ar.ein("tef,efd->ted", g * u, p["w_down"])
    return jnp.einsum("ted,te->td", y, comb,
                      precision=ar.precision).reshape(B, S, d)


def mlstm(p, h, a: dict, ar: Arith):
    B, S, d = h.shape
    dp = a["mlstm_proj_factor"] * d
    H = a["num_heads"]
    dh = dp // H
    x_in, z = jnp.split(ar.mm(h, p["up_proj"]), 2, -1)
    w = p["conv_w"]                                   # (dc, dp)
    dc = w.shape[0]
    xp = jnp.pad(x_in, ((0, 0), (dc - 1, 0), (0, 0)))
    xc = jax.nn.silu(sum(xp[:, i:i + S] * w[i] for i in range(dc)) + p["conv_b"])
    heads = lambda t: t.reshape(B, S, H, dh).transpose(0, 2, 1, 3)  # noqa: E731
    q = heads(ar.mm(xc, p["w_q"]))
    k = heads(ar.mm(xc, p["w_k"])) / math.sqrt(dh)
    v = heads(ar.mm(x_in, p["w_v"]))
    ig = (ar.mm(xc, p["w_i"]) + p["b_i"]).transpose(0, 2, 1)      # (B,H,S)
    fg = (ar.mm(xc, p["w_f"]) + p["b_f"]).transpose(0, 2, 1)
    F = jnp.cumsum(jax.nn.log_sigmoid(fg), -1)
    logd = F[..., :, None] - F[..., None, :] + ig[..., None, :]
    t = jnp.arange(S)
    logd = jnp.where(t[None, :] <= t[:, None], logd, -jnp.inf)
    m = jnp.maximum(jnp.max(logd, -1), -60.0)
    c = ar.ein("bhtd,bhsd->bhts", q, k) * jnp.exp(logd - m[..., None])
    num = ar.ein("bhts,bhsd->bhtd", c, v)
    hh = num / jnp.maximum(jnp.abs(c.sum(-1)), jnp.exp(-m))[..., None]
    hh = hh.transpose(0, 2, 1, 3)                                   # (B,S,H,dh)
    mu = hh.mean(-1, keepdims=True)
    var = ((hh - mu) ** 2).mean(-1, keepdims=True)
    hh = ((hh - mu) * jax.lax.rsqrt(var + 1e-6)).reshape(B, S, dp)
    return ar.mm(hh * p["gn_scale"] * jax.nn.silu(z), p["down_proj"])


def _block(bp, x, a: dict, desc, ar: Arith):
    mixer, ffn = desc
    h = rmsnorm(x, bp["norm1"]["scale"])
    if mixer == "attn":
        x = x + attention(bp["attn"], h, a, ar)
    elif mixer == "mlstm":
        x = x + mlstm(bp["mixer"], h, a, ar)
    else:
        raise ValueError(f"reference has no mixer {mixer!r}")
    if ffn == "dense":
        x = x + swiglu(bp["ffn"], rmsnorm(x, bp["norm2"]["scale"]), ar)
    elif ffn == "moe":
        x = x + moe(bp["ffn"], rmsnorm(x, bp["norm2"]["scale"]), a, ar)
    elif ffn != "none":
        raise ValueError(f"reference has no ffn {ffn!r}")
    return x


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


def hidden(params, a: dict, tokens, prec: str = "f32", remat: bool = False):
    """Final-norm hidden states (B, S, d) in float32.  Weights may be in
    any type; each layer's are cast to float32 as the scan reaches it."""
    ar = Arith(prec)
    pattern = [tuple(p) for p in a["pattern"]]
    x = jnp.take(params["embed"], tokens, 0).astype(jnp.float32) \
        * math.sqrt(a["d_model"])

    def layer(x, blocks):
        for bp, desc in zip(blocks, pattern):
            x = _block(_f32(bp), x, a, desc, ar)
        return x, None

    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, x, tuple(params["blocks"]))
    return rmsnorm(x, params["final_norm"]["scale"].astype(jnp.float32)), ar


def logits(params, a: dict, tokens, prec: str = "f32"):
    """(B, S, vocab_size) float32 logits; the padded columns are dropped."""
    x, ar = hidden(params, a, tokens, prec)
    return ar.mm(x, params["lm_head"].astype(jnp.float32))[..., :a["vocab_size"]]


def loss(params, a: dict, tokens, prec: str = "f32"):
    """Mean next-token cross-entropy over the vocabulary's real columns; the
    last position has no target."""
    x, ar = hidden(params, a, tokens, prec, remat=True)
    lg = ar.mm(x[:, :-1], params["lm_head"])[..., :a["vocab_size"]]
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


def lr_at(o: dict, step):
    """Linear warm-up to `lr`, then cosine decay to min_lr_frac * lr."""
    step = jnp.asarray(step, jnp.float32)
    warm = o["lr"] * step / max(o["warmup_steps"], 1)
    prog = jnp.clip((step - o["warmup_steps"])
                    / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    cos = o["lr"] * (o["min_lr_frac"]
                     + (1 - o["min_lr_frac"]) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(step < o["warmup_steps"], warm, cos)


def adamw_step(params, m, v, grads, step: int, o: dict):
    """One AdamW update in float32: global-norm clipping, bias-corrected
    moments, decoupled weight decay on every leaf."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    lr = lr_at(o, step)
    b1, b2 = o["b1"], o["b2"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, m_, v_):
        return p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + o["eps"])
                         + o["weight_decay"] * p)

    return jax.tree.map(upd, params, m, v), m, v, grads


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])
