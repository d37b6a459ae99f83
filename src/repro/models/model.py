"""Model zoo core: config, init, and the pattern-scanned forward pass.

A model is a *pattern* of block descriptors `(mixer, ffn)` repeated
`num_layers / len(pattern)` times (jamba: 8-layer super-block × 9; dense LMs:
1-layer pattern × L).  Parameters and caches carry a leading `repeats` dim and
the forward pass is a single `lax.scan` over repeats — keeping the HLO small
enough to compile 40 dry-run cells on a CPU host with 512 fake devices.

Modes:
  * train   — full-seq forward, logits for every position (loss in steps.py)
  * prefill — full-seq forward, builds the decode cache, last-token logits
  * decode  — one token against the cache (the online-serving hot path)
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.sharding.context import constrain

from . import layers as L
from . import moe as M
from . import ssm as S

Params = dict[str, Any]

MIXERS = ("attn", "attn_cross", "mamba", "mlstm")
FFNS = ("dense", "moe", "none")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense|moe|hybrid|ssm|encdec|vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    pattern: tuple = (("attn", "dense"),)
    # attention
    attn_kind: str = "gqa"            # gqa | mla
    window: int | None = None         # sliding-window size (None = full)
    rope_theta: float = 10000.0
    softcap: float | None = None
    kv_lora_rank: int = 0
    rope_head_dim: int = 64
    # ffn
    ffn_act: str = "silu"
    # moe
    num_experts: int = 0
    top_k: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0
    moe_renormalize: bool = True
    moe_impl: str = "grouped"         # grouped (production) | dense (oracle)
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # ssm / mlstm
    ssm_d_inner: int = 0
    ssm_state_dim: int = 16
    ssm_conv_dim: int = 4
    ssm_dt_rank: int = 0
    ssm_chunk: int = 256
    mlstm_proj_factor: int = 2
    # encoder (enc-dec archs)
    enc_layers: int = 0
    # modality frontend stubs
    frontend: str = "none"            # none | audio | patch
    num_patches: int = 0              # vlm: image patches prepended to text
    # numerics / impl
    dtype: Any = jnp.bfloat16
    attn_impl: str = "reference"      # reference | pallas
    attn_force_chunked: bool = False  # stream KV chunks even at short seqs
    fused_loss: bool = False          # stream the vocab dim in the loss
    remat: bool = True
    vocab_pad_multiple: int = 256

    @property
    def repeats(self) -> int:
        assert self.num_layers % len(self.pattern) == 0, \
            f"{self.num_layers} layers vs pattern of {len(self.pattern)}"
        return self.num_layers // len(self.pattern)

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab_size + m - 1) // m * m

    @property
    def decode_window(self) -> int | None:
        """KV capacity bound for sliding-window archs (ring cache)."""
        return self.window

    def param_count(self) -> int:
        """Analytic parameter count (for roofline MODEL_FLOPS)."""
        p = 2 * self.padded_vocab * self.d_model   # embed + head
        per_pattern = 0
        for mixer, f in self.pattern:
            per_pattern += self.d_model            # norm1
            if mixer in ("attn", "attn_cross"):
                if self.attn_kind == "mla":
                    H, dh, r, dr = self.num_heads, self.head_dim, self.kv_lora_rank, self.rope_head_dim
                    per_pattern += self.d_model * H * (dh + dr) + self.d_model * (r + dr) \
                        + r * 2 * H * dh + H * dh * self.d_model
                else:
                    H, Hk, dh = self.num_heads, self.num_kv_heads, self.head_dim
                    per_pattern += self.d_model * dh * (H + 2 * Hk) + H * dh * self.d_model
                if mixer == "attn_cross":
                    H, Hk, dh = self.num_heads, self.num_kv_heads, self.head_dim
                    per_pattern += self.d_model * dh * (H + 2 * Hk) + H * dh * self.d_model + self.d_model
            elif mixer == "mamba":
                di, N, dtr, dc = self.ssm_d_inner, self.ssm_state_dim, self.ssm_dt_rank, self.ssm_conv_dim
                per_pattern += self.d_model * 2 * di + dc * di + di * (dtr + 2 * N) \
                    + dtr * di + di * N + di + di * self.d_model + 2 * di  # conv_b, dt_bias
            elif mixer == "mlstm":
                dp = self.mlstm_proj_factor * self.d_model
                per_pattern += self.d_model * 2 * dp + self.ssm_conv_dim * dp + 3 * dp * dp \
                    + 2 * dp * self.num_heads + dp + dp * self.d_model \
                    + dp + 2 * self.num_heads  # conv_b, b_i, b_f
            if f == "dense":
                per_pattern += self.d_model + 3 * self.d_model * self.d_ff
            elif f == "moe":
                per_pattern += self.d_model + self.d_model * self.num_experts \
                    + self.num_experts * 3 * self.d_model * self.moe_d_ff \
                    + (3 * self.d_model * self.moe_d_ff * self.num_shared_experts)
        p += per_pattern * self.repeats
        if self.enc_layers:
            enc = self.enc_layers * (2 * self.d_model
                                     + self.d_model * self.head_dim * (self.num_heads + 2 * self.num_kv_heads)
                                     + self.num_heads * self.head_dim * self.d_model
                                     + 3 * self.d_model * self.d_ff)
            p += enc + self.d_model  # + enc_final_norm
        p += self.d_model                          # final norm
        return p

    def active_param_count(self) -> int:
        """Per-token-active params (MoE: only top-k + shared experts)."""
        if not any(f == "moe" for _, f in self.pattern):
            return self.param_count()
        full = self.param_count()
        moe_positions = sum(1 for _, f in self.pattern if f == "moe")
        dead = (self.num_experts - self.top_k) * 3 * self.d_model * self.moe_d_ff
        return full - dead * moe_positions * self.repeats


# ===========================================================================
# Init
# ===========================================================================

def _block_init(key, cfg: ModelConfig, desc) -> Params:
    mixer, f = desc
    ks = jax.random.split(key, 6)
    p: Params = {"norm1": L.rmsnorm_init(cfg.d_model, cfg.dtype)}
    if mixer == "attn" or mixer == "attn_cross":
        if cfg.attn_kind == "mla":
            p["attn"] = L.mla_init(ks[0], cfg, cfg.dtype)
        else:
            p["attn"] = L.gqa_init(ks[0], cfg, cfg.dtype)
        if mixer == "attn_cross":
            p["norm_cross"] = L.rmsnorm_init(cfg.d_model, cfg.dtype)
            p["cross"] = L.gqa_init(ks[1], cfg, cfg.dtype)
    elif mixer == "mamba":
        p["mixer"] = S.mamba_init(ks[0], cfg, cfg.dtype)
    elif mixer == "mlstm":
        p["mixer"] = S.mlstm_init(ks[0], cfg, cfg.dtype)
    else:
        raise ValueError(mixer)
    if f == "dense":
        p["norm2"] = L.rmsnorm_init(cfg.d_model, cfg.dtype)
        p["ffn"] = L.ffn_init(ks[2], cfg.d_model, cfg.d_ff, cfg.dtype)
    elif f == "moe":
        p["norm2"] = L.rmsnorm_init(cfg.d_model, cfg.dtype)
        p["ffn"] = M.moe_init(ks[2], cfg, cfg.dtype)
    elif f != "none":
        raise ValueError(f)
    return p


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    R = cfg.repeats
    blocks = []
    for i, desc in enumerate(cfg.pattern):
        bkeys = jax.random.split(jax.random.fold_in(keys[0], i), R)
        blocks.append(jax.vmap(partial(_block_init, cfg=cfg, desc=desc))(bkeys))
    p: Params = {
        "embed": L.embed_init(keys[1], (cfg.padded_vocab, cfg.d_model), cfg.dtype),
        "blocks": tuple(blocks),
        "final_norm": L.rmsnorm_init(cfg.d_model, cfg.dtype),
        "lm_head": L.dense_init(keys[2], (cfg.d_model, cfg.padded_vocab), cfg.dtype),
    }
    if cfg.enc_layers:
        ekeys = jax.random.split(keys[3], cfg.enc_layers)
        p["enc_blocks"] = jax.vmap(
            partial(_block_init, cfg=cfg, desc=("attn", "dense")))(ekeys)
        p["enc_final_norm"] = L.rmsnorm_init(cfg.d_model, cfg.dtype)
    return p


# ===========================================================================
# Caches
# ===========================================================================

def init_cache(cfg: ModelConfig, batch: int, kv_capacity: int,
               src_len: int = 0) -> tuple:
    """Decode cache: tuple over pattern positions, each leaf leading-dim R.

    kv_capacity: sequence capacity of attention KV caches (for SWA archs this
    is min(window, kv_capacity): the ring bound).
    """
    R = cfg.repeats
    Hk, dh = cfg.num_kv_heads, cfg.head_dim
    caches = []
    for mixer, _ in cfg.pattern:
        if mixer in ("attn", "attn_cross"):
            cap = kv_capacity if cfg.window is None else min(cfg.window, kv_capacity)
            if cfg.attn_kind == "mla":
                c = {"ckv": jnp.zeros((R, batch, cap, cfg.kv_lora_rank), cfg.dtype),
                     "kr": jnp.zeros((R, batch, cap, 1, cfg.rope_head_dim), cfg.dtype)}
            else:
                c = {"k": jnp.zeros((R, batch, cap, Hk, dh), cfg.dtype),
                     "v": jnp.zeros((R, batch, cap, Hk, dh), cfg.dtype)}
            if mixer == "attn_cross":
                c["xk"] = jnp.zeros((R, batch, src_len, Hk, dh), cfg.dtype)
                c["xv"] = jnp.zeros((R, batch, src_len, Hk, dh), cfg.dtype)
        elif mixer == "mamba":
            st = S.mamba_state_init(batch, cfg)
            c = {k: jnp.zeros((R,) + v.shape, v.dtype) for k, v in st.items()}
        elif mixer == "mlstm":
            st = S.mlstm_state_init(batch, cfg)
            c = {"C": jnp.zeros((R,) + st["carry"][0].shape, jnp.float32),
                 "n": jnp.zeros((R,) + st["carry"][1].shape, jnp.float32),
                 "m": jnp.full((R,) + st["carry"][2].shape, -60.0, jnp.float32),
                 "conv": jnp.zeros((R,) + st["conv"].shape, st["conv"].dtype)}
        else:
            raise ValueError(mixer)
        caches.append(c)
    return tuple(caches)


# ===========================================================================
# Block application
# ===========================================================================

def _cache_write(cache, new, pos):
    """Write `new` (B,1,...) at sequence position `pos` (scalar or (B,)) —
    per-batch positions enable continuous batching (ragged slots)."""
    if jnp.ndim(pos) == 0:
        starts = (0, pos) + (0,) * (cache.ndim - 2)
        return jax.lax.dynamic_update_slice(cache, new, starts)
    def one(c, n, p):
        return jax.lax.dynamic_update_slice(c, n, (p,) + (0,) * (c.ndim - 1))
    return jax.vmap(one)(cache, new, pos)


def _apply_attn(bp, x, cfg: ModelConfig, positions, cache, mode, enc_out=None,
                cross=False):
    """Self-attention sub-block.  Returns (out, new_cache_entries)."""
    B, Sq, _ = x.shape
    new_cache = {}
    if cfg.attn_kind == "mla":
        if mode == "decode":
            pos = positions[..., 0] if positions.ndim > 1 else positions[0]
            ckv_new, kr_new = L.mla_latent(bp["attn"], x, cfg, positions)
            ckv = _cache_write(cache["ckv"], ckv_new, pos)
            kr = _cache_write(cache["kr"], kr_new, pos)
            new_cache = {"ckv": ckv, "kr": kr}
            out = L.mla_attend(bp["attn"], x, ckv, kr, cfg, positions,
                               kv_len=pos + 1, causal=False)
        else:
            ckv, kr = L.mla_latent(bp["attn"], x, cfg, positions)
            out = L.mla_attend(bp["attn"], x, ckv, kr, cfg, positions, causal=True)
            if mode == "prefill":
                new_cache = {"ckv": ckv, "kr": kr}
        return out, new_cache

    q, k, v = L.gqa_project_qkv(bp["attn"], x, cfg, positions)
    if mode == "decode":
        pos = positions[..., 0] if positions.ndim > 1 else positions[0]
        if cfg.window is not None and cache["k"].shape[1] == cfg.window:
            slot = pos % cfg.window
            kc = _cache_write(cache["k"], k, slot)
            vc = _cache_write(cache["v"], v, slot)
            o = L.attention_ring_cache(q, kc, vc, pos=pos, window=cfg.window)
        else:
            kc = _cache_write(cache["k"], k, pos)
            vc = _cache_write(cache["v"], v, pos)
            o = L.attention(q, kc, vc, causal=False, q_offset=pos,
                            kv_len=pos + 1, softcap=cfg.softcap)
        new_cache = {"k": kc, "v": vc}
    else:
        o = L.attention(q, k, v, causal=not cross, window=cfg.window,
                        softcap=cfg.softcap,
                        force_chunked=cfg.attn_force_chunked)
        if mode == "prefill":
            if cfg.window is not None:
                W = cfg.window
                if k.shape[1] > W:          # keep last W entries, ring-aligned
                    kl, vl = k[:, -W:], v[:, -W:]
                    shift = (k.shape[1]) % W
                    kc = jnp.roll(kl, shift, axis=1)
                    vc = jnp.roll(vl, shift, axis=1)
                else:
                    kc, vc = k, v
                new_cache = {"k": kc, "v": vc}
            else:
                new_cache = {"k": k, "v": v}
    out = o.reshape(B, Sq, cfg.num_heads * cfg.head_dim) @ bp["attn"]["w_o"]
    return out, new_cache


def _apply_cross_attn(bp, x, enc_out, cfg, cache, mode):
    """Cross-attention: queries from x, keys/values from encoder output."""
    B, Sq, _ = x.shape
    H, Hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ bp["cross"]["w_q"]).reshape(B, Sq, H, dh)
    if mode == "decode":
        k, v = cache["xk"], cache["xv"]
        new = {"xk": k, "xv": v}
    else:
        Skv = enc_out.shape[1]
        k = (enc_out @ bp["cross"]["w_k"]).reshape(B, Skv, Hk, dh)
        v = (enc_out @ bp["cross"]["w_v"]).reshape(B, Skv, Hk, dh)
        new = {"xk": k, "xv": v} if mode == "prefill" else {}
    o = L.attention(q, k, v, causal=False)
    return o.reshape(B, Sq, H * dh) @ bp["cross"]["w_o"], new


def _apply_block(bp, x, cfg: ModelConfig, desc, positions, cache, mode,
                 enc_out=None, moe_counts=False):
    """Returns (x, new_cache, aux_loss, counts): counts are the MoE
    dispatch's (see `forward`) where `moe_counts` and the block has one,
    else None."""
    mixer, f = desc
    aux = jnp.zeros((), jnp.float32)
    counts = None
    new_cache: dict = {}
    h = L.rmsnorm(bp["norm1"], x)
    if mixer in ("attn", "attn_cross"):
        o, nc = _apply_attn(bp, h, cfg, positions, cache, mode)
        new_cache.update(nc)
        x = x + o
        if mixer == "attn_cross":
            h = L.rmsnorm(bp["norm_cross"], x)
            o, nc = _apply_cross_attn(bp, h, enc_out, cfg, cache, mode)
            new_cache.update(nc)
            x = x + o
    elif mixer == "mamba":
        if mode == "decode":
            st = {"h": cache["h"], "conv": cache["conv"]}
            o, st = S.mamba_decode_step(bp["mixer"], h, st, cfg)
            new_cache = dict(st)
        else:
            o, h_last = S.mamba_mixer(bp["mixer"], h, cfg)
            if mode == "prefill":
                # conv state holds the last dc-1 *inner* pre-conv activations
                x_in = h @ bp["mixer"]["in_proj"][:, :cfg.ssm_d_inner]
                new_cache = {"h": h_last,
                             "conv": x_in[:, -(cfg.ssm_conv_dim - 1):, :]}
        x = x + o
    elif mixer == "mlstm":
        if mode == "decode":
            st = {"carry": (cache["C"], cache["n"], cache["m"]), "conv": cache["conv"]}
            o, st = S.mlstm_decode_step(bp["mixer"], h, st, cfg)
            new_cache = {"C": st["carry"][0], "n": st["carry"][1],
                         "m": st["carry"][2], "conv": st["conv"]}
        else:
            o, carry = S.mlstm_mixer(bp["mixer"], h, cfg)
            if mode == "prefill":
                dp = cfg.mlstm_proj_factor * cfg.d_model
                x_in = h @ bp["mixer"]["up_proj"][:, :dp]
                new_cache = {"C": carry[0], "n": carry[1], "m": carry[2],
                             "conv": x_in[:, -(cfg.ssm_conv_dim - 1):, :]}
        x = x + o
    if f == "dense":
        h = L.rmsnorm(bp["norm2"], x)
        x = x + L.ffn(bp["ffn"], h, cfg.ffn_act)
    elif f == "moe":
        h = L.rmsnorm(bp["norm2"], x)
        if moe_counts:
            o, a, counts = M.moe_ffn(bp["ffn"], h, cfg, counts=True)
        else:
            o, a = M.moe_ffn(bp["ffn"], h, cfg)
        x = x + o
        aux = aux + a
    return x, new_cache, aux, counts


# ===========================================================================
# Full forward
# ===========================================================================

def _embed_inputs(params, cfg: ModelConfig, batch: dict):
    """Assemble the input embedding sequence from tokens and frontend stubs."""
    parts = []
    if cfg.frontend == "patch" and "patch_embeds" in batch:
        parts.append(batch["patch_embeds"].astype(cfg.dtype))
    toks = batch["tokens"]
    parts.append(jnp.take(params["embed"], toks, axis=0))
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    return constrain(x * math.sqrt(cfg.d_model), "dp", None, None)


def _encoder_forward(params, cfg: ModelConfig, src_embeds):
    """Bidirectional encoder over stub frame embeddings (audio frontend)."""
    x = src_embeds.astype(cfg.dtype) * math.sqrt(cfg.d_model)
    S_len = x.shape[1]
    positions = jnp.arange(S_len)

    def body(x, bp):
        h = L.rmsnorm(bp["norm1"], x)
        q, k, v = L.gqa_project_qkv(bp["attn"], h, cfg, positions)
        o = L.attention(q, k, v, causal=False)
        o = o.reshape(x.shape[0], S_len, cfg.num_heads * cfg.head_dim) @ bp["attn"]["w_o"]
        x = x + o
        h = L.rmsnorm(bp["norm2"], x)
        x = x + L.ffn(bp["ffn"], h, cfg.ffn_act)
        return x, None

    if cfg.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return L.rmsnorm(params["enc_final_norm"], x)


def forward(params, cfg: ModelConfig, batch: dict, *, mode: str = "train",
            cache: tuple | None = None, pos=None, moe_counts: bool = False):
    """Unified forward.

    train:   batch={tokens,(src_embeds|patch_embeds)} -> (logits, aux)
    prefill: same batch -> (last_logits, cache, aux)
    decode:  batch={tokens (B,1)}, cache, pos -> (logits, cache), and with
             `moe_counts` (logits, cache, counts): int32 (MoE layers, 2), for
             each MoE layer in depth order the distinct experts the batch's
             tokens were routed to and the routed slots the dispatch dropped
    """
    if moe_counts and mode != "decode":
        raise ValueError("moe_counts is read in decode mode only")
    enc_out = None
    if cfg.enc_layers and mode != "decode":
        enc_out = _encoder_forward(params, cfg, batch["src_embeds"])

    if mode == "decode":
        x = jnp.take(params["embed"], batch["tokens"], axis=0) * math.sqrt(cfg.d_model)
        pos_arr = jnp.asarray(pos)
        positions = pos_arr[:, None] if pos_arr.ndim == 1 else pos_arr[None]
    else:
        x = _embed_inputs(params, cfg, batch)
        positions = jnp.arange(x.shape[1])

    P = len(cfg.pattern)

    def superblock(carry, xs):
        x, aux = carry
        blocks = xs[0]
        caches = xs[1] if cache is not None else (None,) * P
        new_caches, counts = [], []
        for i, desc in enumerate(cfg.pattern):
            x, nc, a, c = _apply_block(blocks[i], x, cfg, desc, positions,
                                       caches[i], mode, enc_out=enc_out,
                                       moe_counts=moe_counts)
            x = constrain(x, "dp", None, None)
            new_caches.append(nc)
            aux = aux + a
            if c is not None:
                counts.append(c)
        if moe_counts:
            return (x, aux), (tuple(new_caches), tuple(counts))
        return (x, aux), tuple(new_caches)

    body = superblock
    if cfg.remat and mode in ("train", "train_hidden"):
        body = jax.checkpoint(superblock)

    xs = (params["blocks"],) if cache is None else (params["blocks"], cache)
    (x, aux), new_cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    if moe_counts:
        new_cache, counts = new_cache      # each (repeats, 2): to depth order
        counts = jnp.stack(counts, 1).reshape(-1, 2)
    x = L.rmsnorm(params["final_norm"], x)

    if mode == "train":
        logits = x @ params["lm_head"]
        return logits, aux
    if mode == "train_hidden":
        return x, aux
    if mode == "prefill":
        last = x[:, -1:]
        logits = last @ params["lm_head"]
        return logits[:, 0], new_cache, aux
    logits = x[:, 0] @ params["lm_head"]
    if moe_counts:
        return logits, new_cache, counts
    return logits, new_cache
