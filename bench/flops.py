"""Operations and bytes that a step needs, from the configuration's shapes.

"Needed" is the least any implementation must do: the vocabulary's real
columns, the experts a token is routed to, causal halves of attention, the
keys and values of a slot's live positions (never the allocated capacity),
and no recomputation.  So a share of the roofline built on these can only
read higher when the program does less redundant work, never above 100%.
"""
from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _layer_weights(a: dict) -> dict:
    """Matmul weights per layer, by part: counts of parameters."""
    d = a["d_model"]
    parts: dict[str, int] = {}
    for mixer, ffn in a["pattern"]:
        if mixer == "attn":
            H, Hk, dh = a["num_heads"], a["num_kv_heads"], a["head_dim"]
            parts["attn"] = parts.get("attn", 0) + d * dh * (2 * H + 2 * Hk)
        elif mixer == "mlstm":
            dp = a["mlstm_proj_factor"] * d
            parts["mlstm"] = parts.get("mlstm", 0) + (
                d * 2 * dp + 3 * dp * dp + 2 * dp * a["num_heads"] + dp * d)
        else:
            raise ValueError(mixer)
        if ffn == "dense":
            parts["ffn"] = parts.get("ffn", 0) + 3 * d * a["d_ff"]
        elif ffn == "moe":
            parts["router"] = parts.get("router", 0) + d * a["num_experts"]
            parts["expert"] = parts.get("expert", 0) + 3 * d * a["moe_d_ff"]
        elif ffn != "none":
            raise ValueError(ffn)
    reps = a["num_layers"] // len(a["pattern"])
    return {k: v * reps for k, v in parts.items()}


def _per_token_matmul(a: dict) -> int:
    """Multiply-adds per token in the weights' matmuls, LM head included."""
    w = _layer_weights(a)
    experts = w.pop("expert", 0) * a.get("top_k", 0)
    return sum(w.values()) + experts + a["d_model"] * a["vocab_size"]


def decode_step(a: dict, live: list[int]) -> tuple[float, float]:
    """(FLOPs, bytes) one decode step needs for the active slots, where
    live[i] is how many positions slot i holds before this step's token.

    Bytes: every weight read once (embedding rows gathered; of a MoE
    layer's experts, the top_k that at least one token must use), the live
    keys and values read, the new ones written, the logits written."""
    T = len(live)
    if T == 0:
        return 0.0, 0.0
    wb = _BYTES[a["dtype"]]
    d, V = a["d_model"], a["vocab_size"]
    L = a["num_layers"]
    flops = 2.0 * T * _per_token_matmul(a)
    kv_read = kv_write = 0
    n_attn = sum(1 for m, _ in a["pattern"] if m == "attn") \
        * (L // len(a["pattern"]))
    if n_attn:
        H, Hk, dh = a["num_heads"], a["num_kv_heads"], a["head_dim"]
        ctx = [p + 1 for p in live]
        flops += n_attn * 4.0 * H * dh * sum(ctx)
        kv_read = n_attn * 2 * Hk * dh * wb * sum(live)
        kv_write = n_attn * 2 * Hk * dh * wb * T
    w = _layer_weights(a)
    experts = w.pop("expert", 0) * min(a.get("top_k", 0), a.get("num_experts", 0))
    router = w.pop("router", 0)
    weight_bytes = (sum(w.values()) + experts) * wb + router * 4 \
        + d * V * wb + T * d * wb
    return flops, float(weight_bytes + kv_read + kv_write + T * V * wb)


def train_step(a: dict, batch: int, seq: int) -> float:
    """FLOPs of one training step: forward and backward (3x the forward's
    matmuls), with mLSTM's chunkwise terms and causal attention; no
    recomputation counted."""
    d = a["d_model"]
    fwd = 2.0 * batch * seq * _per_token_matmul(a)
    reps = a["num_layers"] // len(a["pattern"])
    for mixer, _ in a["pattern"]:
        if mixer == "attn":
            H, dh = a["num_heads"], a["head_dim"]
            fwd += reps * batch * 4.0 * H * dh * seq * (seq + 1) / 2
        elif mixer == "mlstm":
            H = a["num_heads"]
            dh = a["mlstm_proj_factor"] * d // H
            c = min(a["ssm_chunk"], seq)
            n = seq // c
            intra = 4.0 * dh * c * (c + 1) / 2 * n        # q.k and (.)v, causal
            inter = 2.0 * c * dh * dh * (n - 1)           # q . C_prev
            state = 2.0 * c * dh * dh * (n - 1)           # C += k^T v
            fwd += reps * batch * H * (intra + inter + state)
    return 3.0 * fwd


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at its peaks: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def roofline_share(needed_s: float, busy_s: float) -> float | None:
    if busy_s <= 0 or needed_s <= 0:
        return None
    return float(100.0 * needed_s / busy_s)
