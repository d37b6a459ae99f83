"""FLOP and byte functions against hand counts, at CPU-test and published
shapes."""
import pytest

from bench import flops, spec
from bench.tests.small import SMALL_ARCH, SMALL_OFFLINE


def _arch(config, part, small=None):
    a = dict(spec.load_json(spec.BENCH / "configs" / f"{config}.json")[part]["arch"])
    a.update(small or {})
    return a


def test_decode_step_small_danube():
    a = _arch("danube-xlstm", "online", SMALL_ARCH["danube-xlstm"])
    # per layer: attention 64*16*(2*4 + 2*2) = 12288, SwiGLU 3*64*128 =
    # 24576; two layers; LM head 64*256
    per_token = 2 * (12288 + 24576) + 64 * 256
    f, b = flops.decode_step(a, [3, 0])
    # two tokens; attention over 4 and 1 positions in each of two layers
    assert f == 2 * 2 * per_token + 2 * 4 * 4 * 16 * (4 + 1)
    weights = (2 * (12288 + 24576) + 64 * 256) * 2 + 2 * 64 * 2
    kv = 2 * 2 * 2 * 16 * 2 * (3 + 2)        # layers, k+v, Hk, dh, bytes
    assert b == weights + kv + 2 * 256 * 2


def test_decode_step_full_danube():
    a = _arch("danube-xlstm", "online")
    layer = 2560 * 80 * (64 + 16) + 3 * 2560 * 6912
    assert layer == 69_468_160
    f, b = flops.decode_step(a, [100] * 8)
    assert f == 2 * 8 * (24 * layer + 2560 * 32000) \
        + 24 * 4 * 32 * 80 * 8 * 101
    assert b == (24 * layer + 2560 * 32000) * 2 + 8 * 2560 * 2 \
        + 24 * 2 * 8 * 80 * 2 * (800 + 8) + 8 * 32000 * 2
    # about 3.5 GB: 4.3 ms at 819 GB/s, far above the FLOP bound
    assert flops.least_time(f, b, {"bf16_flops": 197e12,
                                   "hbm_bytes_per_s": 819e9}) \
        == pytest.approx(b / 819e9)


# granite-3.0-1b-a400m's published widths: a MoE decode, top 8 of 32
GRANITE = {"num_layers": 24, "d_model": 1024, "num_heads": 16,
           "num_kv_heads": 8, "head_dim": 64, "d_ff": 512,
           "vocab_size": 49155, "pattern": [["attn", "moe"]],
           "num_experts": 32, "top_k": 8, "num_shared_experts": 0,
           "moe_d_ff": 512, "dtype": "bfloat16"}


def test_decode_step_moe_reads_top_k_experts():
    a = GRANITE
    f, b = flops.decode_step(a, [0])
    attn = 1024 * 64 * (32 + 16)
    expert = 3 * 1024 * 512
    router = 1024 * 32
    assert f == 2 * (24 * (attn + router + 8 * expert) + 1024 * 49155) \
        + 24 * 4 * 16 * 64 * 1
    assert b == (24 * (attn + 8 * expert) + 1024 * 49155) * 2 \
        + 24 * router * 4 + 1024 * 2 + 24 * 2 * 8 * 64 * 2 + 49155 * 2


def test_train_step_small_xlstm():
    a = _arch("danube-xlstm", "offline", SMALL_OFFLINE)
    B, S = 4, 16
    dp, H, dh, c = 128, 4, 32, 8
    layer = 64 * 2 * dp + 3 * dp * dp + 2 * dp * H + dp * 64
    per_token = 2 * layer + 64 * 256
    # per head and row: two chunks of 8; causal q.k and (.)v inside each;
    # q.C and the state update across the one boundary
    chunk = 4 * dh * (c * (c + 1) // 2) * 2 + 2 * c * dh * dh + 2 * c * dh * dh
    assert flops.train_step(a, B, S) == 3 * (2 * B * S * per_token
                                             + 2 * B * H * chunk)


def test_train_step_full_xlstm_is_near_6n():
    a = _arch("danube-xlstm", "offline")
    f = flops.train_step(a, 8, 512)
    n = 24 * (1024 * 4096 + 3 * 2048 ** 2 + 2 * 2048 * 4 + 2048 * 1024) \
        + 1024 * 50304
    assert 6 * n * 4096 < f < 1.2 * 6 * n * 4096


def test_roofline_share_reads_nothing_without_time():
    assert flops.roofline_share(1.0, 0.0) is None
    assert flops.roofline_share(0.5, 1.0) == 50.0
