"""granite-moe-1b-a400m [moe]: 24L d1024 16H(kv8) expert_ff=512 v49155
(padded 49280), 32 experts top-8.
[hf:ibm-granite/granite-3.0-1b-a400m-base; hf]

Widths as the source's `config.json` gives them: hidden_size 1024,
num_hidden_layers 24, num_attention_heads 16, num_key_value_heads 8 (head
dim 1024 / 16 = 64), intermediate_size 512 (each expert's width),
num_local_experts 32, num_experts_per_tok 8, rope_theta 10000,
rms_norm_eps 1e-6, hidden_act silu, vocab_size 49155; every layer is an
MoE, softmax router with the top-k weights renormalised, no shared expert.

Departures from the published model (none changes a shape or what a step
costs): granite's four scalars are left out, embedding_multiplier 12 (here
x sqrt(d_model)), attention_multiplier 1/64 (here 1/sqrt(head_dim) = 1/8),
residual_multiplier 0.22 (here 1) and logits_scaling 6 (here 1); and
tie_word_embeddings is true there, while here the LM head is a weight of
its own (the same bytes read per decode step, 50M more parameters).
"""
import dataclasses
from repro.models.model import ModelConfig

FULL = ModelConfig(
    name="granite-moe-1b-a400m", family="moe",
    num_layers=24, d_model=1024, num_heads=16, num_kv_heads=8, head_dim=64,
    d_ff=512, vocab_size=49155, pattern=(("attn", "moe"),),
    num_experts=32, top_k=8, num_shared_experts=0, moe_d_ff=512,
    rope_theta=10000.0, ffn_act="silu",
)

SMOKE = dataclasses.replace(
    FULL, num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=64, moe_d_ff=64, num_experts=8, top_k=2, vocab_size=250,
    vocab_pad_multiple=16, ssm_chunk=8,
)
