"""granite-4.0-h-small [hybrid] — Mamba-2 layers with attention at 5, 15,
25 and 35, every layer ending in a 72-expert top-10 MoE plus a shared
expert; NoPE attention scaled by 1/128, embedding, residual and logit
multipliers [hf:ibm-granite/granite-4.0-h-small config.json,
model_type granitemoehybrid]."""
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    arch_type="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,  # intermediate_size: the routed expert width
    vocab_size=100352,
    layer_pattern="MMMMMFMMMM",  # layer_types: attention at 5 of every 10
    rope_theta=None,  # position_embedding_type "nope"
    attention_multiplier=0.0078125,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    mlp_kind="silu_gated",
    moe=MoEConfig(
        num_experts=72,
        top_k=10,
        expert_d_ff=768,
        num_shared_experts=1,
        shared_d_ff=1536,
    ),
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, conv_kernel=4, chunk_size=256,
                  n_groups=1),
    tie_embeddings=True,
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
    citation="hf:ibm-granite/granite-4.0-h-small",
)


def reduced() -> ModelConfig:
    import dataclasses

    return dataclasses.replace(
        CONFIG,
        num_layers=2,
        d_model=256,
        num_heads=4,
        num_kv_heads=2,
        head_dim=64,
        d_ff=128,
        vocab_size=512,
        layer_pattern="MF",
        attention_multiplier=1 / 64,
        moe=MoEConfig(num_experts=4, top_k=2, expert_d_ff=128, num_shared_experts=1,
                      shared_d_ff=128),
        moe_impl="gshard",  # ragged_dot has no vmap rule for the client axis
        ssm=SSMConfig(d_state=32, head_dim=64, expand=2, conv_kernel=4, chunk_size=32),
        param_dtype="float32",
        compute_dtype="float32",
    )
