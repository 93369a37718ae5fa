"""F: a pre-norm transformer layer with full causal GQA attention under
RoPE, then a SwiGLU MLP (the program's ``F`` layer without MLA or MoE):

    x += GQA-attention(RMSNorm(x));  x += SwiGLU(RMSNorm(x)).

Keys of ``shapes``: ``n_heads``, ``n_kv_heads``, ``head_dim``, ``d_ff``,
``rope_theta``.
"""
import flops
from reference import lm

LETTER = "F"
USES_MOE = False


def program_shapes(cfg):
    if cfg.mla is not None or cfg.mlp_kind != "silu_gated":
        raise ValueError(f"an F layer with MLA or a {cfg.mlp_kind} MLP is another kind")
    return {"n_heads": cfg.num_heads, "n_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff, "rope_theta": cfg.rope_theta}


def layer(s, p, lin, x, quant, ssd):
    h = lm.rms_norm(x, p["ln_attn"]["scale"])
    x = x + lm.attention(s, lin, h, quant, rope_theta=s["rope_theta"])
    return x + lm.mlp(lin, lm.rms_norm(x, p["ln_mlp"]["scale"]))


def matmuls(s):
    return flops.attention_matmuls(s) + flops.mlp_matmuls(s)


def other_flops(s, seq):
    return flops.attention_per_sequence(s, seq, seq)
