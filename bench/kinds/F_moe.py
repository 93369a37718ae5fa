"""F_moe: a granite-4.0-h attention layer, full causal GQA with no
position embedding (NoPE) and scores times ``attention_multiplier``,
then the MoE tail (``reference/moe.py``), each residual branch times the
model's ``residual_multiplier``:

    x += r * GQA-attention(RMSNorm(x));  x += r * tail(RMSNorm(x)).

Keys of ``shapes``: ``n_heads``, ``n_kv_heads``, ``head_dim``,
``attention_multiplier``, the MoE keys, ``residual_multiplier``;
``embedding_multiplier`` and ``logits_scaling`` as ``M_moe``.
"""
import flops
from reference import lm, moe

LETTER = "F"
USES_MOE = True


def program_shapes(cfg):
    if cfg.mla is not None or cfg.rope_theta is not None:
        raise ValueError("an F layer with MLA or RoPE is another kind")
    return {"n_heads": cfg.num_heads, "n_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "attention_multiplier": cfg.attention_multiplier,
            **moe.program_shapes(cfg)}


def layer(s, p, lin, x, quant, ssd):
    r = s["residual_multiplier"]
    h = lm.rms_norm(x, p["ln_attn"]["scale"])
    x = x + r * lm.attention(s, lin, h, quant, scale=s["attention_multiplier"])
    return x + r * moe.tail(s, p["moe"], lin, lm.rms_norm(x, p["ln_mlp"]["scale"]), quant)


def matmuls(s):
    return [("attn." + n, i, o) for n, i, o in flops.attention_matmuls(s)] + moe.matmuls(s)


def other_flops(s, seq):
    return flops.attention_per_sequence(s, seq, seq) + moe.routed_flops(s, seq)
