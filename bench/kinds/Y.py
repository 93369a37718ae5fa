"""Y: hymba's layer (arXiv:2411.13676), sliding-window GQA attention and
SSD heads in parallel on one normed input, then a SwiGLU MLP:

    h = RMSNorm(x);  x += 0.5 * (SWA-GQA-attention(h) + SSD(h));
    x += SwiGLU(RMSNorm(x)).

Departures kept from the program: sliding-window attention in every
layer, no meta tokens, no KV sharing, SSD heads (configuration file,
``assumed``). Keys of ``shapes``: ``n_heads``, ``n_kv_heads``,
``head_dim``, ``d_ff``, ``window``, ``rope_theta`` and the SSD keys.
"""
import flops
import harness
from reference import lm

LETTER = "Y"
USES_MOE = False


def program_shapes(cfg):
    return {"n_heads": cfg.num_heads, "n_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff, "window": cfg.sliding_window,
            "rope_theta": cfg.rope_theta, **harness.ssm_shapes(cfg)}


def layer(s, p, lin, x, quant, ssd):
    h = lm.rms_norm(x, p["ln_attn"]["scale"])
    x = x + 0.5 * (lm.attention(s, lin, h, quant, rope_theta=s["rope_theta"],
                                window=s["window"])
                   + lm.mamba(s, p["mamba"], lin, h, quant, ssd))
    return x + lm.mlp(lin, lm.rms_norm(x, p["ln_mlp"]["scale"]))


def matmuls(s):
    return flops.attention_matmuls(s) + flops.mlp_matmuls(s) + flops.ssd_matmuls(s)


def other_flops(s, seq):
    return (flops.ssd_per_token(s) * seq
            + flops.attention_per_sequence(s, seq, s["window"]))
