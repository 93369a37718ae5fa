"""M_moe: a granite-4.0-h Mamba-2 layer, the Mamba-2 mixer and then the
MoE tail (``reference/moe.py``), each residual branch times the model's
``residual_multiplier``:

    x += r * SSD-block(RMSNorm(x));  x += r * tail(RMSNorm(x)).

Keys of ``shapes``: the SSD keys, the MoE keys, ``residual_multiplier``;
``embedding_multiplier`` and ``logits_scaling``, which ``lm.loss``
applies, are checked against the program here too.
"""
import flops
import harness
from reference import lm, moe

LETTER = "M"
USES_MOE = True


def program_shapes(cfg):
    return {**harness.ssm_shapes(cfg), **moe.program_shapes(cfg)}


def layer(s, p, lin, x, quant, ssd):
    r = s["residual_multiplier"]
    h = lm.rms_norm(x, p["ln_attn"]["scale"])
    x = x + r * lm.mamba(s, p["mamba"], lin, h, quant, ssd)
    return x + r * moe.tail(s, p["moe"], lin, lm.rms_norm(x, p["ln_mlp"]["scale"]), quant)


def matmuls(s):
    return [("mamba." + n, i, o) for n, i, o in flops.ssd_matmuls(s)] + moe.matmuls(s)


def other_flops(s, seq):
    return flops.ssd_per_token(s) * seq + moe.routed_flops(s, seq)
