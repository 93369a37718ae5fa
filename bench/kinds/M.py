"""M: a Mamba-2 layer (arXiv:2405.21060), x += SSD-block(RMSNorm(x)),
with one B/C group. Keys of ``shapes``: ``ssm_d_state``,
``ssm_head_dim``, ``ssm_expand``, ``ssm_conv``.
"""
import flops
import harness
from reference import lm

LETTER = "M"
USES_MOE = False


def program_shapes(cfg):
    return harness.ssm_shapes(cfg)


def layer(s, p, lin, x, quant, ssd):
    return x + lm.mamba(s, p["mamba"], lin, lm.rms_norm(x, p["ln_attn"]["scale"]),
                        quant, ssd)


def matmuls(s):
    return flops.ssd_matmuls(s)


def other_flops(s, seq):
    return flops.ssd_per_token(s) * seq
