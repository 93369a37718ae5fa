"""jit'd wrapper for the sliding-window flash attention kernel.

Accepts the model-layer layout (B, S, H, D) and handles block-size
selection + the non-TPU fallback (oracle on CPU unless interpret=True is
forced for validation). On TPU a shape no block size divides is an
error, never a silent oracle run.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.swa_attention import ref
from repro.kernels.swa_attention.kernel import swa_attention_bhsd


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("window", "interpret"))
def swa_attention(q, k, v, window: int, *, interpret: bool = False):
    """q: (B, S, Hq, D); k, v: (B, S, Hkv, D) -> (B, S, Hq, Dv)."""
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    if not (_is_tpu() or interpret):
        out = ref.swa_attention_ref(qt, kt, vt, window)
    else:
        s = q.shape[1]
        block = 128
        while s % block or window % block:
            block //= 2
            if block < 8:
                if _is_tpu():
                    raise ValueError(
                        f"swa_attention: no block size >= 8 divides both "
                        f"seq {s} and window {window}; the kernel cannot "
                        f"run on this shape")
                out = ref.swa_attention_ref(qt, kt, vt, window)
                break
        else:
            out = swa_attention_bhsd(qt, kt, vt, window, block_q=block,
                                     block_k=block, interpret=interpret)
    return jnp.moveaxis(out, 1, 2)
