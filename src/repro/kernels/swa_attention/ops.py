"""Sliding-window causal attention: the splash-attention kernel on a TPU.

Accepts the model-layer layout (B, S, H, D). On a TPU (or with
``interpret=True``) it runs ``jax.experimental.pallas.ops.tpu.
splash_attention``: a block-sparse flash kernel whose ``LocalMask`` keeps
the band ``0 <= q_pos - k_pos < window`` and skips every kv block outside
it, with GQA by kv-head index (kv heads are never repeated), a saved
logsumexp and its own backward kernel (dq and dkv fused). Elsewhere it
runs the jnp oracle (``ref.py``). On a TPU a shape the kernel cannot take
is an error, never a silent oracle run.
"""
from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.kernels.swa_attention import ref

LANES = 128       # a kernel block is a whole number of lane tiles
MAX_BLOCK = 512


def _is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def block_size(seq: int, window: int) -> int | None:
    """The kernel's q and kv block, forward and backward: the largest of
    512, 256, 128 that divides both ``seq`` and ``window``, else 128 (the
    mask is exact inside a block); None where 128 does not divide ``seq``.
    At hymba-1.5b's shapes (seq 2048, window 1024, head 64) 512 with the
    fused backward took 40.0 ms for 32 layers' forward, remat'd forward
    and backward on a TPU v5e, against 46.4 with separate dq and dkv
    kernels and 71.8 / 161.0 at blocks of 256 / 128."""
    if seq % LANES:
        return None
    return max(math.gcd(seq, window, MAX_BLOCK), LANES)


def takes(seq: int, window: int) -> bool:
    """Whether a W layer of ``seq`` tokens runs the kernel: on a TPU, with
    kv blocks that tile the window, at the lengths where the jnp band runs
    (``seq >= 2 * window``)."""
    return (_is_tpu() and seq % LANES == 0 and window % LANES == 0
            and seq >= 2 * window)


@lru_cache(maxsize=16)
def _kernel(seq: int, heads: int, window: int, interpret: bool):
    """The splash kernel for one shape, its mask and ``MaskInfo`` built
    once, at trace time, and reused by every layer and call."""
    block = block_size(seq, window)
    mask = splash.MultiHeadMask(
        [splash.LocalMask((seq, seq), (window - 1, 0), 0)] * heads)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_q_dkv=block, block_kv_dkv=block,
        use_fused_bwd_kernel=True)
    # concrete arrays, not tracers of the trace that first asks for the
    # kernel: the cached kernel is reused by later traces
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                      q_seq_shards=1, interpret=interpret)


@partial(jax.jit, static_argnames=("window", "interpret"))
def swa_attention(q, k, v, window: int, *, interpret: bool = False):
    """q: (B, S, Hq, D); k, v: (B, S, Hkv, D) -> (B, S, Hq, Dv)."""
    _, s, hq, d = q.shape
    qt, kt, vt = (jnp.moveaxis(a, 1, 2) for a in (q, k, v))
    if not (_is_tpu() or interpret):
        out = ref.swa_attention_ref(qt, kt, vt, window)
    elif block_size(s, window) is None:
        if _is_tpu():
            raise ValueError(
                f"swa_attention: seq {s} is not a multiple of {LANES}; the "
                f"kernel cannot run on this shape")
        out = ref.swa_attention_ref(qt, kt, vt, window)
    else:
        kernel = _kernel(s, hq, window, interpret)
        qt = qt * jnp.asarray(1.0 / math.sqrt(d), q.dtype)
        out = jax.vmap(kernel)(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)
