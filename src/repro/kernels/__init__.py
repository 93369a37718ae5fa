"""Pallas TPU kernels for the system's compute hot-spots (the paper itself
has no kernel-level contribution — DESIGN.md §6):

  scaffold_update   fused control-variate local step y - η(g + c - c_i)
  swa_attention     sliding-window flash attention with its backward, O(S·W)
                    band: JAX's splash-attention kernel, run by W layers on
                    a TPU

Each ships ops.py (jit'd wrapper with CPU fallback) and ref.py (pure-jnp
oracle); scaffold_update's own kernels are in kernel.py / megakernel.py
(pl.pallas_call + BlockSpec VMEM tiling). All are validated in interpret
mode over shape/dtype sweeps (tests/test_kernels.py).
"""
from repro.kernels.scaffold_update import scaffold_update  # noqa: F401
from repro.kernels.swa_attention import swa_attention  # noqa: F401
