"""K-step SCAFFOLD local loop as ONE Pallas kernel (DESIGN.md §15).

The packed per-step path (ops.py) issues one ``pallas_call`` per dtype
group per *local step* — K launches per client round. This module fuses
the whole corrected local loop

    for k in 0..K-1:  y <- y - eta_k * (grad_k(y) + c - c_i)

into a single ``pallas_call`` with ``grid=(K,)``: the packed
``(rows, 128)`` parameter buffer is an *output* ref revisited by every
grid step, so it stays pinned in VMEM across all K steps, while the
per-step client batches stream HBM->VMEM through blocked input specs
(Pallas double-buffers the next block while the current one computes).
The per-step eta table rides as a ``(K,)`` scalar-prefetch operand
(``PrefetchScalarGridSpec``), which serves both the constant-eta solvers
(``sgd``, ``momentum``) and the scheduled one (``sgd_sched``) with the
same kernel.

The gradient must be kernel-expressible, so the megakernel starts with
the quadratics substrate (``data/quadratics.py``): per-sample loss
``0.5 y^T A y + b^T y`` whose batch-mean gradient is
``sym(mean A) y + mean b``. Dispatch is capability-based
(``LocalSolver.megakernel`` + the grad fn's ``megakernel_grad`` marker,
see ``core/local_solver.megakernel_incompatibility``); incompatible
combinations fall back loudly to the per-step path with a
``megakernel_fallback_reason`` in round metrics.

Off-TPU (and outside interpret mode) the loop falls through to
``ref.scaffold_local_loop_ref`` — a lean ``lax.scan`` with the
symmetrized batch-mean operators hoisted out of the loop, which is both
the oracle and the CPU fast path (it skips the per-step autodiff
machinery entirely).

All paths accumulate in fp32 and round once per step at the cast back to
the parameter dtype, matching the per-step fused kernels' discipline.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.scaffold_update import ops, ref
from repro.kernels.scaffold_update.kernel import LANES


def _grad_terms(y, A_ref, b_ref, rows: int):
    """In-kernel quadratics gradient pieces for grid step k.

    Returns ``(Av, bm)`` with ``Av = sym(mean_b A_k) @ y`` and
    ``bm = mean_b b_k``, both fp32 ``(rows, LANES)``.

    The matvec is a sum of 2-D ``(rows, 128) x (128, 128)`` matmuls, the
    only contraction shape the TPU compiler takes here: with ``B`` the
    ``(r', r)`` lane block of ``sym(mean A)``, output row ``r`` is
    ``sum_r' y[r'] @ B``. Masking ``y`` to row ``r'`` before the matmul
    leaves that product in row ``r'``; a sublane sum then moves it to
    row ``r``.
    """
    A = A_ref[0].astype(jnp.float32)  # (bsz, dp, dp)
    Am = jnp.mean(A, axis=0)
    Am = 0.5 * (Am + Am.T)  # autodiff of 0.5 y^T A y is the symmetric part
    bm = jnp.mean(b_ref[0].astype(jnp.float32), axis=0)  # (rows, LANES)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    y_rows = [jnp.where(row == r, y, 0.0) for r in range(rows)]
    Av = jnp.zeros((rows, LANES), jnp.float32)
    for r in range(rows):
        part = jnp.zeros((rows, LANES), jnp.float32)
        for rp in range(rows):
            part += jnp.dot(
                y_rows[rp],
                Am[rp * LANES:(rp + 1) * LANES, r * LANES:(r + 1) * LANES],
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        Av = jnp.where(row == r, jnp.sum(part, axis=0, keepdims=True), Av)
    return Av, bm


def _store_loss(loss_ref, k, loss):
    """Write step k's loss into row k of the resident ``(K, 128)`` loss
    block (a select over the whole block: no dynamic sublane store)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, loss_ref.shape, 0)
    loss_ref[...] = jnp.where(rows == k, loss, loss_ref[...])


def _local_loop_kernel(eta_ref, y0_ref, corr_ref, A_ref, b_ref,
                       y_ref, loss_ref, *, rows: int):
    """One grid step k of the fused sgd/sgd_sched local loop."""
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _():
        y_ref[...] = y0_ref[...]
        loss_ref[...] = jnp.zeros_like(loss_ref)

    y = y_ref[...].astype(jnp.float32)
    Av, bm = _grad_terms(y, A_ref, b_ref, rows)
    loss = 0.5 * jnp.sum(Av * y) + jnp.sum(bm * y)
    _store_loss(loss_ref, k, loss)
    g = Av + bm + corr_ref[...].astype(jnp.float32)
    y_ref[...] = (y - eta_ref[k] * g).astype(y_ref.dtype)


def _momentum_loop_kernel(eta_ref, y0_ref, corr_ref, m0_ref, A_ref, b_ref,
                          y_ref, m_ref, loss_ref, *, rows: int, beta: float):
    """One grid step k of the fused heavy-ball local loop:
    m <- beta*m + (g + corr);  y <- y - eta_k*m, with the fp32 momentum
    slot pinned in VMEM alongside the parameter buffer."""
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _():
        y_ref[...] = y0_ref[...]
        m_ref[...] = m0_ref[...]
        loss_ref[...] = jnp.zeros_like(loss_ref)

    y = y_ref[...].astype(jnp.float32)
    Av, bm = _grad_terms(y, A_ref, b_ref, rows)
    loss = 0.5 * jnp.sum(Av * y) + jnp.sum(bm * y)
    _store_loss(loss_ref, k, loss)
    g = Av + bm + corr_ref[...].astype(jnp.float32)
    m = beta * m_ref[...] + g
    m_ref[...] = m
    y_ref[...] = (y - eta_ref[k] * m).astype(y_ref.dtype)


def scaffold_local_loop_2d(eta_table, y0, corr, A, b, *,
                           interpret: bool = False):
    """All K corrected sgd steps in one ``pallas_call``.

    ``y0``/``corr``: packed ``(rows, 128)``; ``A``: ``(K, bsz, dp, dp)``;
    ``b``: ``(K, bsz, dp)`` with ``dp = rows*128``; ``eta_table``:
    ``(K,)`` fp32 scalar-prefetch operand. Returns ``(y_K, losses)`` with
    ``losses`` shaped ``(K,)``.
    """
    K, bsz, dp = A.shape[0], A.shape[1], A.shape[2]
    rows = y0.shape[0]
    whole = pl.BlockSpec((rows, LANES), lambda k, _: (0, 0))
    loss_block = pl.BlockSpec((K, LANES), lambda k, _: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(K,),
        in_specs=[
            whole,
            whole,
            pl.BlockSpec((1, bsz, dp, dp), lambda k, _: (k, 0, 0, 0)),
            pl.BlockSpec((1, bsz, rows, LANES), lambda k, _: (k, 0, 0, 0)),
        ],
        out_specs=(whole, loss_block),
    )
    y_out, losses = pl.pallas_call(
        partial(_local_loop_kernel, rows=rows),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((rows, LANES), y0.dtype),
                   jax.ShapeDtypeStruct((K, LANES), jnp.float32)),
        interpret=interpret,
    )(eta_table, y0, corr, A, b.reshape(K, bsz, rows, LANES))
    return y_out, losses[:, 0]


def scaffold_momentum_local_loop_2d(eta_table, y0, corr, m0, A, b, *,
                                    beta: float, interpret: bool = False):
    """All K heavy-ball steps in one ``pallas_call``; ``m0`` is the
    packed fp32 ``(rows, 128)`` momentum slot. Returns
    ``(y_K, m_K, losses)``."""
    K, bsz, dp = A.shape[0], A.shape[1], A.shape[2]
    rows = y0.shape[0]
    whole = pl.BlockSpec((rows, LANES), lambda k, _: (0, 0))
    loss_block = pl.BlockSpec((K, LANES), lambda k, _: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(K,),
        in_specs=[
            whole,
            whole,
            whole,
            pl.BlockSpec((1, bsz, dp, dp), lambda k, _: (k, 0, 0, 0)),
            pl.BlockSpec((1, bsz, rows, LANES), lambda k, _: (k, 0, 0, 0)),
        ],
        out_specs=(whole, whole, loss_block),
    )
    y_out, m_out, losses = pl.pallas_call(
        partial(_momentum_loop_kernel, rows=rows, beta=float(beta)),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((rows, LANES), y0.dtype),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((K, LANES), jnp.float32)),
        interpret=interpret,
    )(eta_table, y0, corr, m0, A, b.reshape(K, bsz, rows, LANES))
    return y_out, m_out, losses[:, 0]


def _pad_lanes(v, dp: int):
    """1-D ``(d,)`` -> packed ``(dp//128, 128)`` with lane-only padding."""
    return jnp.pad(v, (0, dp - v.shape[0])).reshape(-1, LANES)


def scaffold_local_loop(y, correction, batches, eta_table, *, m=None,
                        beta: float = 0.0, interpret: bool = False):
    """Tree-level megakernel entry: the whole K-step local loop.

    ``y`` is a params pytree with a single 1-D leaf (the quadratics
    substrate — callers gate on ``megakernel_incompatibility`` first);
    ``correction`` is a like-shaped pytree or None; ``batches`` is
    ``{"A": (K, bsz, d, d), "b": (K, bsz, d)}``; ``eta_table`` is the
    ``(K,)`` per-step learning-rate table. Pass ``m`` (params-shaped fp32
    pytree) + ``beta`` for the heavy-ball variant.

    Returns ``(y_K, m_K | None, losses)`` with ``losses`` shaped ``(K,)``.
    Off-TPU and outside interpret mode this runs the lean
    :func:`ref.scaffold_local_loop_ref` scan instead of the kernel.
    """
    interpret = bool(interpret or ops._FORCE_INTERPRET)
    leaves, treedef = jax.tree.flatten(y)
    (x,) = leaves
    corr_leaf = None if correction is None else (
        treedef.flatten_up_to(correction)[0])
    m_leaf = None if m is None else treedef.flatten_up_to(m)[0]
    A, bvec = batches["A"], batches["b"]

    if not (ops._is_tpu() or interpret):
        y_out, m_out, losses = ref.scaffold_local_loop_ref(
            x, corr_leaf, eta_table, A, bvec, m=m_leaf, beta=beta)
    else:
        d = x.shape[0]
        dp = -(-d // LANES) * LANES
        pad = dp - d
        y2 = _pad_lanes(x, dp)
        c2 = (jnp.zeros((dp // LANES, LANES), x.dtype) if corr_leaf is None
              else _pad_lanes(corr_leaf, dp))
        Ap = jnp.pad(A, ((0, 0), (0, 0), (0, pad), (0, pad)))
        bp = jnp.pad(bvec, ((0, 0), (0, 0), (0, pad)))
        eta32 = jnp.asarray(eta_table, jnp.float32)
        if m_leaf is None:
            y2_out, losses = scaffold_local_loop_2d(
                eta32, y2, c2, Ap, bp, interpret=interpret)
            m_out = None
        else:
            m2 = _pad_lanes(m_leaf.astype(jnp.float32), dp)
            y2_out, m2_out, losses = scaffold_momentum_local_loop_2d(
                eta32, y2, c2, m2, Ap, bp, beta=beta, interpret=interpret)
            m_out = m2_out.reshape(-1)[:d]
        y_out = y2_out.reshape(-1)[:d]

    y_tree = jax.tree.unflatten(treedef, [y_out])
    m_tree = None if m_out is None else jax.tree.unflatten(treedef, [m_out])
    return y_tree, m_tree, losses
