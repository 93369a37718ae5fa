"""The client local-update layer: a pluggable ``LocalSolver`` registry.

Algorithm 1 lines 7-11 treat the client's inner loop as plain SGD plus a
drift correction,

    y <- y - eta_l * (g_i(y) + correction(y))

and that is the registered ``sgd`` solver — bit-for-bit the pre-registry
path. The fourth registry (after Algorithm / ServerOptimizer /
Compressor, DESIGN.md §12) makes the *local* optimizer a strategy too:

  ``sgd``        the paper's corrected step (DESIGN.md §3); with
                 ``use_fused_update`` it routes through the packed
                 Pallas ``scaffold_update`` path (one ``pallas_call``
                 per dtype group per step, DESIGN.md §8).
  ``momentum``   client heavy-ball on the corrected gradient:
                 m <- beta*m + (g + corr); y <- y - eta_l*m. Stateful —
                 per-client slots persist across rounds in the client
                 store (Mime-style local momentum; "Momentum Benefits
                 Non-IID Federated Learning", PAPERS.md). Has its own
                 fused kernel variant (``scaffold_momentum_update``).
  ``adam``       local adaptivity (Mime/FedAdam-style client step,
                 Reddi et al. 2021): fp32 m/v moments + a step counter,
                 persisted per client like the momentum slot.
  ``sgd_sched``  sgd with a per-local-step eta_l table from
                 ``optim/schedules.local_eta_table``
                 (``spec.eta_l_schedule``: constant | warmup | cosine).

A solver is two hooks over an explicit, *fixed-shape* slot pytree:

    init(spec, x)                     -> slots
    step(spec, slots, y, grads,
         correction, t_local)         -> (y', slots')

Per-step state is an explicit scan-carryable pytree instead of a
closed-over constant, which is what lets slots ride ``lax.scan`` (the
K-step loop *and* the scanned multi-round engine), vmap over clients,
and live as ``(N, ...)`` rows of the device-resident client store when
``stateful`` (DESIGN.md §12). ``t_local`` is the within-round step index
(0..K-1, traced); cross-round counters (adam's ``t``) live in the slots.
Two optional hooks refine the contract: ``shard_slots`` applies the
caller's param-tree sharding constraint to param-shaped slot entries
(the FSDP carry pin), and ``check_steps`` validates slots against the
actual scan length at trace time (``sgd_sched`` rejects a
``spec.local_steps`` / batches mismatch loudly).

Solvers without a fused kernel variant (``adam``, ``sgd_sched`` — the
scheduled eta is a traced scalar, the fused kernels take a static eta)
silently take their jnp path under ``use_fused_update``; the flag is a
kernel routing hint, never a semantics change.

``local_sgd`` remains the back-compat surface of the seed: a thin
wrapper over :func:`run_local_steps` with the ``sgd`` solver, returning
``(y_K, mean loss)`` — trajectories are bit-for-bit identical
(tests/test_local_solvers.py).
"""
from __future__ import annotations

import types
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.util import uscan


# ---------------------------------------------------------------------------
# the solver strategy + registry
# ---------------------------------------------------------------------------


class LocalSolver:
    """One client-side local optimizer = init/step over explicit slots.

    stateful: the slots are per-client optimizer state worth persisting
              across rounds — the engine then carries them in
              ``ClientRoundState.solver_slots`` (leaves ``(S, ...)``)
              and as ``"solver"`` rows of the ``(N, ...)`` client store
              (host ``ClientStateStore`` / scanned device store).
              Stateless solvers may still use slots *within* a round
              (``sgd_sched``'s eta table); those are rebuilt by ``init``
              every round and never stored.
    """

    name: str = ""
    stateful: bool = False
    #: the solver step is expressible inside the K-step Pallas megakernel
    #: (kernels/scaffold_update/megakernel.py) — see
    #: :func:`megakernel_incompatibility` for the full dispatch gate
    megakernel: bool = False

    def init(self, spec, x) -> Any:
        """Fresh slots for a client holding model ``x`` (zeros for a
        client that has never been sampled — ``ClientStateStore`` and the
        device store zero-fill unsampled rows, so ``init`` must be
        all-zeros for stateful solvers)."""
        return {}

    def step(self, spec, slots, y, grads, correction, t_local, *,
             use_fused_update: bool = False) -> Tuple[Any, Any]:
        """One local update: ``(y, slots) -> (y', slots')``.

        ``grads`` may carry fp32 leaves even for sub-fp32 params (the
        FedProx prox term is accumulated in fp32 — see
        :func:`run_local_steps`); ``correction`` is the algorithm's
        per-round constant (SCAFFOLD's ``c - c_i``) or None. Slot
        shapes/dtypes must be invariant under ``step`` (scan carry).
        """
        raise NotImplementedError

    def shard_slots(self, shard_fn, slots):
        """Pin the param-shaped slot entries to the param sharding.

        ``shard_fn`` is the caller's *param-tree* constraint (the FSDP
        carry pin of :func:`run_local_steps`) — it cannot be applied to
        the slot tree wholesale because slots nest param-like trees
        under slot keys (momentum's ``{"m": <params>}``), so solvers
        with param-sized slots override this to apply it per entry.
        Without the pin, GSPMD can replicate the model-sized fp32
        moments per device inside the scan, the exact hazard ``shard_fn``
        exists to prevent. Default: no param-shaped slots, pass through.
        """
        return slots

    def check_steps(self, spec, slots, k_steps: int) -> None:
        """Trace-time validation hook: ``k_steps`` is the actual scan
        length (the batches' leading dim). Solvers whose slots are sized
        by ``spec.local_steps`` override this to fail loudly on a
        mismatch instead of silently clamping an index."""


class SGDSolver(LocalSolver):
    """The paper's corrected local step (eq. 3) — the pre-registry path,
    preserved bit-for-bit including the fused-kernel routing."""

    name = "sgd"
    megakernel = True

    def step(self, spec, slots, y, grads, correction, t_local, *,
             use_fused_update: bool = False):
        eta = spec.eta_l
        if correction is not None:
            if use_fused_update:
                from repro.kernels.scaffold_update import ops as fused_ops

                y_new = fused_ops.scaffold_update_packed(
                    y, grads, correction, eta)
            else:
                y_new = jax.tree.map(
                    lambda yy, gg, cc: (yy - eta * (gg + cc)).astype(yy.dtype),
                    y, grads, correction,
                )
        else:
            y_new = jax.tree.map(
                lambda yy, gg: (yy - eta * gg).astype(yy.dtype), y, grads
            )
        return y_new, slots


class MomentumSolver(LocalSolver):
    """Client heavy-ball on the corrected gradient:
    m <- beta*m + (g + corr);  y <- y - eta_l * m.

    beta is ``spec.local_momentum``; the slot ``m`` is fp32 (like the
    server optimizer moments) and persists per client across rounds.
    With ``use_fused_update`` and an active correction the whole update
    runs the packed Pallas momentum kernel — still one ``pallas_call``
    per dtype group per step, now 4 reads + 2 writes (DESIGN.md §12)."""

    name = "momentum"
    stateful = True
    megakernel = True  # fused heavy-ball slot pinned in VMEM

    def init(self, spec, x):
        return {"m": jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), x)}

    def shard_slots(self, shard_fn, slots):
        return {"m": shard_fn(slots["m"])}

    def step(self, spec, slots, y, grads, correction, t_local, *,
             use_fused_update: bool = False):
        eta, beta = spec.eta_l, spec.local_momentum
        if use_fused_update and correction is not None:
            from repro.kernels.scaffold_update import ops as fused_ops

            y_new, m_new = fused_ops.scaffold_momentum_update_packed(
                y, grads, correction, slots["m"], eta, beta)
            return y_new, {"m": m_new}
        if correction is not None:
            m_new = jax.tree.map(
                lambda mm, gg, cc: beta * mm + (gg.astype(jnp.float32)
                                                + cc.astype(jnp.float32)),
                slots["m"], grads, correction,
            )
        else:
            m_new = jax.tree.map(
                lambda mm, gg: beta * mm + gg.astype(jnp.float32),
                slots["m"], grads,
            )
        y_new = jax.tree.map(
            lambda yy, mm: (yy.astype(jnp.float32) - eta * mm).astype(yy.dtype),
            y, m_new,
        )
        return y_new, {"m": m_new}


class AdamSolver(LocalSolver):
    """Local-adaptivity client step (Mime / FedAdam-style, Reddi et al.
    2021 applied at the client): Adam on the corrected gradient with fp32
    m/v moments and a per-client step counter, all persisted across
    rounds. beta1 = ``spec.local_momentum``, beta2 = ``spec.local_beta2``.
    No fused variant — ``use_fused_update`` takes the jnp path."""

    name = "adam"
    stateful = True
    eps = 1e-8

    def init(self, spec, x):
        f32 = lambda a: jnp.zeros(a.shape, jnp.float32)  # noqa: E731
        return {
            "m": jax.tree.map(f32, x),
            "v": jax.tree.map(f32, x),
            "t": jnp.zeros((), jnp.int32),
        }

    def shard_slots(self, shard_fn, slots):
        return {"m": shard_fn(slots["m"]), "v": shard_fn(slots["v"]),
                "t": slots["t"]}

    def step(self, spec, slots, y, grads, correction, t_local, *,
             use_fused_update: bool = False):
        b1, b2 = spec.local_momentum, spec.local_beta2
        if correction is not None:
            g32 = jax.tree.map(
                lambda gg, cc: gg.astype(jnp.float32)
                + cc.astype(jnp.float32), grads, correction)
        else:
            g32 = jax.tree.map(lambda gg: gg.astype(jnp.float32), grads)
        t = slots["t"] + 1
        m_new = jax.tree.map(
            lambda m, g: b1 * m + (1.0 - b1) * g, slots["m"], g32)
        v_new = jax.tree.map(
            lambda v, g: b2 * v + (1.0 - b2) * jnp.square(g),
            slots["v"], g32)
        bc1 = 1.0 - b1 ** t.astype(jnp.float32)
        bc2 = 1.0 - b2 ** t.astype(jnp.float32)
        y_new = jax.tree.map(
            lambda yy, m, v: (
                yy.astype(jnp.float32)
                - spec.eta_l * (m / bc1) / (jnp.sqrt(v / bc2) + self.eps)
            ).astype(yy.dtype),
            y, m_new, v_new,
        )
        return y_new, {"m": m_new, "v": v_new, "t": t}


class ScheduledSGDSolver(LocalSolver):
    """sgd with a per-local-step eta_l schedule. The K schedule values
    (``spec.eta_l_schedule`` through ``optim.schedules.local_eta_table``)
    are baked into the slots as a (K,) fp32 table at trace time, so the
    traced step counter just indexes it inside the scan. Stateless: the
    schedule restarts every round, nothing persists per client. The
    traced eta can't feed the static-eta fused kernels, so
    ``use_fused_update`` takes the jnp path."""

    name = "sgd_sched"
    # the megakernel streams the (K,) eta table as a scalar-prefetch
    # operand, so (unlike the per-step fused kernels) the traced eta is
    # no obstacle there
    megakernel = True

    def init(self, spec, x):
        from repro.optim.schedules import local_eta_table

        table = local_eta_table(spec.eta_l_schedule or "constant",
                                spec.eta_l, spec.local_steps)
        return {"eta": jnp.asarray(table, jnp.float32)}

    def check_steps(self, spec, slots, k_steps: int) -> None:
        # the table is sized by spec.local_steps; a longer scan would
        # silently clamp the gather to the last eta — reject it loudly
        assert slots["eta"].shape[0] == k_steps, (
            f"sgd_sched eta table has {slots['eta'].shape[0]} steps but "
            f"the batches carry {k_steps} local steps; spec.local_steps "
            f"must match the batches' leading dim")

    def step(self, spec, slots, y, grads, correction, t_local, *,
             use_fused_update: bool = False):
        eta = slots["eta"][t_local]
        if correction is not None:
            y_new = jax.tree.map(
                lambda yy, gg, cc: (yy - eta * (gg + cc)).astype(yy.dtype),
                y, grads, correction,
            )
        else:
            y_new = jax.tree.map(
                lambda yy, gg: (yy - eta * gg).astype(yy.dtype), y, grads
            )
        return y_new, slots


_LOCAL_SOLVERS: Dict[str, LocalSolver] = {}


def register_local_solver(solver: LocalSolver) -> LocalSolver:
    """Register a ``LocalSolver`` instance under its ``name``."""
    assert solver.name, "LocalSolver subclasses must set a name"
    _LOCAL_SOLVERS[solver.name] = solver
    return solver


def get_local_solver(name: str) -> LocalSolver:
    """Look up a registered local solver; unknown names fail loudly."""
    try:
        return _LOCAL_SOLVERS[name]
    except KeyError:
        raise KeyError(
            f"unknown local solver {name!r}; registered: "
            f"{local_solver_names()}"
        ) from None


def local_solver_names() -> Tuple[str, ...]:
    """Sorted names of all registered local solvers."""
    return tuple(sorted(_LOCAL_SOLVERS))


for _s in (SGDSolver(), MomentumSolver(), AdamSolver(),
           ScheduledSGDSolver()):
    register_local_solver(_s)


def resolve_local_solver(spec) -> str:
    """The spec's local solver name ("sgd" for duck-typed specs that
    predate the registry)."""
    return getattr(spec, "local_solver", "") or "sgd"


# ---------------------------------------------------------------------------
# the K-step local loop
# ---------------------------------------------------------------------------


def megakernel_incompatibility(grad_fn, solver: LocalSolver, *,
                               prox_mu: float = 0.0, params=None,
                               batches=None):
    """Why this (grad_fn, solver, problem) combination can NOT take the
    K-step megakernel path — None when it can (DESIGN.md §15).

    The megakernel computes the gradient *in-kernel*, so the loss must
    advertise a kernel-expressible grad via a ``megakernel_grad`` marker
    (``"quadratic"`` — attached to ``data.quadratics.quadratic_loss`` and
    propagated by ``core.controller.make_grad_fn``), and the solver step
    must be expressible too (``solver.megakernel``; ``adam``'s
    per-element rsqrt state is not fused — yet). The returned string is
    what engines surface as ``megakernel_fallback_reason`` in round
    metrics, mirroring ``scan_fallback_reason``.
    """
    marker = getattr(grad_fn, "megakernel_grad", None)
    if marker != "quadratic":
        return ("grad not kernel-expressible (loss_fn lacks "
                "megakernel_grad='quadratic')")
    if not getattr(solver, "megakernel", False):
        return f"local solver {solver.name!r} has no megakernel variant"
    if prox_mu:
        return "FedProx prox term is not expressible in the megakernel"
    if params is not None:
        leaves = jax.tree.leaves(params)
        if len(leaves) != 1 or leaves[0].ndim != 1:
            return "params are not a single 1-D leaf"
    if batches is not None and not (
            isinstance(batches, dict) and "A" in batches and "b" in batches):
        return "batches are not quadratic (A, b) pairs"
    return None


def _run_megakernel_steps(spec, y0, batches, *, solver: LocalSolver, slots,
                          correction, shard_fn, k_steps: int):
    """The megakernel fast path of :func:`run_local_steps`: one
    ``pallas_call`` for all K steps (DESIGN.md §15). Callers must have
    cleared :func:`megakernel_incompatibility` first."""
    from repro.kernels.scaffold_update import megakernel as mk

    if solver.name == "sgd_sched":
        eta_table = slots["eta"]
    else:
        eta_table = jnp.full((k_steps,), spec.eta_l, jnp.float32)
    is_momentum = solver.name == "momentum"
    y_K, m_K, losses = mk.scaffold_local_loop(
        y0, correction, batches, eta_table,
        m=slots["m"] if is_momentum else None,
        beta=spec.local_momentum if is_momentum else 0.0)
    slots_K = {"m": m_K} if is_momentum else slots
    if shard_fn is not None:
        y_K = shard_fn(y_K)
        slots_K = solver.shard_slots(shard_fn, slots_K)
    return y_K, slots_K, jnp.mean(losses)


def run_local_steps(
    grad_fn: Callable,
    spec,
    y0,
    batches,  # pytree, leaves (K, b, ...)
    *,
    solver: LocalSolver | None = None,
    slots=None,
    correction=None,  # pytree like params, or None
    prox_mu: float = 0.0,
    prox_center=None,
    use_fused_update: bool = False,
    shard_fn=None,  # optional with_sharding_constraint for the scan carry
) -> Tuple[Any, Any, jnp.ndarray]:
    """K local solver steps; returns ``(y_K, slots_K, mean local loss)``.

    The K-step loop is a ``lax.scan`` carrying ``(y, slots, t_local)``,
    so the lowered HLO is compact regardless of K and the solver slots
    are explicit carry state (vmap/scan/shard like any other pytree).
    ``slots=None`` starts from ``solver.init`` (fresh client);
    the engine passes persisted rows for stateful solvers.

    The FedProx prox term is accumulated in **fp32** — the grads handed
    to the solver carry fp32 leaves when ``prox_mu`` is active — so the
    fused kernel path (which accumulates fp32 internally) and the jnp
    path (fp32 by promotion) round identically to the fp32 oracle for
    sub-fp32 params: one rounding, at the final cast to the param dtype
    (tests/test_kernels.py). For fp32 params every cast is a no-op and
    the trajectory is bit-for-bit the pre-registry one.

    ``shard_fn`` pins the carried client model to its param sharding —
    without it GSPMD can fail to propagate the FSDP sharding into the
    while-loop carry and replicate the full model per device (observed:
    11.6 TB temp on deepseek-v3).
    """
    if solver is None:
        solver = get_local_solver(resolve_local_solver(spec))
    if slots is None:
        slots = solver.init(spec, y0)
    k_steps = jax.tree.leaves(batches)[0].shape[0]
    solver.check_steps(spec, slots, k_steps)

    if getattr(spec, "use_megakernel", False) and megakernel_incompatibility(
            grad_fn, solver, prox_mu=prox_mu, params=y0,
            batches=batches) is None:
        with jax.named_scope(spans.LOCAL_STEP):
            return _run_megakernel_steps(
                spec, y0, batches, solver=solver, slots=slots,
                correction=correction, shard_fn=shard_fn, k_steps=k_steps)

    def step(carry, batch):
        y, sl, t = carry
        grads, metrics = grad_fn(y, batch)
        if prox_mu:
            grads = jax.tree.map(
                lambda g, yy, xx: g.astype(jnp.float32)
                + prox_mu * (yy.astype(jnp.float32)
                             - xx.astype(jnp.float32)),
                grads, y, prox_center,
            )
        with jax.named_scope(spans.SOLVER_STEP):
            y_new, sl_new = solver.step(spec, sl, y, grads, correction, t,
                                        use_fused_update=use_fused_update)
        if shard_fn is not None:
            # pin the whole param-sized carry, slots included — an
            # unpinned carry lets GSPMD replicate model-sized state per
            # device (see docstring; the fp32 moments are *larger* than
            # bf16 params)
            y_new = shard_fn(y_new)
            sl_new = solver.shard_slots(shard_fn, sl_new)
        return (y_new, sl_new, t + 1), metrics["loss"]

    with jax.named_scope(spans.LOCAL_STEP):  # names the ops of every step
        (y, slots, _), losses = uscan(
            step, (y0, slots, jnp.zeros((), jnp.int32)), batches)
    return y, slots, jnp.mean(losses)


def local_sgd(
    grad_fn: Callable,
    y0,
    batches,  # pytree, leaves (K, b, ...)
    eta_l: float,
    *,
    correction=None,
    prox_mu: float = 0.0,
    prox_center=None,
    use_fused_update: bool = False,
    shard_fn=None,
) -> Tuple[Any, jnp.ndarray]:
    """Back-compat seed surface: K plain corrected SGD steps; returns
    ``(y_K, mean local loss)`` — bit-for-bit :func:`run_local_steps`
    with the ``sgd`` solver (tests/test_local_solvers.py)."""
    y, _, loss = run_local_steps(
        grad_fn, types.SimpleNamespace(eta_l=eta_l), y0, batches,
        solver=get_local_solver("sgd"), correction=correction,
        prox_mu=prox_mu, prox_center=prox_center,
        use_fused_update=use_fused_update, shard_fn=shard_fn,
    )
    return y, loss
