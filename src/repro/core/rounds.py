"""One federated communication round, pure & jittable.

``run_round(grad_fn, spec, server, clients, batches)`` is the typed
entrypoint: it implements Algorithm 1 (SCAFFOLD) and every registered
variant (FedAvg / FedProx / large-batch SGD / the momentum algorithms)
for the S *sampled* clients of the round, taking a ``ServerState`` +
``ClientRoundState`` and returning a fixed-arity ``RoundOutput``
(DESIGN.md §9). Algorithm behaviour is dispatched through the
``Algorithm`` registry and the server step through the
``ServerOptimizer`` registry (``core/api.py``) — no string branching.

``federated_round(...)`` is the thin back-compat shim over ``run_round``
with the seed's positional-tuple signature; its trajectories are
bit-for-bit identical to the typed path (tests/test_api_equivalence.py).

Client states for the unsampled N-S clients never enter the device
program — the controller (repro.core.controller) scatters the returned
``c_i`` back into the host store, matching the paper's stateful-client
semantics.

The round is generic over the ``server.x`` pytree: under a non-identity
``UpdateSpace`` (DESIGN.md §17) ``x`` is the *trainable-delta* tree
(LoRA factors / head subtrees), ``grad_fn`` differentiates in that
space (``make_grad_fn(space=...)``), and ``c``/``c_i``/residuals/solver
slots — all templated off ``x`` — are delta-shaped with it. Nothing in
this module branches on the space; broadcast and uplink payloads (and
so ``round_comm_bytes``) shrink to the delta automatically.

``use_fused_update=True`` routes every local step's update arithmetic
through the packed Pallas path (one kernel launch per dtype group per
step — DESIGN.md §8). It matches its fp32-accumulating oracle
(``ref.scaffold_update_ref``) exactly; for sub-fp32 param dtypes that
accumulation differs by rounding from the native-dtype jnp expression.
``spec.use_megakernel`` goes further where the grad/solver combination
allows it: ``run_local_steps`` fuses the *whole* K-step local loop into
one ``pallas_call`` per dtype group per round (DESIGN.md §15);
inexpressible combinations fall back per-step with the reason surfaced
as ``megakernel_fallback_reason`` in the engines' round metrics.

Two execution strategies with identical algorithm semantics (tested):
  client_parallel   vmap over the S clients (client axis shards over the
                    `data` mesh axis; round aggregation becomes one
                    all-reduce — the paper's "communication round").
  client_sequential lax.scan over the S clients (FSDP-style for models
                    whose state cannot fit one model-parallel group).

The client's inner optimizer is the spec's registered ``LocalSolver``
(``core/local_solver.py``, DESIGN.md §12) — both strategies thread its
slot pytree through the local steps, and for stateful solvers
(momentum/adam) the per-client slots ride ``ClientRoundState.
solver_slots`` in and out of the round exactly like the control
variates.

Communication compression (DESIGN.md §11) lives at this level, shared by
both strategies: the uplink codec (``spec.compress``, from the
``Compressor`` registry) round-trips each client's dy with its carried
error-feedback residual, and the optional downlink codec
(``spec.compress_downlink``) transforms the broadcast (x, c) pair the
clients receive. Every round's metrics include the static
``bytes_up``/``bytes_down`` accounting.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.core.api import (
    ClientRoundState,
    RoundOutput,
    ServerState,
    get_algorithm,
    get_server_optimizer,
    resolve_server_optimizer,
)
from repro.core.compression import (
    get_compressor,
    resolve_compressor,
    resolve_downlink,
    round_comm_bytes,
)
from repro.core.local_solver import (
    get_local_solver,
    resolve_local_solver,
    run_local_steps,
)
from repro.core.privatizer import get_privatizer, resolve_privatizer
from repro.util import uscan
from repro.core.tree import (
    tree_mean_leading,
    tree_norm,
    tree_sub,
    tree_zeros_like,
)


def _merge_step_batches(batches):
    """(K, b, ...) leaves -> (K*b, ...) for Option I's pass at x."""
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), batches)


def client_update(grad_fn, spec, x, c, c_i, batches, solver_slots=None,
                  use_fused_update: bool = False, shard_fn=None):
    """Local work of one sampled client.

    batches: pytree with leaves (K, b, ...). Returns
    (dy, dc, c_i_new, solver_slots_new, loss) — dy = y_K - x (model
    delta), dc = c_i_new - c_i (control delta), solver_slots_new the
    local solver's slots after the K steps (``{}`` for slot-free
    solvers; ``run_round`` persists them only for stateful solvers).
    ``solver_slots=None`` starts from ``solver.init`` (fresh client).
    ``x`` / ``c`` are whatever the client *received* (the downlink-
    compressed broadcast when ``spec.compress_downlink``); uplink
    compression of dy happens at the ``run_round`` level, shared by both
    client strategies.
    """
    with jax.named_scope(spans.CLIENT):
        algo = get_algorithm(spec.algorithm)
        correction = algo.local_correction(spec, x, c, c_i)
        prox_mu = algo.prox_mu(spec)
        prox_center = x if prox_mu else None

        y, slots_new, loss = run_local_steps(
            grad_fn, spec, x, batches,
            slots=solver_slots, correction=correction,
            prox_mu=prox_mu, prox_center=prox_center,
            use_fused_update=use_fused_update, shard_fn=shard_fn,
        )
        dy = tree_sub(y, x)

        with jax.named_scope(spans.CONTROL):
            c_i_new, dc = algo.client_control_update(
                spec, x, y, c, c_i,
                lambda: grad_fn(x, _merge_step_batches(batches))[0],
            )
    return dy, dc, c_i_new, slots_new, loss


def _whole_batch_round(grad_fn, spec, server, clients, batches) -> RoundOutput:
    """Large-batch SGD baseline: one server step on the whole round batch —
    no local work, control variates, weights or server optimizer
    (``FedRoundSpec.__post_init__`` rejects those combinations loudly)."""
    flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[3:]), batches)
    grads, metrics = grad_fn(server.x, flat)
    with jax.named_scope(spans.SERVER):
        x_new = jax.tree.map(
            lambda xx, gg: (xx - spec.eta_l * gg).astype(xx.dtype),
            server.x, grads,
        )
        out_metrics = {
            "loss": metrics["loss"],
            "drift": jnp.zeros((), jnp.float32),
            "update_norm": tree_norm(tree_sub(x_new, server.x)),
            **_bytes_metrics(spec, server.x, stateful_clients=False),
        }
    return RoundOutput(
        server=dataclasses.replace(server, x=x_new),
        clients=clients,
        metrics=out_metrics,
    )


def _bytes_metrics(spec, x, *, stateful_clients: bool):
    """Static per-round communicated-bytes metrics (fp32 scalars so they
    stack under the scanned engine like every other metric — inexact
    above 2^24 bytes/round; the trainer overwrites its history with the
    exact ints from ``round_comm_bytes``, which is also the surface for
    exact consumers)."""
    return {k: jnp.asarray(v, jnp.float32)
            for k, v in round_comm_bytes(
                spec, x, stateful_clients=stateful_clients).items()}


def run_round(grad_fn, spec, server: ServerState, clients: ClientRoundState,
              batches, use_fused_update: bool = False,
              shard_fn=None, comp_key=None, priv_key=None,
              dp_round=None) -> RoundOutput:
    """One communication round over the S sampled clients (typed API).

    server:   ``ServerState`` (x, c, server-optimizer slots).
    clients:  ``ClientRoundState`` — c_i / uplink error-feedback
              residuals / local-solver slots with leaves (S, ...),
              optional (S,) aggregation weights. A None
              ``uplink_residual`` under an active codec starts from
              zeros; a None ``solver_slots`` under a stateful local
              solver starts from ``solver.init`` (also zeros).
    batches:  pytree with leaves (S, K, b, ...).
    comp_key: PRNG key of this round's compression stream (derive as
              ``fold_in(base, t)`` — stateless in the round index, like
              the cohort/data streams). Required only when a configured
              codec is keyed (``randk_ef``); client ``i`` then draws
              ``fold_in(fold_in(comp_key, 0), i)`` and the downlink
              broadcast draws ``fold_in(comp_key, 1)``, identically
              under both client strategies and all three execution
              modes.
    priv_key: PRNG key of this round's privacy stream (``fold_in(key(
              seed+3), t)`` — the fourth stateless stream). Required
              when ``spec.privatizer`` is a noise-adding mechanism;
              client ``i`` draws ``fold_in(fold_in(priv_key, 0), i)``
              and the server draw is ``fold_in(priv_key, 1)``.
    dp_round: absolute round index (int or traced), required when
              privatizing — the accountant's ``dp_epsilon`` after this
              round is ``epsilon(dp_round + 1)``.

    With an active privatizer (DESIGN.md §16) each client's dy is
    L2-clipped to ``spec.clip_norm`` *before* the uplink codec (clip →
    compress → aggregate: the sensitivity bound must hold on what each
    client contributes, and the error-feedback residual stream would
    otherwise re-inject unclipped mass); distributed noise rides each
    clipped delta pre-codec, server noise touches only the aggregated
    mean. The control-variate stream dc is left untouched, exactly like
    the codecs (perturbing it would break the drift correction the
    paper is about). Metrics gain ``dp_epsilon`` / ``dp_clipped_frac``.
    """
    algo = get_algorithm(spec.algorithm)
    if algo.whole_batch:
        return _whole_batch_round(grad_fn, spec, server, clients, batches)

    up = get_compressor(resolve_compressor(spec))
    down = get_compressor(resolve_downlink(spec))
    if (up.needs_key or down.needs_key) and comp_key is None:
        raise ValueError(
            f"compressors ({up.name!r}/{down.name!r}) are keyed: pass "
            f"comp_key to run_round")
    k_up = (jax.random.fold_in(comp_key, 0) if comp_key is not None
            else None)

    priv = get_privatizer(resolve_privatizer(spec))
    privatizing = priv.name != "none"
    if privatizing:
        if priv.needs_key and priv_key is None:
            raise ValueError(
                f"privatizer {priv.name!r} is keyed: pass priv_key to "
                f"run_round (the seed+3 stream, folded by round)")
        if dp_round is None:
            raise ValueError(
                f"privatizer {priv.name!r} needs dp_round (the absolute "
                f"round index) for the dp_epsilon accountant metric")
    k_priv = (jax.random.fold_in(priv_key, 0) if priv_key is not None
              else None)

    x, c = server.x, server.c
    # what the clients *receive*: the (optionally compressed) broadcast.
    # dy is measured against the received x so the server-side apply of
    # mean dy to the exact x matches real federated execution.
    if down.name != "none":
        x_cl, c_cl = down.apply_stateless(
            spec, (x, c),
            key=(jax.random.fold_in(comp_key, 1) if comp_key is not None
                 else None))
    else:
        x_cl, c_cl = x, c

    c_i, weights = clients.c_i, clients.weights
    # stateful local solvers (momentum/adam) carry per-client slots —
    # None means every sampled client starts from solver.init (zeros,
    # matching the zero-filled store rows of never-sampled clients)
    solver = get_local_solver(resolve_local_solver(spec))
    slots_in = clients.solver_slots
    if solver.stateful and slots_in is None:
        slots_in = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (spec.num_sampled,) + a.shape),
            solver.init(spec, x))
    fn = partial(client_update, grad_fn, spec,
                 use_fused_update=use_fused_update,
                 shard_fn=shard_fn if spec.strategy == "client_sequential"
                 else None)

    if weights is not None:
        wnorm = weights.astype(jnp.float32)
        wnorm = wnorm / jnp.maximum(wnorm.sum(), 1e-12)

    def _wmean(tree_stacked):
        with jax.named_scope(spans.AGGREGATE):
            if weights is None:
                return tree_mean_leading(tree_stacked)
            return jax.tree.map(
                lambda a: jnp.tensordot(
                    wnorm, a.astype(jnp.float32), axes=(0, 0)
                ).astype(a.dtype),
                tree_stacked)

    def _res0(dy_like):
        """The carried residuals, or the codec's fresh ones (leaves match
        the stacked per-client deltas); None for stateless codecs."""
        if clients.uplink_residual is not None:
            return clients.uplink_residual
        return up.init_residual(dy_like)

    uplink_res_new = clients.uplink_residual
    clipped_frac = None
    if spec.strategy == "client_parallel":
        dy, dc, c_i_new, slots_new, losses = jax.vmap(
            fn, in_axes=(None, None, 0, 0, 0 if solver.stateful else None)
        )(x_cl, c_cl, c_i, batches, slots_in)
        if privatizing and priv.clips:
            # clip -> (distributed noise) -> compress: the codec sees a
            # norm-bounded, already-noised delta
            dy, clipped = jax.vmap(lambda d: priv.clip(spec, d))(dy)
            clipped_frac = jnp.mean(clipped)
            if priv.noise_at == "client":
                pkeys = jax.vmap(lambda i: jax.random.fold_in(k_priv, i))(
                    jnp.arange(spec.num_sampled))
                dy = jax.vmap(
                    lambda d, k: priv.client_noise(spec, d, k))(dy, pkeys)
        if up.name != "none":
            res = _res0(dy)
            if up.needs_key:
                keys = jax.vmap(lambda i: jax.random.fold_in(k_up, i))(
                    jnp.arange(spec.num_sampled))
                dy, uplink_res_new = jax.vmap(
                    lambda d, r, k: up.round_trip(spec, d, r, key=k))(
                        dy, res, keys)
            else:
                dy, uplink_res_new = jax.vmap(
                    lambda d, r: up.round_trip(spec, d, r))(dy, res)
        dy_mean = _wmean(dy)
        dc_mean = _wmean(dc)
        loss = jnp.mean(losses)
        drift = jnp.mean(jax.vmap(tree_norm)(dy))
    else:  # client_sequential
        s = spec.num_sampled
        w_seq = (wnorm if weights is not None
                 else jnp.full((s,), 1.0 / s, jnp.float32))
        compressing = up.name != "none"
        clipping = privatizing and priv.clips
        # the per-client index feeds the keyed codecs and/or the
        # per-client privacy noise keys
        need_i = ((compressing and up.needs_key)
                  or (privatizing and priv.noise_at == "client"))

        def scan_body(carry, inp):
            if clipping:
                dy_acc, dc_acc, loss_acc, clip_acc = carry
            else:
                dy_acc, dc_acc, loss_acc = carry
            ci_k, batch_k, w_k = inp["c_i"], inp["batch"], inp["w"]
            slots_k = inp["slots"] if solver.stateful else None
            dy_k, dc_k, ci_new_k, slots_new_k, loss_k = fn(
                x_cl, c_cl, ci_k, batch_k, slots_k)
            if clipping:
                dy_k, clipped_k = priv.clip(spec, dy_k)
                clip_acc = clip_acc + clipped_k
                if priv.noise_at == "client":
                    dy_k = priv.client_noise(
                        spec, dy_k, jax.random.fold_in(k_priv, inp["i"]))
            if compressing:
                key_k = (jax.random.fold_in(k_up, inp["i"]) if up.needs_key
                         else None)
                dy_k, res_new_k = up.round_trip(spec, dy_k, inp["res"],
                                                key=key_k)
            with jax.named_scope(spans.AGGREGATE):
                dy_acc = jax.tree.map(
                    lambda a, d: a + w_k * d.astype(a.dtype), dy_acc, dy_k)
                dc_acc = jax.tree.map(
                    lambda a, d: a + w_k * d.astype(a.dtype), dc_acc, dc_k)
            if shard_fn is not None:
                dy_acc = shard_fn(dy_acc)
                dc_acc = shard_fn(dc_acc)
                ci_new_k = shard_fn(ci_new_k)
                if compressing and res_new_k is not None:
                    res_new_k = shard_fn(res_new_k)
                if solver.stateful:
                    # shard_fn is the param-tree constraint; slots nest
                    # param trees under slot keys, so pin per entry
                    slots_new_k = solver.shard_slots(shard_fn, slots_new_k)
            ys = {"c_i": ci_new_k}
            if compressing:
                ys["res"] = res_new_k
            if solver.stateful:
                ys["slots"] = slots_new_k
            if clipping:
                return (dy_acc, dc_acc, loss_acc + loss_k, clip_acc), ys
            return (dy_acc, dc_acc, loss_acc + loss_k), ys

        xs = {"c_i": c_i, "batch": batches, "w": w_seq}
        if need_i or compressing:
            # "i" stays in xs for every compressing config (the
            # pre-privatizer layout — unkeyed codecs just ignore it)
            xs["i"] = jnp.arange(s, dtype=jnp.int32)
        if compressing:
            xs["res"] = _res0(c_i)
        if solver.stateful:
            xs["slots"] = slots_in
        zeros = tree_zeros_like(x)
        carry0 = (zeros, tree_zeros_like(c), jnp.zeros((), jnp.float32))
        if clipping:
            carry0 = carry0 + (jnp.zeros((), jnp.float32),)
        carry_out, ys = uscan(scan_body, carry0, xs)
        if clipping:
            dy_mean, dc_mean, loss_sum, clip_sum = carry_out
            clipped_frac = clip_sum / s
        else:
            dy_mean, dc_mean, loss_sum = carry_out
        c_i_new = ys["c_i"]
        if compressing:
            uplink_res_new = ys["res"]
        slots_new = ys.get("slots")
        loss = loss_sum / s
        drift = tree_norm(dy_mean)

    with jax.named_scope(spans.SERVER):
        # trusted-aggregator noise lands on the aggregated mean, after the
        # codec round-trip and before the server optimizer sees it
        if privatizing and priv.noise_at == "server":
            dy_mean = priv.server_noise(
                spec, dy_mean, jax.random.fold_in(priv_key, 1))

        # server update (eq. 5 / alg. 1 line 16-17) through the registered
        # server optimizer (sgd / heavy-ball momentum / FedAdam), applied
        # to the server's *exact* x (the downlink codec only perturbs what
        # the clients see)
        opt = get_server_optimizer(resolve_server_optimizer(spec))
        x_new, opt_state_new, applied = opt.apply(
            spec, server.opt_state, x, dy_mean)
        c_new = algo.server_control_update(spec, c, dc_mean)
        metrics = {
            "loss": loss,
            "drift": drift,
            "update_norm": tree_norm(applied),
            **_bytes_metrics(spec, x, stateful_clients=algo.stateful_clients),
        }
        if privatizing:
            # fp32 so they scan-stack like every metric; the engines
            # overwrite history's dp_epsilon with the exact float64
            # accountant, the same discipline as the bytes metrics
            metrics["dp_epsilon"] = priv.epsilon_traced(
                spec, jnp.asarray(dp_round, jnp.float32) + 1.0)
            if clipped_frac is not None:
                metrics["dp_clipped_frac"] = clipped_frac
    return RoundOutput(
        server=ServerState(x=x_new, c=c_new, opt_state=opt_state_new),
        clients=ClientRoundState(c_i=c_i_new,
                                 uplink_residual=uplink_res_new,
                                 weights=weights,
                                 solver_slots=(slots_new if solver.stateful
                                               else None)),
        metrics=metrics,
    )


def federated_round(grad_fn, spec, x, c, c_i, batches, momentum=None,
                    weights=None, uplink_res=None,
                    use_fused_update: bool = False, shard_fn=None,
                    comp_key=None):
    """Back-compat shim over :func:`run_round` (the seed signature).

    x, c: param-like pytrees (server model / server control variate).
    c_i: pytree with leaves (S, ...) — sampled clients' control variates.
    batches: pytree with leaves (S, K, b, ...).
    momentum: server heavy-ball state — required whenever the spec resolves
    to the momentum server optimizer (spec.server_momentum>0, or a
    momentum-default algorithm like scaffold_m/fedavgm); the return then
    becomes (x, c, c_i, momentum_new, metrics).
    weights: optional (S,) client aggregation weights (paper §2 weighted
    case; e.g. client dataset sizes) — normalised internally.
    uplink_res: per-client error-feedback residuals (leaves (S, ...)) when
    spec.compress_uplink; the new residuals are returned in metrics-position
    order (x, c, c_i, [momentum], [uplink_res], metrics).
    comp_key: per-round compression key (keyed codecs — see run_round).
    Returns (x_new, c_new, c_i_new, metrics).
    """
    opt_name = resolve_server_optimizer(spec)
    assert opt_name in ("sgd", "momentum"), (
        f"the tuple-shim only carries sgd/momentum server state; use "
        f"run_round + ServerState for {opt_name!r}")
    solver_name = resolve_local_solver(spec)
    assert not get_local_solver(solver_name).stateful, (
        f"the tuple-shim cannot carry the per-client slots of stateful "
        f"local solver {solver_name!r} (they would silently reset every "
        f"call); use run_round + ClientRoundState.solver_slots")
    whole_batch = get_algorithm(spec.algorithm).whole_batch
    if opt_name == "momentum" and not whole_batch:
        # also covers the momentum-default algorithms (scaffold_m/fedavgm):
        # without a threaded slot the heavy-ball state would silently reset
        # every call and diverge from the typed path
        assert momentum is not None, "pass momentum state for server_momentum"
    opt_state = {"m": momentum} if momentum is not None else {}
    out = run_round(
        grad_fn, spec,
        ServerState(x=x, c=c, opt_state=opt_state),
        ClientRoundState(c_i=c_i, uplink_residual=uplink_res,
                         weights=weights),
        batches, use_fused_update=use_fused_update, shard_fn=shard_fn,
        comp_key=comp_key,
    )
    if whole_batch:
        return out.server.x, out.server.c, out.clients.c_i, out.metrics
    outs = [out.server.x, out.server.c, out.clients.c_i]
    if opt_name == "momentum":
        outs.append(out.server.opt_state["m"])
    if spec.compress_uplink:
        outs.append(out.clients.uplink_residual)
    outs.append(out.metrics)
    return tuple(outs)
