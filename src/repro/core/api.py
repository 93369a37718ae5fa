"""Typed round-state API and the algorithm / server-optimizer registries.

The paper presents SCAFFOLD, FedAvg, FedProx and large-batch SGD as
instances of one round template (Algorithm 1: local updates → aggregate
deltas → server step). This module encodes that template as *types*
instead of string `if/elif` chains and variable-arity tuples:

  ServerState       everything the server owns between rounds: the model
                    ``x``, the server control variate ``c``, and the
                    server-optimizer slots (momentum / Adam moments).
                    Under a non-identity ``UpdateSpace`` (DESIGN.md §17)
                    ``x`` is the trainable-*delta* pytree against a
                    frozen base held by the controller; everything here
                    — including both scanned engines' store rows — is
                    generic over that tree.
  ClientRoundState  the sampled clients' round-scoped state: their
                    control variates ``c_i`` (leaves ``(S, ...)``),
                    uplink error-feedback residuals, and aggregation
                    weights.
  RoundOutput       new ``ServerState`` + new ``ClientRoundState`` +
                    the round metrics, fixed arity for every algorithm.

All three are registered pytree dataclasses, so they jit/vmap/donate
like any other pytree (DESIGN.md §9).

Two registries make the template pluggable:

  ``Algorithm``       the per-round algorithm strategy — what drift
                      correction local steps apply and how the control
                      variates update (``local_correction``,
                      ``client_control_update``,
                      ``server_control_update``). Registered:
                      ``scaffold``, ``fedavg``, ``fedprox``, ``sgd``,
                      plus the momentum variants ``scaffold_m`` /
                      ``fedavgm`` (server heavy-ball by default — Cheng
                      et al. 2023 show momentum helps non-IID FL; Hsu et
                      al. 2019 is the FedAvgM baseline).
  ``ServerOptimizer`` how the aggregated delta is applied to ``x`` —
                      ``sgd`` (eq. 5), ``momentum`` (heavy-ball), and
                      ``adam`` (FedAdam-style, Reddi et al. 2021).
                      Composes with any algorithm.

Registering a new algorithm or server optimizer is one subclass + one
``register_*`` call; nothing in the engine, controller, checkpointing or
launch layers needs to change.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.core.tree import tree_sub, tree_zeros_like

# ---------------------------------------------------------------------------
# typed round state (registered pytree dataclasses)
# ---------------------------------------------------------------------------


@partial(jax.tree_util.register_dataclass,
         data_fields=["x", "c", "opt_state"], meta_fields=[])
@dataclasses.dataclass
class ServerState:
    """Everything the server carries between rounds.

    x:         model parameters (param pytree).
    c:         server control variate (param-like pytree; zeros and
               unused for non-SCAFFOLD algorithms, kept for fixed arity).
    opt_state: server-optimizer slots (``{}`` for plain SGD, ``{"m": …}``
               for heavy-ball, ``{"m": …, "v": …, "t": …}`` for Adam).
    """

    x: Any
    c: Any
    opt_state: Any


@partial(jax.tree_util.register_dataclass,
         data_fields=["c_i", "uplink_residual", "weights", "solver_slots"],
         meta_fields=[])
@dataclasses.dataclass
class ClientRoundState:
    """Round-scoped state of the S sampled clients.

    c_i:             control variates, leaves ``(S, ...)``.
    uplink_residual: error-feedback residuals carried across rounds when
                     ``spec.compress_uplink`` (leaves ``(S, ...)``,
                     fp32), else None.
    weights:         optional ``(S,)`` aggregation weights (paper §2
                     weighted case, e.g. client dataset sizes);
                     normalised inside the round.
    solver_slots:    per-client local-solver slots when the spec's
                     ``local_solver`` is stateful (momentum/adam —
                     leaves ``(S, ...)``, DESIGN.md §12), else None
                     (``run_round`` then starts from ``solver.init``).
    """

    c_i: Any
    uplink_residual: Any = None
    weights: Optional[jnp.ndarray] = None
    solver_slots: Any = None


@partial(jax.tree_util.register_dataclass,
         data_fields=["server", "clients", "metrics"], meta_fields=[])
@dataclasses.dataclass
class RoundOutput:
    """Fixed-arity result of one communication round."""

    server: ServerState
    clients: ClientRoundState
    metrics: Dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# algorithm strategies
# ---------------------------------------------------------------------------


class Algorithm:
    """One federated algorithm = one strategy over the round template.

    Subclasses override the three hooks; the engine (``core/rounds.py``)
    and controller never branch on algorithm names.
    """

    name: str = ""
    # scaffold-family: clients carry c_i across rounds and the controller
    # scatters c_i_new back into the host store
    stateful_clients: bool = False
    # sgd baseline: one server step on the whole round batch, no local work
    whole_batch: bool = False
    # server optimizer used when the spec does not name one
    default_server_optimizer: str = "sgd"

    def local_correction(self, spec, x, c, c_i):
        """Constant per-step correction added to local gradients
        (SCAFFOLD's ``c - c_i``), or None."""
        return None

    def prox_mu(self, spec) -> float:
        """FedProx proximal coefficient (0 disables the prox term)."""
        return 0.0

    def client_control_update(self, spec, x, y, c, c_i,
                              grad_at_x: Callable[[], Any]
                              ) -> Tuple[Any, Any]:
        """New client control variate after the K local steps.

        ``grad_at_x`` lazily computes g_i(x) over the client's round data
        (only traced if called — SCAFFOLD option I). Returns
        ``(c_i_new, dc)`` with ``dc = c_i_new - c_i``.
        """
        return c_i, tree_zeros_like(c_i)

    def server_control_update(self, spec, c, dc_mean):
        """New server control variate from the aggregated dc."""
        return c


class FedAvg(Algorithm):
    """Plain federated averaging (McMahan et al. 2017) — no correction."""

    name = "fedavg"


class FedProx(Algorithm):
    """FedAvg + a proximal term pulling local steps toward the server
    model (``spec.fedprox_mu``)."""

    name = "fedprox"

    def prox_mu(self, spec) -> float:
        return spec.fedprox_mu


class Scaffold(Algorithm):
    """The paper's Algorithm 1: control-variate-corrected local steps,
    c_i updated by option I or II (``spec.scaffold_option``)."""

    name = "scaffold"
    stateful_clients = True

    def local_correction(self, spec, x, c, c_i):
        # c - c_i, applied every local step (eq. 3)
        return tree_sub(c, c_i)

    def client_control_update(self, spec, x, y, c, c_i, grad_at_x):
        if spec.scaffold_option == "II":
            # c_i+ = c_i - c + (x - y)/(K*eta_l)   (eq. 4, option II)
            inv = 1.0 / (spec.local_steps * spec.eta_l)
            c_i_new = jax.tree.map(
                lambda ci, cc, xx, yy: (ci - cc + inv * (xx - yy)).astype(ci.dtype),
                c_i, c, x, y,
            )
        else:
            # c_i+ = g_i(x): extra pass over the client's round data (eq. 4, I)
            c_i_new = jax.tree.map(
                lambda g, ci: g.astype(ci.dtype), grad_at_x(), c_i)
        return c_i_new, tree_sub(c_i_new, c_i)

    def server_control_update(self, spec, c, dc_mean):
        # c+ = c + (S/N) * mean dc   (alg. 1 line 17)
        frac = spec.num_sampled / spec.num_clients
        return jax.tree.map(
            lambda cc, d: (cc + frac * d).astype(cc.dtype), c, dc_mean
        )


class LargeBatchSGD(Algorithm):
    """The large-batch baseline: one server step on the whole round
    batch, no local work (Table-comparison anchor in the paper)."""

    name = "sgd"
    whole_batch = True


class ScaffoldM(Scaffold):
    """SCAFFOLD with a server heavy-ball step by default (momentum on the
    aggregated drift-corrected delta — the server-side variant of Cheng
    et al. 2023's momentum corrections)."""

    name = "scaffold_m"
    default_server_optimizer = "momentum"


class FedAvgM(FedAvg):
    """FedAvgM (Hsu et al. 2019): FedAvg + server heavy-ball."""

    name = "fedavgm"
    default_server_optimizer = "momentum"


_ALGORITHMS: Dict[str, Algorithm] = {}


def register_algorithm(algo: Algorithm) -> Algorithm:
    """Register an ``Algorithm`` instance under its ``name``."""
    assert algo.name, "Algorithm subclasses must set a name"
    _ALGORITHMS[algo.name] = algo
    return algo


def get_algorithm(name: str) -> Algorithm:
    """Look up a registered algorithm; unknown names fail loudly."""
    try:
        return _ALGORITHMS[name]
    except KeyError:
        raise KeyError(
            f"unknown algorithm {name!r}; registered: {algorithm_names()}"
        ) from None


def algorithm_names() -> Tuple[str, ...]:
    """Sorted names of all registered algorithms."""
    return tuple(sorted(_ALGORITHMS))


for _a in (Scaffold(), FedAvg(), FedProx(), LargeBatchSGD(),
           ScaffoldM(), FedAvgM()):
    register_algorithm(_a)


# ---------------------------------------------------------------------------
# server optimizers
# ---------------------------------------------------------------------------


class ServerOptimizer:
    """Applies the aggregated round delta ``dy_mean`` to the server model.

    ``apply`` returns ``(x_new, opt_state_new, applied_update)`` where
    ``applied_update`` is the effective step direction (reported as the
    round's ``update_norm`` metric).
    """

    name: str = ""

    def init(self, spec, x) -> Any:
        return {}

    def apply(self, spec, opt_state, x, dy_mean):
        raise NotImplementedError


class ServerSGD(ServerOptimizer):
    """x+ = x + eta_g * dy_mean  (eq. 5 / alg. 1 line 16)."""

    name = "sgd"

    def apply(self, spec, opt_state, x, dy_mean):
        x_new = jax.tree.map(
            lambda xx, d: (xx + spec.eta_g * d).astype(xx.dtype), x, dy_mean
        )
        return x_new, opt_state, dy_mean


class ServerMomentum(ServerOptimizer):
    """Heavy-ball on the aggregated delta (FedAvgM-style):
    m+ = beta*m + dy;  x+ = x + eta_g * m+.

    beta is exactly ``spec.server_momentum`` — momentum-default algorithms
    get 0.9 written onto the spec at construction
    (``FedRoundSpec.__post_init__``), so the running beta is always
    visible and an explicit beta=0.0 is honoured."""

    name = "momentum"

    def beta(self, spec) -> float:
        return spec.server_momentum

    def init(self, spec, x):
        return {"m": tree_zeros_like(x)}

    def apply(self, spec, opt_state, x, dy_mean):
        beta = self.beta(spec)
        m_new = jax.tree.map(
            lambda m, d: (beta * m + d).astype(m.dtype),
            opt_state["m"], dy_mean,
        )
        x_new = jax.tree.map(
            lambda xx, d: (xx + spec.eta_g * d).astype(xx.dtype), x, m_new
        )
        return x_new, {"m": m_new}, m_new


class ServerAdam(ServerOptimizer):
    """FedAdam (Reddi et al. 2021, "Adaptive Federated Optimization"):
    Adam on the pseudo-gradient ``dy_mean``, fp32 moment slots."""

    name = "adam"

    def init(self, spec, x):
        f32 = lambda a: jnp.zeros(a.shape, jnp.float32)  # noqa: E731
        return {
            "m": jax.tree.map(f32, x),
            "v": jax.tree.map(f32, x),
            "t": jnp.zeros((), jnp.int32),
        }

    def apply(self, spec, opt_state, x, dy_mean):
        b1, b2, eps = spec.server_beta1, spec.server_beta2, spec.server_eps
        t = opt_state["t"] + 1
        m_new = jax.tree.map(
            lambda m, d: b1 * m + (1.0 - b1) * d.astype(jnp.float32),
            opt_state["m"], dy_mean,
        )
        v_new = jax.tree.map(
            lambda v, d: b2 * v + (1.0 - b2) * jnp.square(d.astype(jnp.float32)),
            opt_state["v"], dy_mean,
        )
        bc1 = 1.0 - b1 ** t.astype(jnp.float32)
        bc2 = 1.0 - b2 ** t.astype(jnp.float32)
        step = jax.tree.map(
            lambda m, v: (m / bc1) / (jnp.sqrt(v / bc2) + eps), m_new, v_new
        )
        x_new = jax.tree.map(
            lambda xx, d: (xx + spec.eta_g * d).astype(xx.dtype), x, step
        )
        return x_new, {"m": m_new, "v": v_new, "t": t}, step


_SERVER_OPTIMIZERS: Dict[str, ServerOptimizer] = {}


def register_server_optimizer(opt: ServerOptimizer) -> ServerOptimizer:
    """Register a ``ServerOptimizer`` instance under its ``name``."""
    assert opt.name, "ServerOptimizer subclasses must set a name"
    _SERVER_OPTIMIZERS[opt.name] = opt
    return opt


def get_server_optimizer(name: str) -> ServerOptimizer:
    """Look up a registered server optimizer; unknown names fail loudly."""
    try:
        return _SERVER_OPTIMIZERS[name]
    except KeyError:
        raise KeyError(
            f"unknown server optimizer {name!r}; "
            f"registered: {server_optimizer_names()}"
        ) from None


def server_optimizer_names() -> Tuple[str, ...]:
    """Sorted names of all registered server optimizers."""
    return tuple(sorted(_SERVER_OPTIMIZERS))


for _o in (ServerSGD(), ServerMomentum(), ServerAdam()):
    register_server_optimizer(_o)


# ---------------------------------------------------------------------------
# state construction
# ---------------------------------------------------------------------------


def resolve_server_optimizer(spec) -> str:
    """The spec's server optimizer, resolved against back-compat knobs:
    an explicit ``spec.server_optimizer`` wins; else ``server_momentum>0``
    selects heavy-ball (the pre-registry API); else the algorithm's
    default."""
    if getattr(spec, "server_optimizer", ""):
        return spec.server_optimizer
    if spec.server_momentum > 0.0:
        return "momentum"
    return get_algorithm(spec.algorithm).default_server_optimizer


def init_server_state(spec, x) -> ServerState:
    """Fresh ``ServerState`` for model ``x``: zero control variate + the
    resolved server optimizer's initial slots."""
    opt = get_server_optimizer(resolve_server_optimizer(spec))
    return ServerState(x=x, c=tree_zeros_like(x), opt_state=opt.init(spec, x))


# ---------------------------------------------------------------------------
# the scanned multi-round engine (DESIGN.md §10)
# ---------------------------------------------------------------------------


def run_rounds(grad_fn, spec, server: ServerState, client_store, R: int, *,
               data, batch_fn, sample_key, data_key, comp_key=None,
               priv_key=None, start_round=0, sizes=None,
               use_fused_update: bool = False, shard_fn=None):
    """R communication rounds as one ``lax.scan`` — zero host round trips.

    The host loop pays per-round dispatch (numpy cohort sampling, host
    gather/scatter of the c_i store, a fresh ``jit`` call, host data
    loading); at paper scale (thousands of rounds, Fig. 3 / Tables 3–5)
    that dominates wall-clock. Here the whole round sequence is one
    device program: cohort sampling is a ``jax.random`` permutation, the
    *full* N-client control-variate store stays resident on device with
    dynamic gather/scatter inside the scan body, and data loading is a
    gather through the dataset's device-batch function.

    server:       ``ServerState`` at round ``start_round``.
    client_store: full client-state store, leaves ``(N, ...)`` (shard its
                  leading axis over "data" via
                  ``dist.partition_client_store`` on a multi-device mesh).
                  With an active uplink codec (``spec.compress_uplink``)
                  and/or a stateful local solver (``spec.local_solver``
                  in {momentum, adam}) this is a dict with the row
                  families the config carries — ``{"c_i": <x-like
                  tree>[, "residual": <fp32 x-like tree>][, "solver":
                  <slot tree>]}`` — error-feedback residuals and
                  local-solver slots are ordinary store rows,
                  gathered/scattered inside the scan exactly like the
                  control variates (DESIGN.md §11/§12).
    R:            trip count (python int — static under jit).
    data:         dataset device arrays (``dataset.device_data()``).
    batch_fn:     pure ``(data, ids, key) -> batches`` with leaves
                  ``(S, K, b, ...)`` (``dataset.device_batch_fn(K, b)``).
    sample_key:   base key of the cohort stream; round ``t`` draws
                  ``device_sample_ids(sample_key, t, N, S)``.
    data_key:     base key of the data stream; round ``t`` uses
                  ``fold_in(data_key, t)``.
    comp_key:     base key of the compression stream; round ``t`` uses
                  ``fold_in(comp_key, t)``. Required only when a
                  configured codec is keyed (``randk_ef``).
    priv_key:     base key of the privacy stream (``key(seed+3)``);
                  round ``t`` uses ``fold_in(priv_key, t)``. Required
                  only when ``spec.privatizer`` adds noise.
    start_round:  absolute index of the first round (int or traced scalar
                  — traced keeps one compilation across resume chunks).
    sizes:        optional ``(N,)`` per-client dataset sizes for
                  ``spec.weighted_aggregation``.

    RNG contract: all four streams are *stateless* functions of (base
    key, absolute round index), so a host loop calling ``run_round`` once
    per round with the same keys — or this scan re-entered at any chunk
    boundary after a checkpoint restore — consumes identical randomness
    and produces bit-for-bit identical trajectories
    (tests/test_scan_engine.py).

    Returns ``(server, client_store, metrics)`` with metrics leaves
    stacked ``(R,)`` and ``client_store`` in the input structure
    (residuals / solver slots included when carried).
    """
    # lazy imports: rounds.py imports this module at top level
    from repro.core.compression import get_compressor, resolve_compressor
    from repro.core.local_solver import get_local_solver, resolve_local_solver
    from repro.core.rounds import run_round
    from repro.core.sampling import device_sample_ids
    from repro.core.tree import tree_gather, tree_scatter

    up = get_compressor(resolve_compressor(spec))
    solver = get_local_solver(resolve_local_solver(spec))
    carry_residuals = up.stateful
    carry_slots = solver.stateful
    wrapped = carry_residuals or carry_slots
    if wrapped:
        need = {"c_i"}
        if carry_residuals:
            need.add("residual")
        if carry_slots:
            need.add("solver")
        assert (isinstance(client_store, dict)
                and need <= set(client_store)), (
            f"this config carries per-client rows beyond c_i (uplink codec "
            f"{up.name!r} stateful={carry_residuals}, local solver "
            f"{solver.name!r} stateful={carry_slots}): pass client_store "
            f"as a dict with keys {sorted(need)} and (N, ...) leaves")

    def body(carry, t):
        server, store = carry
        with jax.named_scope(spans.SAMPLE):
            ids = device_sample_ids(sample_key, t, spec.num_clients,
                                    spec.num_sampled)
        with jax.named_scope(spans.BATCHES):
            batches = batch_fn(data, ids, jax.random.fold_in(data_key, t))
        with jax.named_scope(spans.GATHER):
            gathered = tree_gather(store, ids)
        clients = ClientRoundState(
            c_i=gathered["c_i"] if wrapped else gathered,
            uplink_residual=(gathered["residual"] if carry_residuals
                             else None),
            solver_slots=gathered["solver"] if carry_slots else None,
            weights=(sizes[ids].astype(jnp.float32)
                     if sizes is not None else None),
        )
        out = run_round(grad_fn, spec, server, clients, batches,
                        use_fused_update=use_fused_update, shard_fn=shard_fn,
                        comp_key=(jax.random.fold_in(comp_key, t)
                                  if comp_key is not None else None),
                        priv_key=(jax.random.fold_in(priv_key, t)
                                  if priv_key is not None else None),
                        dp_round=t)
        if wrapped:
            new_rows = {"c_i": out.clients.c_i}
            if carry_residuals:
                new_rows["residual"] = out.clients.uplink_residual
            if carry_slots:
                new_rows["solver"] = out.clients.solver_slots
        else:
            new_rows = out.clients.c_i
        with jax.named_scope(spans.SCATTER):
            store = tree_scatter(store, ids, new_rows)
        return (out.server, store), out.metrics

    ts = jnp.arange(R, dtype=jnp.int32) + jnp.asarray(start_round, jnp.int32)
    (server, client_store), metrics = jax.lax.scan(
        body, (server, client_store), ts)
    return server, client_store, metrics


def run_rounds_cohort(grad_fn, spec, server: ServerState, cohort_store,
                      R: int, *, data, batch_fn, round_ids, slot_ids,
                      data_key, comp_key=None, priv_key=None, start_round=0,
                      weights=None, use_fused_update: bool = False,
                      shard_fn=None):
    """``run_rounds`` over a *cohort-sized* client-store buffer — the
    tiered store's scanned engine (DESIGN.md §13).

    ``run_rounds`` keeps the full ``(N, ...)`` client store
    device-resident; at population scale (N = 10^6+ clients with real
    params) that store cannot live in HBM. Here the population store
    stays host-side (``core/store.py``) and the scan only ever touches
    ``cohort_store`` — the same pytree/dict layout as ``run_rounds``'s
    store but with leaves ``(U, ...)``, where U is the chunk's fixed
    cohort-union capacity ``min(N, R*S)`` — so peak device client-store
    bytes are bounded by cohort size, never by N.

    cohort_store: the chunk's client-state rows, leaves ``(U, ...)``
                  (dict of row families exactly as in ``run_rounds``).
                  Rows beyond the chunk's actual union are padding: no
                  ``slot_ids`` entry points at them, so they are never
                  read or written and the capacity stays
                  shape-static (one compile per chunk length R).
    round_ids:    ``(R, S)`` int32 — round r's *global* cohort ids. The
                  host precomputes them from the same stateless
                  ``device_sample_ids`` stream the dense scan folds, so
                  trajectories are bit-for-bit identical.
    slot_ids:     ``(R, S)`` int32 — the same cohorts as row indices of
                  ``cohort_store`` (host-built via ``np.unique``, so a
                  client resampled across the chunk's rounds maps to one
                  slot and within-chunk read-after-write matches the
                  dense store exactly).
    weights:      optional ``(R, S)`` fp32 aggregation weights — the
                  host-gathered ``sizes[round_ids]`` (the dense scan
                  gathers from a device-resident ``(N,)`` sizes array,
                  which a tiered run must not materialise).

    Global ids only ever reach the data gather (``batch_fn``) and the
    metrics; every store gather/scatter goes through ``slot_ids``.
    Returns ``(server, cohort_store, metrics)`` like ``run_rounds``;
    the caller writes the first-U rows back to the population store.
    """
    from repro.core.compression import get_compressor, resolve_compressor
    from repro.core.local_solver import get_local_solver, resolve_local_solver
    from repro.core.rounds import run_round
    from repro.core.tree import tree_gather, tree_scatter

    up = get_compressor(resolve_compressor(spec))
    solver = get_local_solver(resolve_local_solver(spec))
    carry_residuals = up.stateful
    carry_slots = solver.stateful
    wrapped = carry_residuals or carry_slots

    def body(store_and_server, xs):
        server, store = store_and_server
        t, ids, slots = xs["t"], xs["ids"], xs["slots"]
        with jax.named_scope(spans.BATCHES):
            batches = batch_fn(data, ids, jax.random.fold_in(data_key, t))
        with jax.named_scope(spans.GATHER):
            gathered = tree_gather(store, slots)
        clients = ClientRoundState(
            c_i=gathered["c_i"] if wrapped else gathered,
            uplink_residual=(gathered["residual"] if carry_residuals
                             else None),
            solver_slots=gathered["solver"] if carry_slots else None,
            weights=xs["w"] if "w" in xs else None,
        )
        out = run_round(grad_fn, spec, server, clients, batches,
                        use_fused_update=use_fused_update, shard_fn=shard_fn,
                        comp_key=(jax.random.fold_in(comp_key, t)
                                  if comp_key is not None else None),
                        priv_key=(jax.random.fold_in(priv_key, t)
                                  if priv_key is not None else None),
                        dp_round=t)
        if wrapped:
            new_rows = {"c_i": out.clients.c_i}
            if carry_residuals:
                new_rows["residual"] = out.clients.uplink_residual
            if carry_slots:
                new_rows["solver"] = out.clients.solver_slots
        else:
            new_rows = out.clients.c_i
        with jax.named_scope(spans.SCATTER):
            store = tree_scatter(store, slots, new_rows)
        return (out.server, store), out.metrics

    xs = {
        "t": (jnp.arange(R, dtype=jnp.int32)
              + jnp.asarray(start_round, jnp.int32)),
        "ids": jnp.asarray(round_ids, jnp.int32),
        "slots": jnp.asarray(slot_ids, jnp.int32),
    }
    if weights is not None:
        xs["w"] = jnp.asarray(weights, jnp.float32)
    (server, cohort_store), metrics = jax.lax.scan(
        body, (server, cohort_store), xs)
    return server, cohort_store, metrics
