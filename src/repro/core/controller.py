"""Host-side federated training controller.

Owns:
  * the typed ``ServerState`` (x, c, server-optimizer slots) on device,
  * the *full* N-client host stores (``core/store.py``, one row per
    client behind a pluggable ``StoreBackend`` — the paper's "stateful
    clients"): control variates, plus uplink error-feedback residuals
    when an uplink codec is active (``spec.compress`` — DESIGN.md §11),
    plus local-solver slots when the spec's ``local_solver`` is stateful
    (momentum/adam — DESIGN.md §12; in dense scan mode all of these live
    in the device-resident store and the host stores are checkpoint
    mirrors; ``store="tiered"`` keeps the population host-side in every
    mode and gathers only cohort rows to the device — DESIGN.md §13),
  * the sampler and the per-round gather/scatter of sampled clients'
    round state (``ClientRoundState``),
  * the jitted typed round function (``core/rounds.run_round``).

The device program only ever sees the S sampled clients (DESIGN.md §2);
algorithm behaviour and the server step come from the registries in
``core/api.py`` (DESIGN.md §9), so the controller never branches on
algorithm names.

Execution is one of three modes:

  synchronous  ``pipeline_depth=0`` (the seed behaviour): sample, gather,
               load, execute, scatter — strictly in order.
  pipelined    ``pipeline_depth>=1`` (DESIGN.md §8): the round function
               is dispatched asynchronously, the host prepares the next
               rounds' inputs (client sampling, c_i/residual gathers,
               ``dataset.round_batches``) while the device computes, and
               the host-store scatters are deferred until the round's
               outputs are actually consumed. Prefetched gathers that a
               later scatter would invalidate are re-gathered row-wise,
               so the pipelined trajectory is bit-for-bit identical to
               the synchronous one.
  scanned      ``scan_rounds=R>0`` (DESIGN.md §10): the round loop itself
               moves on device — ``core/api.run_rounds`` ``lax.scan``s
               the typed round over chunks of up to R rounds with
               on-device cohort sampling, a device-resident (N, ...)
               client store, and the dataset's device-batch gather. The
               host only touches the trainer at chunk boundaries
               (metrics, checkpoints). Requires the dataset's
               device-data protocol; configs that can't scan fall back
               to the host loop with a warning
               (``scan_fallback_reason``). ``pipeline_depth`` is ignored
               while scanning (there is no host work left to overlap).
"""
from __future__ import annotations

import dataclasses
import warnings
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import (
    ClientRoundState,
    get_algorithm,
    init_server_state,
    run_rounds,
    run_rounds_cohort,
)
from repro.core.async_engine import AsyncBufferedEngine
from repro.core.compression import (
    get_compressor,
    resolve_compressor,
    resolve_downlink,
    round_comm_bytes,
)
from repro.core.local_solver import (
    get_local_solver,
    megakernel_incompatibility,
    resolve_local_solver,
)
from repro.core.privatizer import get_privatizer, resolve_privatizer
from repro.core.rounds import run_round
from repro.core.sampling import (
    ClientSampler,
    DeviceClientSampler,
    device_sample_ids,
    key_from_state,
    key_state,
)
from repro.core.store import (  # noqa: F401  (ClientStateStore re-exported)
    ClientStateStore,
    TieredClientStore,
    make_store_backend,
    refresh_rows as _refresh_rows,
    stale_mask,
)
from repro.core.tree import tree_cast
from repro.core.update_space import get_update_space, resolve_update_space


def make_grad_fn(loss_fn: Callable, *, space=None, spec=None,
                 base_params=None) -> Callable:
    """``loss_fn(params, batch) -> (scalar, metrics)``  =>
    ``grad_fn(params, batch) -> (grads, metrics)``.

    Propagates the loss's ``megakernel_grad`` marker (losses whose
    gradient is expressible inside the K-step megakernel advertise it —
    ``data.quadratics.quadratic_loss``) so
    ``local_solver.megakernel_incompatibility`` can gate on the grad fn
    it actually receives.

    With a non-identity ``space`` (an :class:`~repro.core.update_space.
    UpdateSpace`, DESIGN.md §17) the returned function differentiates in
    *delta* space: ``grad_fn(deltas, batch)`` evaluates the loss at
    ``space.apply(spec, base_params, deltas)`` and pulls the full-space
    cotangent back through ``space.grad_project`` — the exact chain
    rule, so every engine trains the delta pytree unchanged. The
    megakernel marker is dropped there (the delta-space gradient is no
    longer the loss's closed form), which surfaces as a clean
    ``megakernel_fallback_reason``."""

    if space is not None and space.trains_subset:

        def grad_fn(deltas, batch):
            full = space.apply(spec, base_params, deltas)
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(full, batch)
            return space.grad_project(spec, base_params, deltas, grads), \
                metrics

        grad_fn.megakernel_grad = None
        return grad_fn

    def grad_fn(params, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        return grads, metrics

    grad_fn.megakernel_grad = getattr(loss_fn, "megakernel_grad", None)
    return grad_fn


class _ChunkPlan(NamedTuple):
    """Host-precomputed cohort plan of one tiered scan chunk: the rounds'
    global cohort ids, their union (the population rows the chunk needs),
    per-round slots into the cohort buffer, and the buffer's fixed
    capacity min(N, R*S) (padding keeps compilations per chunk length,
    exactly like the dense scan — core/store.py / DESIGN.md §13)."""

    t0: int
    rounds: int
    round_ids: np.ndarray  # (R, S) int32, global ids
    union: np.ndarray      # (u,) unique global ids, u <= capacity
    slot_ids: np.ndarray   # (R, S) int32, rows of the cohort buffer
    capacity: int


class _RoundInputs(NamedTuple):
    """Host-prepared inputs of one round: sampled ids, their gathered c_i
    / residuals / local-solver slots (numpy, mutable — stale rows are
    re-gathered in place), weights, data batches, and the host-RNG
    states *before* this round was prepared (what a checkpoint must
    record to re-prepare it)."""

    ids: np.ndarray
    c_i: Any
    uplink_res: Any
    solver_slots: Any
    weights: Optional[np.ndarray]
    batches: Any
    host_state: Dict[str, Any]


class FederatedTrainer:
    """Runs registered federated algorithms (scaffold / fedavg / fedprox /
    sgd / scaffold_m / fedavgm / ...) against a federated dataset.
    ``dataset.round_batches(ids, K, b, rng)`` must return a pytree with
    leaves (S, K, b, ...); with ``spec.weighted_aggregation`` it must also
    expose ``client_sizes(ids) -> (S,)`` per-client dataset sizes.

    ``pipeline_depth=0`` runs each round fully synchronously (sample,
    gather, load, execute, scatter — the seed semantics, bit-for-bit).
    ``pipeline_depth=d>=1`` keeps up to d rounds of host-side inputs
    prefetched while the device executes, overlapping data loading and
    state gathers with compute; trajectories are identical.
    ``scan_rounds=R>0`` moves the loop on device in chunks of up to R
    rounds (``run_rounds`` — requires the dataset's device-data protocol:
    ``device_data()`` + ``device_batch_fn(K, b)``); incompatible configs
    fall back to the host loop and record why in ``scan_fallback_reason``.

    ``store="tiered"`` keeps the ``(N, ...)`` population stores host-side
    behind ``store_backend`` ("dense" RAM / "memmap" disk / "sharded") in
    every mode, with ``prefetch_depth`` chunks of gather-ahead; under the
    scanned engine the device then only ever holds the chunk's
    cohort-union buffer — min(N, R*S) rows — instead of the full (N, ...)
    store (DESIGN.md §13). Trajectories are bit-for-bit the dense
    store's (tests/test_store.py).
    """

    def __init__(self, loss_fn, init_params, spec, dataset, *, seed: int = 0,
                 use_fused_update: bool = False, donate: bool = True,
                 pipeline_depth: int = 0, scan_rounds: int = 0,
                 store: str = "dense", store_backend: str = "",
                 prefetch_depth: int = 2, async_buffer: int = 0,
                 max_inflight: int = 0,
                 availability: Any = "always_on",
                 availability_kwargs: Optional[Dict[str, Any]] = None,
                 staleness_weighting: Any = "constant",
                 staleness_kwargs: Optional[Dict[str, Any]] = None):
        assert pipeline_depth >= 0, pipeline_depth
        assert scan_rounds >= 0, scan_rounds
        assert store in ("dense", "tiered"), store
        assert prefetch_depth >= 1, prefetch_depth
        assert async_buffer >= 0, async_buffer
        if async_buffer and scan_rounds:
            raise ValueError(
                "async_buffer is incompatible with scan_rounds: the scanned "
                "engine is a synchronous-cohort loop by construction")
        if async_buffer and pipeline_depth:
            raise ValueError(
                "async_buffer is incompatible with pipeline_depth: the async "
                "engine owns its own dispatch overlap")
        self.spec = spec
        self.dataset = dataset
        self.algorithm = get_algorithm(spec.algorithm)
        if spec.weighted_aggregation and not hasattr(dataset, "client_sizes"):
            raise ValueError(
                "spec.weighted_aggregation=True needs the dataset to expose "
                "client_sizes(ids); add it or disable weighting")
        key = jax.random.key(seed)
        # update space (DESIGN.md §17): with a non-identity space the
        # full parameters are frozen as self.base_params and server.x
        # becomes the trainable-delta pytree — everything templated off
        # it below (c, c_i, residuals, solver slots, store row families,
        # comm-bytes accounting) is delta-shaped automatically. The
        # adapter init draws from the fifth counter-based stream
        # (key(seed+4)), so full-space RNG consumption is untouched.
        self.update_space = get_update_space(resolve_update_space(spec))
        full_init = init_params(key)
        if self.update_space.trains_subset:
            self.base_params = full_init
            self.server = init_server_state(
                spec, self.update_space.init_deltas(
                    spec, full_init, jax.random.key(seed + 4)))
        else:
            self.base_params = None
            self.server = init_server_state(spec, full_init)
        # tiered population store (DESIGN.md §13): rows live host-side in a
        # pluggable StoreBackend; one worker thread serialises all backend
        # I/O across the row families so gather-ahead repairs stay ordered
        self.store_kind = store
        self.prefetch_depth = int(prefetch_depth)
        self._store_exec: Optional[ThreadPoolExecutor] = None
        if store == "tiered":
            self._store_exec = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tiered-store")
            make_store = lambda tmpl: TieredClientStore(  # noqa: E731
                tmpl, spec.num_clients,
                backend=make_store_backend(store_backend or "dense"),
                prefetch_depth=self.prefetch_depth,
                executor=self._store_exec)
        else:
            make_store = lambda tmpl: ClientStateStore(  # noqa: E731
                tmpl, spec.num_clients, backend=store_backend or "dense")
        self.store = make_store(self.server.x)
        # uplink error-feedback residuals persist per client across rounds
        # (fp32; gated on the codec's ``stateful`` — the same predicate
        # run_rounds uses for the device-store layout, so a registered
        # stateless codec needs no residual rows anywhere)
        self.compressor = get_compressor(resolve_compressor(spec))
        self.residual_store = (
            make_store(tree_cast(self.server.x, jnp.float32))
            if self.compressor.stateful else None)
        # stateful local solvers (momentum/adam) persist per-client slots
        # across rounds, exactly like the control variates / residuals:
        # one (N, ...) host store row family, mirrored into the device
        # store under the scanned engine (DESIGN.md §12)
        self.local_solver = get_local_solver(resolve_local_solver(spec))
        self.solver_store = (
            make_store(self.local_solver.init(spec, self.server.x))
            if self.local_solver.stateful else None)
        self.sampler = ClientSampler(spec.num_clients, spec.num_sampled, seed)
        self._rng = np.random.default_rng(seed + 1)
        # compression stream: stateless in the round index like the scan's
        # cohort/data streams — round t folds _comp_base_key by t. Only
        # keyed codecs (randk_ef) consume it.
        self._comp_base_key = jax.random.key(seed + 2)
        self._comp_keyed = (
            self.compressor.needs_key
            or get_compressor(resolve_downlink(spec)).needs_key)
        # privacy stream (DESIGN.md §16): the fourth stateless
        # counter-based stream — round t folds _priv_base_key by t; only
        # noise-adding privatizers consume it. Clip state is per-cohort,
        # so the privatizer adds no store row families.
        self.privatizer = get_privatizer(resolve_privatizer(spec))
        self._priv_base_key = jax.random.key(seed + 3)
        self._priv_active = self.privatizer.name != "none"
        # exact per-round communicated bytes (python ints -> float is
        # lossless well past any model size); the device metrics carry
        # the same numbers as fp32 scalars, inexact above 2^24 B/round,
        # so history/logging use this host-side copy
        self._comm_bytes = {
            k: float(v) for k, v in round_comm_bytes(
                spec, self.server.x,
                stateful_clients=self.algorithm.stateful_clients).items()}
        space = self.update_space

        def grad_fn_for(base):
            # the frozen base is an argument of every jitted program below
            # (None, i.e. no argument at all, in the full space) and the
            # delta grad fn is built from it at trace time: a closed-over
            # base would be serialised into each program as a constant
            return make_grad_fn(loss_fn, space=space, spec=spec,
                                base_params=base)

        grad_fn = grad_fn_for(self.base_params)
        # the async engine re-derives the per-dispatch client phase from
        # these (core/async_engine.py — DESIGN.md §14)
        self._grad_fn_for = grad_fn_for
        self._use_fused_update = use_fused_update
        # megakernel capability gate (DESIGN.md §15): decided once at
        # trainer init from static config — "" when every local loop will
        # take the fused K-step kernel, a reason string when they fall
        # back to the per-step path, None when the spec never asked.
        # Surfaced per round as metrics["megakernel_fallback_reason"],
        # mirroring scan_fallback_reason.
        self.megakernel_fallback_reason: Optional[str] = None
        if getattr(spec, "use_megakernel", False):
            if self.algorithm.whole_batch:
                self.megakernel_fallback_reason = (
                    f"whole-batch {spec.algorithm!r} runs no local steps")
            else:
                self.megakernel_fallback_reason = megakernel_incompatibility(
                    grad_fn, self.local_solver,
                    prox_mu=self.algorithm.prox_mu(spec),
                    params=self.server.x) or ""
            if self.megakernel_fallback_reason:
                warnings.warn(
                    f"use_megakernel requested but running the per-step "
                    f"path: {self.megakernel_fallback_reason}", stacklevel=2)

        def round_fn(server, clients, batches, comp_key, priv_key, dp_round,
                     base):
            return run_round(grad_fn_for(base), spec, server, clients,
                             batches, use_fused_update=use_fused_update,
                             comp_key=comp_key, priv_key=priv_key,
                             dp_round=dp_round)

        self.round_fn = jax.jit(round_fn,
                                donate_argnums=(0, 1) if donate else ())
        self.round_idx = 0
        self.history = []
        self.pipeline_depth = int(pipeline_depth)
        self._prefetch: deque = deque()

        # -- async buffered-aggregation mode (DESIGN.md §14) -------------
        self.async_engine = None
        if async_buffer:
            self.async_engine = AsyncBufferedEngine(
                self, buffer_size=async_buffer, max_inflight=max_inflight,
                availability=availability,
                availability_kwargs=availability_kwargs,
                staleness_weighting=staleness_weighting,
                staleness_kwargs=staleness_kwargs)

        # -- scanned-engine mode (DESIGN.md §10) -------------------------
        self.scan_rounds = int(scan_rounds)
        self.scan_fallback_reason: Optional[str] = None
        self._scan_mode = False
        if self.scan_rounds > 0:
            self.scan_fallback_reason = self._scan_incompatibility()
            if self.scan_fallback_reason is not None:
                warnings.warn(
                    f"scan_rounds={scan_rounds} requested but running the "
                    f"host loop: {self.scan_fallback_reason}", stacklevel=2)
        self._tiered_scan = False
        if self.scan_rounds > 0 and self.scan_fallback_reason is None:
            self._scan_mode = True
            # device RNG streams mirror the host pair (sampler=seed,
            # data=seed+1) but are stateless in the round index — see
            # sampling.device_sample_ids / DESIGN.md §10
            self.device_sampler = DeviceClientSampler(
                spec.num_clients, spec.num_sampled, seed)
            self._data_base_key = jax.random.key(seed + 1)
            self._device_data = dataset.device_data()
            self._device_batch_fn = dataset.device_batch_fn(
                spec.local_steps, spec.local_batch)
            batch_fn = self._device_batch_fn
            self._host_store_dirty = False
            self._tiered_scan = self.store_kind == "tiered"
        if self._tiered_scan:
            # tiered scanned engine (DESIGN.md §13): the population rows
            # stay host-side in self.store/residual_store/solver_store;
            # each chunk gathers only its cohort union — at most
            # min(N, R*S) rows — into a fixed-capacity device buffer
            # (run_rounds_cohort). Chunk plans and population reads are
            # prefetched on the store worker while the device computes.
            self._store_wrapped = (self.residual_store is not None
                                   or self.solver_store is not None)
            self._sizes_host = (
                np.asarray(dataset.device_client_sizes(), np.float32)
                if spec.weighted_aggregation else None)
            self._plan_futures: OrderedDict = OrderedDict()

            def cohort_fn(server, cohort, data, round_ids, slot_ids,
                          data_key, comp_key, priv_key, weights, t0, R, base):
                return run_rounds_cohort(
                    grad_fn_for(base), spec, server, cohort, R, data=data,
                    batch_fn=batch_fn, round_ids=round_ids,
                    slot_ids=slot_ids, data_key=data_key, comp_key=comp_key,
                    priv_key=priv_key, start_round=t0, weights=weights,
                    use_fused_update=use_fused_update)

            # R is static (one compile per distinct chunk length — the
            # cohort capacity min(N, R*S) is a pure function of R, so the
            # buffer shape is static too); t0 is traced
            self._cohort_fn = jax.jit(
                cohort_fn, static_argnums=(10,),
                donate_argnums=(0, 1) if donate else ())
        elif self._scan_mode:
            self._device_sizes = (
                jnp.asarray(dataset.device_client_sizes())
                if spec.weighted_aggregation else None)
            # full (N, ...) client store, device-resident between chunks;
            # with an active uplink codec / stateful local solver the
            # error-feedback residuals / solver slots are ordinary store
            # rows riding next to the control variates. The host
            # self.store / self.residual_store / self.solver_store
            # mirrors are lazily synced and only checkpointing reads them
            rows = lambda tmpl: jax.tree.map(  # noqa: E731
                lambda a: jnp.zeros(
                    (spec.num_clients,) + jnp.asarray(a).shape,
                    jnp.asarray(a).dtype),
                tmpl)
            c_store = rows(self.server.x)
            if self.compressor.stateful or self.local_solver.stateful:
                self.device_store = {"c_i": c_store}
                if self.compressor.stateful:
                    self.device_store["residual"] = rows(
                        tree_cast(self.server.x, jnp.float32))
                if self.local_solver.stateful:
                    self.device_store["solver"] = rows(
                        self.local_solver.init(spec, self.server.x))
            else:
                self.device_store = c_store

            def chunk_fn(server, store, data, sample_key, data_key,
                         comp_key, priv_key, sizes, t0, R, base):
                return run_rounds(
                    grad_fn_for(base), spec, server, store, R, data=data,
                    batch_fn=batch_fn, sample_key=sample_key,
                    data_key=data_key, comp_key=comp_key, priv_key=priv_key,
                    start_round=t0, sizes=sizes,
                    use_fused_update=use_fused_update)

            # R is static (one compile per distinct chunk length); t0 is
            # traced so resume chunks reuse the compilation
            self._scan_fn = jax.jit(
                chunk_fn, static_argnums=(9,),
                donate_argnums=(0, 1) if donate else ())

    @property
    def scan_active(self) -> bool:
        """True when rounds execute through the scanned engine."""
        return self._scan_mode

    @property
    def async_active(self) -> bool:
        """True when rounds execute through the async buffered engine."""
        return self.async_engine is not None

    def _scan_incompatibility(self) -> Optional[str]:
        """Why this config can't run the scanned engine (None = it can)."""
        d = self.dataset
        if not (hasattr(d, "device_data") and hasattr(d, "device_batch_fn")):
            return (f"dataset {type(d).__name__} has no device-data protocol "
                    f"(device_data()/device_batch_fn(K, b))")
        if (self.spec.weighted_aggregation
                and not hasattr(d, "device_client_sizes")):
            return ("weighted_aggregation needs "
                    f"{type(d).__name__}.device_client_sizes()")
        return None

    # ------------------------------------------------------------------
    # back-compat views of the typed server state
    # ------------------------------------------------------------------

    @property
    def x(self):
        return self.server.x

    @x.setter
    def x(self, value):
        self.server = dataclasses.replace(self.server, x=value)

    @property
    def c(self):
        return self.server.c

    @c.setter
    def c(self, value):
        self.server = dataclasses.replace(self.server, c=value)

    def eval_params(self):
        """The *full* parameter pytree for evaluation/serving: the frozen
        base with the trained deltas merged in (``update_space.apply``).
        In the identity ``full`` space this is ``server.x`` itself — the
        same arrays, so the eval path is bit-for-bit the pre-registry
        one."""
        if self.base_params is None:
            return self.server.x
        return self.update_space.apply(self.spec, self.base_params,
                                       self.server.x)

    @property
    def momentum(self):
        """Server heavy-ball slot, if the resolved optimizer is momentum
        (adam's first moment is not a heavy-ball state and returns None)."""
        from repro.core.api import resolve_server_optimizer

        if resolve_server_optimizer(self.spec) == "momentum":
            return self.server.opt_state.get("m")
        return None

    # ------------------------------------------------------------------
    # host-side round preparation (the work the pipeline overlaps)
    # ------------------------------------------------------------------

    def host_rng_state(self) -> Dict[str, Any]:
        """Sampler + data-RNG states as of the *next unprepared* round —
        i.e. rewound past any prefetched inputs, so a restore re-prepares
        them identically (checkpoint/checkpoint.py). In scan mode the
        device streams are stateless in the round index, so only their
        base keys ride along (the round counter is checkpointed anyway)."""
        if self._prefetch:
            return self._prefetch[0].host_state
        state = {"sampler": self.sampler.get_state(),
                 "data_rng": self._rng.bit_generator.state,
                 "comp_key": key_state(self._comp_base_key),
                 "priv_key": key_state(self._priv_base_key)}
        if self._scan_mode:
            state["device_sampler"] = self.device_sampler.get_state()
            state["device_data_key"] = key_state(self._data_base_key)
        return state

    def set_host_rng_state(self, state: Dict[str, Any]) -> None:
        self._prefetch.clear()
        if self._tiered_scan:
            self._drop_tiered_prefetch()
        self.sampler.set_state(state["sampler"])
        self._rng.bit_generator.state = state["data_rng"]
        if "comp_key" in state:
            self._comp_base_key = key_from_state(state["comp_key"])
        if "priv_key" in state:
            self._priv_base_key = key_from_state(state["priv_key"])
        if self._scan_mode and "device_sampler" in state:
            self.device_sampler.set_state(state["device_sampler"])
            self._data_base_key = key_from_state(state["device_data_key"])

    def _prepare_inputs(self) -> _RoundInputs:
        """Sample → gather → load, in the exact host-RNG order of the
        synchronous loop (prefetching only moves the calls earlier in wall
        time, never reorders them across rounds)."""
        host_state = {"sampler": self.sampler.get_state(),
                      "data_rng": self._rng.bit_generator.state,
                      "comp_key": key_state(self._comp_base_key),
                      "priv_key": key_state(self._priv_base_key)}
        ids = self.sampler.sample()
        c_i = self.store.gather(ids)
        uplink_res = (self.residual_store.gather(ids)
                      if self.residual_store is not None else None)
        solver_slots = (self.solver_store.gather(ids)
                        if self.solver_store is not None else None)
        weights = None
        if self.spec.weighted_aggregation:
            weights = np.asarray(self.dataset.client_sizes(ids), np.float32)
        batches = self.dataset.round_batches(
            ids, self.spec.local_steps, self.spec.local_batch, self._rng
        )
        return _RoundInputs(ids, c_i, uplink_res, solver_slots, weights,
                            batches, host_state)

    def _refresh_stale_rows(self, inputs: _RoundInputs,
                            ids_written: np.ndarray) -> None:
        """Re-gather the rows of a prefetched c_i / residual gather that a
        scatter just overwrote, restoring gather-at-launch-time semantics
        (the repair primitives live in core/store.py and are unit-tested
        there — tests/test_store_properties.py)."""
        stale = stale_mask(inputs.ids, ids_written)
        if not stale.any():
            return
        stale_ids = inputs.ids[stale]
        if self.algorithm.stateful_clients:
            _refresh_rows(inputs.c_i, self.store.gather(stale_ids), stale)
        if self.residual_store is not None:
            _refresh_rows(inputs.uplink_res,
                          self.residual_store.gather(stale_ids), stale)
        if self.solver_store is not None:
            _refresh_rows(inputs.solver_slots,
                          self.solver_store.gather(stale_ids), stale)

    def _dispatch(self, inp: _RoundInputs):
        """Launch the jitted round (async dispatch — returns futures).
        Stores the new ServerState (still unmaterialised device arrays);
        returns the new ClientRoundState + metrics."""
        clients = ClientRoundState(
            c_i=inp.c_i,
            uplink_residual=inp.uplink_res,
            solver_slots=inp.solver_slots,
            weights=(jnp.asarray(inp.weights)
                     if inp.weights is not None else None),
        )
        # per-round compression/privacy keys, stateless in the round
        # index (only computed when consumed; dispatch order ==
        # execution order so round_idx is this round's absolute index
        # even when pipelined)
        comp_key = (jax.random.fold_in(self._comp_base_key, self.round_idx)
                    if self._comp_keyed else None)
        priv_key = dp_round = None
        if self._priv_active:
            priv_key = jax.random.fold_in(self._priv_base_key,
                                          self.round_idx)
            dp_round = jnp.asarray(self.round_idx, jnp.int32)
        out = self.round_fn(self.server, clients, inp.batches, comp_key,
                            priv_key, dp_round, self.base_params)
        self.server = out.server
        return out.clients, out.metrics

    # ------------------------------------------------------------------
    # scanned engine (DESIGN.md §10): device store residency + chunks
    # ------------------------------------------------------------------

    def _store_families(self):
        """The trainer's per-client row families as (name, store) pairs —
        names matching the scanned engines' store-dict keys."""
        fams = [("c_i", self.store)]
        if self.residual_store is not None:
            fams.append(("residual", self.residual_store))
        if self.solver_store is not None:
            fams.append(("solver", self.solver_store))
        return fams

    def client_store_device_bytes(self,
                                  chunk_rounds: Optional[int] = None) -> int:
        """Peak device-resident client-store bytes of this trainer's
        execution mode: the full ``(N, ...)`` store under the dense
        scanned engine; the fixed cohort-union capacity ``min(N, R*S)``
        under the tiered scanned engine (``chunk_rounds`` overrides the
        constructor's ``scan_rounds``); one gathered cohort per in-flight
        round under the host loop (pipelined: depth+1 cohorts)."""
        row = sum(st.row_nbytes for _, st in self._store_families())
        N, S = self.spec.num_clients, self.spec.num_sampled
        if self.async_engine is not None:
            # in-flight dispatch payloads + the aggregation buffer
            eng = self.async_engine
            return (eng.max_inflight + eng.buffer_size) * row
        if self._tiered_scan:
            return min(N, (chunk_rounds or self.scan_rounds) * S) * row
        if self._scan_mode:
            return N * row
        return S * row * (self.pipeline_depth + 1)

    def close(self) -> None:
        """Release store resources (the tiered store's worker thread,
        memmap files). Idempotent; the trainer is unusable afterwards."""
        for _, st in self._store_families():
            st.close()
        if self._store_exec is not None:
            self._store_exec.shutdown(wait=True)
            self._store_exec = None

    def sync_host_store(self) -> None:
        """Mirror the device-resident client store (control variates +
        uplink residuals when compressing + solver slots for stateful
        local solvers) into the host stores. Checkpointing reads the
        host stores; no-op outside scan mode or when the mirror is
        current. Under the tiered scan the population already lives in
        the host stores — syncing means draining the async writebacks."""
        if self._tiered_scan:
            for _, st in self._store_families():
                st.flush()
            return
        if self._scan_mode and self._host_store_dirty:
            all_ids = np.arange(self.spec.num_clients)
            dev = jax.tree.map(np.asarray, self.device_store)
            if self.residual_store is not None or self.solver_store is not None:
                self.store.scatter(all_ids, dev["c_i"])
                if self.residual_store is not None:
                    self.residual_store.scatter(all_ids, dev["residual"])
                if self.solver_store is not None:
                    self.solver_store.scatter(all_ids, dev["solver"])
            else:
                self.store.scatter(all_ids, dev)
            self._host_store_dirty = False

    def _drop_tiered_prefetch(self) -> None:
        """Invalidate the tiered scan's gather-ahead state: wait out the
        in-flight plan tasks (so no late prefetch lands afterwards), then
        drop every prefetched read. Used on checkpoint restore — the
        deterministic cohort stream restarts from the restored round."""
        plans, self._plan_futures = self._plan_futures, OrderedDict()
        for fut in plans.values():
            fut.result()
        for _, st in self._store_families():
            st.drop_prefetches()

    def push_host_store_to_device(self) -> None:
        """Reload the device store from the host stores after a checkpoint
        restore scattered into them (checkpoint.load_trainer). Under the
        tiered scan the host stores *are* the population — there is no
        (N, ...) device store to reload, only stale gather-ahead state to
        invalidate."""
        if self._tiered_scan:
            self._drop_tiered_prefetch()
            return
        if self._scan_mode:
            all_ids = np.arange(self.spec.num_clients)
            c_store = jax.tree.map(jnp.asarray, self.store.gather(all_ids))
            if self.residual_store is not None or self.solver_store is not None:
                self.device_store = {"c_i": c_store}
                if self.residual_store is not None:
                    self.device_store["residual"] = jax.tree.map(
                        jnp.asarray, self.residual_store.gather(all_ids))
                if self.solver_store is not None:
                    self.device_store["solver"] = jax.tree.map(
                        jnp.asarray, self.solver_store.gather(all_ids))
            else:
                self.device_store = c_store
            self._host_store_dirty = False

    # -- tiered scanned engine (DESIGN.md §13) -------------------------

    def _plan_chunk(self, t0: int, R: int) -> _ChunkPlan:
        """Deterministic cohort plan for rounds [t0, t0+R): global cohort
        ids drawn from the *same* stateless ``device_sample_ids`` stream
        the dense scan folds (bit-for-bit identical cohorts), their
        union, and per-round slots into the fixed-capacity buffer."""
        key, N, S = (self.device_sampler.key, self.spec.num_clients,
                     self.spec.num_sampled)
        ids = jax.vmap(lambda t: device_sample_ids(key, t, N, S))(
            jnp.arange(t0, t0 + R, dtype=jnp.int32))
        round_ids = np.asarray(ids, np.int32)
        union, inv = np.unique(round_ids, return_inverse=True)
        return _ChunkPlan(
            t0=t0, rounds=R, round_ids=round_ids,
            union=union.astype(np.int64),
            slot_ids=inv.reshape(round_ids.shape).astype(np.int32),
            capacity=min(N, R * S))

    def _plan_and_prefetch(self, t0: int, R: int) -> _ChunkPlan:
        """Runs on the store worker: plan the chunk, then queue the
        population reads of its union rows under token (t0, R) — reads
        execute next on the same worker, i.e. while the device computes
        the current chunk, never blocking the dispatch thread."""
        plan = self._plan_chunk(t0, R)
        for _, st in self._store_families():
            st.prefetch((t0, R), plan.union)
        return plan

    def _queue_prefetch(self, t0: int, R: int) -> None:
        """Gather-ahead: queue plan+read tasks for the next
        ``prefetch_depth`` chunks, assuming run()'s chunking keeps length
        R (a mispredicted chunk start just falls back to a synchronous
        plan + gather in ``_run_tiered_chunk``)."""
        for i in range(self.prefetch_depth):
            token = (t0 + i * R, R)
            if token not in self._plan_futures:
                self._plan_futures[token] = self._store_exec.submit(
                    self._plan_and_prefetch, *token)
        while len(self._plan_futures) > self.prefetch_depth:
            self._plan_futures.popitem(last=False)  # plans are read-only

    @staticmethod
    def _pad_rows(rows, u: int, capacity: int):
        """Pad gathered union rows (u, ...) to the buffer capacity. Pad
        slots are never referenced by slot_ids nor written back."""
        if u == capacity:
            return rows
        return jax.tree.map(
            lambda l: np.concatenate(
                [l, np.zeros((capacity - u,) + l.shape[1:], l.dtype)]),
            rows)

    def _run_tiered_chunk(self, R: int):
        """One cohort-buffered scan chunk: take the (prefetched) union
        rows, run ``run_rounds_cohort`` on device, queue the next chunks'
        gather-ahead while the device computes, then write the dirty
        union rows back asynchronously."""
        t0 = self.round_idx
        token = (t0, R)
        fut = self._plan_futures.pop(token, None)
        plan = fut.result() if fut is not None else self._plan_chunk(t0, R)
        u = len(plan.union)
        fams = self._store_families()
        cohort = {name: self._pad_rows(st.take(token, plan.union), u,
                                       plan.capacity)
                  for name, st in fams}
        if not self._store_wrapped:
            cohort = cohort["c_i"]
        cohort = jax.tree.map(jnp.asarray, cohort)  # device buffer (donated)
        weights = (self._sizes_host[plan.round_ids]
                   if self._sizes_host is not None else None)
        server, cohort, metrics = self._cohort_fn(
            self.server, cohort, self._device_data, plan.round_ids,
            plan.slot_ids, self._data_base_key,
            self._comp_base_key if self._comp_keyed else None,
            self._priv_base_key if self._priv_active else None,
            weights, t0, R, self.base_params)
        self.server = server
        # gather-ahead for the next chunks while the device crunches this
        # one (async dispatch: nothing above blocked on the chunk yet)
        self._queue_prefetch(t0 + R, R)
        # first sync point: materialise the chunk's store rows, then hand
        # the dirty union rows to the async writeback queue
        out_rows = jax.tree.map(np.asarray, cohort)
        for name, st in fams:
            rows = out_rows[name] if self._store_wrapped else out_rows
            st.scatter_async(plan.union,
                             jax.tree.map(lambda l: l[:u], rows))
        return metrics

    def _run_scan_chunk(self, R: int):
        """Execute R rounds as one on-device scan; returns the R per-round
        metric dicts (also appended to ``history``)."""
        if self._tiered_scan:
            metrics = self._run_tiered_chunk(R)
        else:
            server, store, metrics = self._scan_fn(
                self.server, self.device_store, self._device_data,
                self.device_sampler.key, self._data_base_key,
                self._comp_base_key if self._comp_keyed else None,
                self._priv_base_key if self._priv_active else None,
                self._device_sizes, self.round_idx, R, self.base_params)
            self.server, self.device_store = server, store
            self._host_store_dirty = True
        stacked = {k: np.asarray(v) for k, v in metrics.items()}
        out = []
        for r in range(R):
            self.round_idx += 1
            m = {k: float(v[r]) for k, v in stacked.items()}
            m.update(self._comm_bytes)  # exact ints over the fp32 metrics
            if self._priv_active:
                # exact float64 accountant over the fp32 device metric
                m["dp_epsilon"] = self.privatizer.epsilon(
                    self.spec, self.round_idx)
            if self.megakernel_fallback_reason is not None:
                m["megakernel_fallback_reason"] = (
                    self.megakernel_fallback_reason)
            if self.update_space.trains_subset:
                m["update_space"] = self.update_space.name
            m["round"] = self.round_idx
            self.history.append(m)
            out.append(m)
        return out

    # ------------------------------------------------------------------
    # round loop
    # ------------------------------------------------------------------

    def run_round(self) -> Dict[str, float]:
        if self.async_engine is not None:
            # one "round" = one buffered aggregation (DESIGN.md §14)
            return self.async_engine.run_round()
        if self._scan_mode:
            # chunk of one — bit-for-bit the same trajectory as a larger
            # chunk (tests/test_scan_engine.py), so per-round driving and
            # run()'s chunking compose freely
            return self._run_scan_chunk(1)[0]
        if self.pipeline_depth > 0:
            inp = (self._prefetch.popleft() if self._prefetch
                   else self._prepare_inputs())
        else:
            inp = self._prepare_inputs()
        clients_new, metrics = self._dispatch(inp)
        # Overlap: while the device executes the dispatched round, prepare
        # the next rounds' inputs on the host. Nothing below blocks until
        # the scatter/metrics conversion actually needs the round outputs.
        while len(self._prefetch) < self.pipeline_depth:
            self._prefetch.append(self._prepare_inputs())
        scattered = False
        if self.algorithm.stateful_clients:
            self.store.scatter(inp.ids, clients_new.c_i)  # first sync point
            scattered = True
        if self.residual_store is not None:
            self.residual_store.scatter(inp.ids, clients_new.uplink_residual)
            scattered = True
        if self.solver_store is not None:
            self.solver_store.scatter(inp.ids, clients_new.solver_slots)
            scattered = True
        if scattered:
            for pending in self._prefetch:
                self._refresh_stale_rows(pending, inp.ids)
        self.round_idx += 1
        out = {k: float(v) for k, v in metrics.items()}
        out.update(self._comm_bytes)  # exact ints over the fp32 metrics
        if self._priv_active:
            # exact float64 accountant over the fp32 device metric
            out["dp_epsilon"] = self.privatizer.epsilon(
                self.spec, self.round_idx)
        if self.megakernel_fallback_reason is not None:
            out["megakernel_fallback_reason"] = self.megakernel_fallback_reason
        if self.update_space.trains_subset:
            out["update_space"] = self.update_space.name
        out["round"] = self.round_idx
        self.history.append(out)
        return out

    def run(self, rounds: int, *, eval_fn: Optional[Callable] = None,
            eval_every: int = 0, target_metric: Optional[float] = None,
            metric_name: str = "accuracy", verbose: bool = False):
        """Run rounds; if target_metric given, stop early once
        eval_fn(x)[metric_name] >= target and return rounds used.

        In scan mode the rounds execute in on-device chunks of up to
        ``scan_rounds``, with chunk ends aligned to ``eval_every`` so the
        eval/early-stop schedule matches the host loop exactly."""
        if self._scan_mode:
            done = 0
            while done < rounds:
                chunk = min(self.scan_rounds, rounds - done)
                if eval_fn is not None and eval_every:
                    chunk = min(chunk, eval_every - done % eval_every)
                m = self._run_scan_chunk(chunk)[-1]
                done += chunk
                if (eval_fn is not None and eval_every
                        and done % eval_every == 0):
                    em = eval_fn(self.eval_params())
                    m.update(em)
                    if verbose:
                        print(f"round {done}: {m}")
                    if (target_metric is not None
                            and em[metric_name] >= target_metric):
                        return done
            return rounds
        for r in range(rounds):
            m = self.run_round()
            if eval_fn is not None and eval_every and (r + 1) % eval_every == 0:
                em = eval_fn(self.eval_params())
                m.update(em)
                if verbose:
                    print(f"round {r+1}: {m}")
                if target_metric is not None and em[metric_name] >= target_metric:
                    return r + 1
        return rounds
