"""FedBuff-style asynchronous buffered aggregation (DESIGN.md §14).

The fourth execution mode. The synchronous engines dispatch one S-client
cohort and block until every member reports; here the server keeps up to
``max_inflight`` (K) dispatches outstanding against whatever clients the
availability model (``core/availability.py``) says are online, buffers
completed updates as they land — out of order, possibly computed against
an older broadcast — and applies one ``ServerOptimizer`` step once
``buffer_size`` (M) of them have arrived, weighting each buffered update
by its staleness τ = current_version - dispatch_version through a
pluggable ``StalenessWeighting`` (constant / polynomial 1/(1+τ)^a /
cutoff — registered like every other strategy surface).

Per-client row semantics survive out-of-order completion: control
variates c_i, error-feedback residuals, and stateful local-solver slots
are written back through the trainer's (tiered) client stores at
*delivery* time, one row per completed dispatch (``scatter_async`` on
the PR-6 tiered store — the single I/O worker serialises them against
any concurrent gather). A dropped dispatch (the fault-injection hook:
client dies mid-round) is never delivered and its rows stay untouched.

The sync-limit equivalence contract (tests/test_async_engine.py, the
same discipline as the pipelined/scanned engines): with ``M = K =
num_sampled``, the ``always_on`` model (zero latency, no dropout), and
constant weighting, the engine is **bit-for-bit identical** to
``FederatedTrainer(pipeline_depth=0)`` — same server state, same store
rows, same metrics — because

  * ``sample_available`` over the full idle population consumes the
    sampler stream exactly like ``sample()``;
  * dispatch groups replicate ``run_round``'s client_parallel block
    (same vmap, same per-client compression keys
    ``fold_in(fold_in(fold_in(base, version), 0), position)``, same
    downlink broadcast ``fold_in(fold_in(base, version), 1)``);
  * the aggregation replays ``run_round``'s exact mean / weighted
    tensordot arithmetic and server/control updates.

History entries carry the sync-comparable keys (loss / drift /
update_norm / exact-int bytes_up / bytes_down / round) plus the async
observability block: per-aggregation staleness histogram, mean buffer
occupancy, in-flight count, dropped-update counts, virtual time, and
simulated-time rounds/s.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import (
    ServerState,
    get_algorithm,
    get_server_optimizer,
    resolve_server_optimizer,
)
from repro.core.availability import (
    AvailabilityModel,
    Dispatch,
    DispatchSimulator,
    make_availability,
)
from repro.core.compression import (
    get_compressor,
    resolve_compressor,
    resolve_downlink,
    round_comm_bytes,
)
from repro.core.local_solver import get_local_solver, resolve_local_solver
from repro.core.privatizer import get_privatizer, resolve_privatizer
from repro.core.rounds import client_update
from repro.core.store import TieredClientStore
from repro.core.tree import tree_cast, tree_mean_leading, tree_norm

# ---------------------------------------------------------------------------
# staleness-aware weighting + registry
# ---------------------------------------------------------------------------


class StalenessWeighting:
    """Per-update weight as a function of staleness τ (aggregation
    versions elapsed since the update's dispatch). ``uniform=True``
    declares the weights constant, letting the engine use the exact
    unweighted-mean arithmetic of the sync round (the bit-for-bit
    degenerate limit)."""

    name: str = ""
    uniform: bool = False

    def weights(self, tau):
        """(M,) float32 staleness values -> (M,) unnormalised weights
        (traced inside the jitted aggregation)."""
        raise NotImplementedError


class ConstantWeighting(StalenessWeighting):
    """FedBuff's plain buffered mean: staleness-blind."""

    name = "constant"
    uniform = True

    def weights(self, tau):
        return jnp.ones_like(tau)


class PolynomialWeighting(StalenessWeighting):
    """``1 / (1 + τ)^alpha`` — the standard polynomial staleness decay
    (alpha=0.5 is FedBuff's default)."""

    name = "polynomial"

    def __init__(self, alpha: float = 0.5):
        assert alpha >= 0.0, alpha
        self.alpha = float(alpha)

    def weights(self, tau):
        return 1.0 / (1.0 + tau) ** self.alpha


class CutoffWeighting(StalenessWeighting):
    """Hard staleness cutoff: weight 1 for τ <= cutoff, else 0 (an
    all-stale buffer normalises to a zero step — the aggregation is a
    harmless no-op rather than an error)."""

    name = "cutoff"

    def __init__(self, cutoff: float = 10.0):
        assert cutoff >= 0.0, cutoff
        self.cutoff = float(cutoff)

    def weights(self, tau):
        return jnp.where(tau <= self.cutoff, 1.0, 0.0)


_STALENESS: Dict[str, Callable[..., StalenessWeighting]] = {}


def register_staleness_weighting(
        name: str, factory: Callable[..., StalenessWeighting]) -> None:
    """Register a staleness-weighting *factory* under ``name``."""
    assert name, "staleness weightings must be registered under a name"
    _STALENESS[name] = factory


def make_staleness_weighting(name: str, **kwargs) -> StalenessWeighting:
    """Build a registered staleness weighting; unknown names fail loudly."""
    try:
        factory = _STALENESS[name]
    except KeyError:
        raise KeyError(
            f"unknown staleness weighting {name!r}; registered: "
            f"{staleness_weighting_names()}") from None
    return factory(**kwargs)


def staleness_weighting_names() -> Tuple[str, ...]:
    """Sorted names of all registered staleness weightings."""
    return tuple(sorted(_STALENESS))


register_staleness_weighting("constant", ConstantWeighting)
register_staleness_weighting("polynomial", PolynomialWeighting)
register_staleness_weighting("cutoff", CutoffWeighting)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class _Pending(object):
    """One dispatched-but-not-aggregated client update: the dispatch
    record, the server version it was computed against, and its row in
    the dispatch group's stacked device payload."""

    __slots__ = ("dispatch", "version", "row", "payload", "size")

    def __init__(self, dispatch: Dispatch, version: int, row: int,
                 payload: Dict[str, Any], size: float):
        self.dispatch = dispatch
        self.version = version
        self.row = row
        self.payload = payload
        self.size = size


class AsyncBufferedEngine:
    """Buffered-asynchronous execution of a ``FederatedTrainer``
    (constructed by the trainer when ``async_buffer=M`` is set; drive it
    through ``trainer.run_round()`` / ``trainer.run()`` as usual —
    one "round" = one aggregation)."""

    def __init__(self, trainer, *, buffer_size: int, max_inflight: int = 0,
                 availability: "str | AvailabilityModel" = "always_on",
                 availability_kwargs: Optional[Dict[str, Any]] = None,
                 staleness_weighting: "str | StalenessWeighting" = "constant",
                 staleness_kwargs: Optional[Dict[str, Any]] = None):
        spec = trainer.spec
        self.trainer = trainer
        self.spec = spec
        self.algo = get_algorithm(spec.algorithm)
        if self.algo.whole_batch:
            raise ValueError(
                f"async_buffer does not support the whole-batch baseline "
                f"({spec.algorithm!r}): there is no per-client update to "
                f"buffer")
        if spec.strategy != "client_parallel":
            raise ValueError(
                "async_buffer requires strategy='client_parallel' (dispatch "
                "groups are vmapped exactly like the sync round)")
        self.buffer_size = int(buffer_size)
        self.max_inflight = int(max_inflight) or spec.num_sampled
        assert self.buffer_size >= 1, buffer_size
        assert self.max_inflight >= self.buffer_size, (
            f"max_inflight={self.max_inflight} < buffer_size="
            f"{self.buffer_size}: the buffer could never fill")
        self.model = (availability if isinstance(availability,
                                                 AvailabilityModel)
                      else make_availability(availability,
                                             **(availability_kwargs or {})))
        self.weighting = (
            staleness_weighting
            if isinstance(staleness_weighting, StalenessWeighting)
            else make_staleness_weighting(staleness_weighting,
                                          **(staleness_kwargs or {})))
        self.up = get_compressor(resolve_compressor(spec))
        self.down = get_compressor(resolve_downlink(spec))
        self.solver = get_local_solver(resolve_local_solver(spec))
        # DP (DESIGN.md §16): clip/noise ride the dispatch groups exactly
        # like the sync round's client_parallel block; the privacy stream
        # folds by *version* (fold_in(fold_in(base, version), {0: clients,
        # 1: server}) with per-dispatch positions), so the degenerate sync
        # limit consumes identical noise
        self.priv = get_privatizer(resolve_privatizer(spec))
        self.sim = DispatchSimulator(self.model, trainer.sampler,
                                     spec.num_clients, self.max_inflight)
        # exact per-client wire bytes, derived from the sync round's
        # S-client accounting (history keeps exact host ints, like the
        # sync engines overwrite the fp32 device metrics)
        rb = round_comm_bytes(spec, trainer.server.x,
                              stateful_clients=self.algo.stateful_clients)
        self._round_bytes_up = int(rb["bytes_up"])
        self._round_bytes_down = int(rb["bytes_down"])

        self.version = 0                      # aggregations applied
        self._inflight: Dict[int, _Pending] = {}   # seq -> pending
        self._buffer: List[_Pending] = []
        self.dropped_total = 0
        self._delivered_since = 0
        self._dropped_since = 0
        self._dispatched_since = 0
        self._occ_sum = 0
        self._occ_n = 0
        self._ver_positions = 0   # dispatches made at the current version
        self._last_agg_clock = 0.0
        self._bcast: Optional[Tuple[int, Any, Any]] = None

        self._client_fn = jax.jit(self._make_client_fn())
        self._agg_fn = jax.jit(self._make_agg_fn())
        self._down_fn = (
            jax.jit(lambda xc, key: self.down.apply_stateless(spec, xc,
                                                              key=key))
            if self.down.name != "none" else None)

    # ------------------------------------------------------------------
    # jitted pieces — mirrors of run_round's client_parallel arithmetic
    # ------------------------------------------------------------------

    def _make_client_fn(self):
        """The client phase of one dispatch group (g clients): exactly
        ``run_round``'s client_parallel block — same vmap, same
        compression round-trip, per-client loss and post-compression
        drift rows instead of their means (the means happen at
        aggregation over the *buffered* rows)."""
        spec, solver, up, priv = self.spec, self.solver, self.up, self.priv
        grad_fn_for = self.trainer._grad_fn_for
        fused = self.trainer._use_fused_update

        def client_fn(x_cl, c_cl, c_i, batches, slots_in, res_in, k_up,
                      k_priv, positions, base):
            fn = partial(client_update, grad_fn_for(base), spec,
                         use_fused_update=fused)
            dy, dc, c_i_new, slots_new, losses = jax.vmap(
                fn, in_axes=(None, None, 0, 0, 0 if solver.stateful else None)
            )(x_cl, c_cl, c_i, batches, slots_in)
            clipped = None
            if priv.clips:
                # clip -> (distributed noise) -> compress, exactly as in
                # run_round's client_parallel block
                dy, clipped = jax.vmap(lambda d: priv.clip(spec, d))(dy)
                if priv.noise_at == "client":
                    pkeys = jax.vmap(
                        lambda i: jax.random.fold_in(k_priv, i))(positions)
                    dy = jax.vmap(
                        lambda d, k: priv.client_noise(spec, d, k))(dy, pkeys)
            res_new = None
            if up.name != "none":
                res = res_in if res_in is not None else up.init_residual(dy)
                if up.needs_key:
                    keys = jax.vmap(
                        lambda i: jax.random.fold_in(k_up, i))(positions)
                    dy, res_new = jax.vmap(
                        lambda d, r, k: up.round_trip(spec, d, r, key=k))(
                            dy, res, keys)
                else:
                    dy, res_new = jax.vmap(
                        lambda d, r: up.round_trip(spec, d, r))(dy, res)
            return dy, dc, c_i_new, res_new, slots_new, losses, clipped

        return client_fn

    def _make_agg_fn(self):
        """One buffered aggregation: ``run_round``'s exact aggregation +
        server-step arithmetic over the M buffered rows. Constant
        weighting + unweighted spec takes the identical
        ``tree_mean_leading`` path; anything else goes through the same
        normalised fp32 tensordot as the sync weighted case, with the
        staleness weights folded in."""
        spec, algo, weighting = self.spec, self.algo, self.weighting
        opt = get_server_optimizer(resolve_server_optimizer(spec))
        weighted = spec.weighted_aggregation
        priv = self.priv

        def agg_fn(server, dy, dc, losses, tau, sizes, noise_key):
            if weighting.uniform and not weighted:
                dy_mean = tree_mean_leading(dy)
                dc_mean = tree_mean_leading(dc)
            else:
                w = weighting.weights(tau.astype(jnp.float32))
                if weighted:
                    w = w * sizes.astype(jnp.float32)
                wnorm = w / jnp.maximum(w.sum(), 1e-12)

                def wmean(tree):
                    return jax.tree.map(
                        lambda a: jnp.tensordot(
                            wnorm, a.astype(jnp.float32),
                            axes=(0, 0)).astype(a.dtype), tree)

                dy_mean = wmean(dy)
                dc_mean = wmean(dc)
            if priv.noise_at == "server":
                dy_mean = priv.server_noise(spec, dy_mean, noise_key)
            x_new, opt_state_new, applied = opt.apply(
                spec, server.opt_state, server.x, dy_mean)
            c_new = algo.server_control_update(spec, server.c, dc_mean)
            metrics = {"loss": jnp.mean(losses),
                       "drift": jnp.mean(jax.vmap(tree_norm)(dy)),
                       "update_norm": tree_norm(applied)}
            return (ServerState(x=x_new, c=c_new, opt_state=opt_state_new),
                    metrics)

        return agg_fn

    # ------------------------------------------------------------------
    # dispatch / deliver / aggregate
    # ------------------------------------------------------------------

    def _broadcast(self):
        """The (x, c) the current version's dispatches receive — the
        downlink-compressed broadcast, computed once per version with
        the sync round's key ``fold_in(fold_in(base, version), 1)``."""
        if self._bcast is not None and self._bcast[0] == self.version:
            return self._bcast[1], self._bcast[2]
        tr = self.trainer
        x, c = tr.server.x, tr.server.c
        if self._down_fn is None:
            x_cl, c_cl = x, c
        else:
            key = None
            if tr._comp_keyed:
                key = jax.random.fold_in(
                    jax.random.fold_in(tr._comp_base_key, self.version), 1)
            x_cl, c_cl = self._down_fn((x, c), key)
        self._bcast = (self.version, x_cl, c_cl)
        return x_cl, c_cl

    def _fill(self) -> int:
        """Dispatch to newly-available clients (up to the free in-flight
        slots) and compute their updates eagerly against the current
        broadcast. Host-RNG consumption order matches the sync loop:
        sampler draw, then ``dataset.round_batches`` on the data rng."""
        dispatches = self.sim.fill()
        if not dispatches:
            return 0
        tr = self.trainer
        g = len(dispatches)
        ids = np.fromiter((d.client for d in dispatches), np.int64, g)
        self._dispatched_since += g
        x_cl, c_cl = self._broadcast()
        c_i = tr.store.gather(ids)
        res = (tr.residual_store.gather(ids)
               if tr.residual_store is not None else None)
        slots = (tr.solver_store.gather(ids)
                 if tr.solver_store is not None else None)
        sizes = None
        if self.spec.weighted_aggregation:
            sizes = np.asarray(tr.dataset.client_sizes(ids), np.float32)
        batches = tr.dataset.round_batches(
            ids, self.spec.local_steps, self.spec.local_batch, tr._rng)
        k_up = k_priv = positions = None
        priv_client = self.priv.noise_at == "client"
        if tr._comp_keyed or priv_client:
            positions = jnp.arange(self._ver_positions,
                                   self._ver_positions + g, dtype=jnp.int32)
        if tr._comp_keyed:
            k_up = jax.random.fold_in(
                jax.random.fold_in(tr._comp_base_key, self.version), 0)
        if priv_client:
            k_priv = jax.random.fold_in(
                jax.random.fold_in(tr._priv_base_key, self.version), 0)
        self._ver_positions += g
        dy, dc, c_i_new, res_new, slots_new, losses, clipped = (
            self._client_fn(x_cl, c_cl, c_i, batches, slots, res, k_up,
                            k_priv, positions, tr.base_params))
        payload = {"dy": dy, "dc": dc, "c_i": c_i_new, "loss": losses}
        if self.up.stateful:
            payload["residual"] = res_new
        if self.solver.stateful:
            payload["solver"] = slots_new
        if self.priv.clips:
            payload["clipped"] = clipped
        for row, d in enumerate(dispatches):
            self._inflight[d.seq] = _Pending(
                d, self.version, row, payload,
                float(sizes[row]) if sizes is not None else 1.0)
        return g

    @staticmethod
    def _scatter_row(store, ids1, rows) -> None:
        if isinstance(store, TieredClientStore):
            store.scatter_async(ids1, rows)
        else:
            store.scatter(ids1, rows)

    def _deliver(self, p: _Pending) -> None:
        """A dispatch completed: write its c_i / residual / solver rows
        back (per-client row semantics survive out-of-order completion)
        and buffer the update for the next aggregation."""
        tr = self.trainer
        i = p.row
        ids1 = np.array([p.dispatch.client], np.int64)

        def row(tree):
            return jax.tree.map(lambda a: np.asarray(a[i])[None], tree)

        if self.algo.stateful_clients:
            self._scatter_row(tr.store, ids1, row(p.payload["c_i"]))
        if tr.residual_store is not None:
            self._scatter_row(tr.residual_store, ids1,
                              row(p.payload["residual"]))
        if tr.solver_store is not None:
            self._scatter_row(tr.solver_store, ids1, row(p.payload["solver"]))
        self._buffer.append(p)
        self._delivered_since += 1
        self._occ_sum += len(self._buffer)
        self._occ_n += 1

    def _aggregate(self) -> Dict[str, float]:
        """Apply one server step over the M buffered updates and emit
        the history entry (sync-comparable keys + observability)."""
        tr, buf = self.trainer, self._buffer
        self._buffer = []

        def stack(key):
            rows = [jax.tree.map(lambda a: a[p.row], p.payload[key])
                    for p in buf]
            return jax.tree.map(lambda *r: jnp.stack(r), *rows)

        dy, dc = stack("dy"), stack("dc")
        losses = jnp.stack([p.payload["loss"][p.row] for p in buf])
        tau_np = np.array([self.version - p.version for p in buf], np.int64)
        sizes = (jnp.asarray([p.size for p in buf], jnp.float32)
                 if self.spec.weighted_aggregation else None)
        noise_key = None
        if self.priv.noise_at == "server":
            # the sync round's server draw: fold_in(fold_in(base, t), 1)
            noise_key = jax.random.fold_in(
                jax.random.fold_in(tr._priv_base_key, self.version), 1)
        clip_frac = None
        if self.priv.clips:
            clip_frac = jnp.mean(
                jnp.stack([p.payload["clipped"][p.row] for p in buf]))
        server, metrics = self._agg_fn(
            tr.server, dy, dc, losses,
            jnp.asarray(tau_np, jnp.int32), sizes, noise_key)
        tr.server = server
        self.version += 1
        tr.round_idx = self.version
        self._ver_positions = 0
        self._bcast = None

        S = self.spec.num_sampled
        out = {k: float(v) for k, v in metrics.items()}
        # exact host-int wire accounting: bytes actually moved since the
        # previous aggregation (per-client bytes = the sync round's
        # S-client totals / S)
        out["bytes_up"] = float(
            self._delivered_since * self._round_bytes_up // S)
        out["bytes_down"] = float(
            self._dispatched_since * self._round_bytes_down // S)
        if self.priv.name != "none":
            # exact float64 accountant, like the sync engines' overwrite
            out["dp_epsilon"] = self.priv.epsilon(self.spec, self.version)
            if clip_frac is not None:
                out["dp_clipped_frac"] = float(clip_frac)
        out["round"] = self.version
        # async observability
        out["staleness_mean"] = float(tau_np.mean())
        out["staleness_max"] = int(tau_np.max())
        out["staleness_hist"] = np.bincount(tau_np).tolist()
        out["buffer_occupancy"] = self._occ_sum / max(self._occ_n, 1)
        out["inflight"] = len(self._inflight)
        out["dispatched"] = self._dispatched_since
        out["dropped"] = self._dropped_since
        out["dropped_total"] = self.dropped_total
        out["sim_time"] = self.sim.clock
        dt = self.sim.clock - self._last_agg_clock
        out["sim_rounds_per_s"] = (1.0 / dt) if dt > 0 else 0.0
        self._delivered_since = 0
        self._dropped_since = 0
        self._dispatched_since = 0
        self._occ_sum = self._occ_n = 0
        self._last_agg_clock = self.sim.clock
        if tr.megakernel_fallback_reason is not None:
            out["megakernel_fallback_reason"] = tr.megakernel_fallback_reason
        if tr.update_space.trains_subset:
            out["update_space"] = tr.update_space.name
        tr.history.append(out)
        return out

    def run_round(self) -> Dict[str, float]:
        """Advance virtual time until one aggregation fires."""
        sim = self.sim
        idle_advances = 0
        while True:
            if sim.should_fill():
                if self._fill():
                    idle_advances = 0
            if not sim.pending():
                # nothing in flight and nobody dispatchable: jump to the
                # next availability window (loud error if there is none)
                sim.advance_to_available()
                idle_advances += 1
                if idle_advances > 100_000:
                    raise RuntimeError(
                        "async engine made no dispatch across 100000 "
                        "availability windows — availability model starves "
                        "the fleet")
                continue
            d = sim.pop()
            p = self._inflight.pop(d.seq)
            if d.dropped:
                # fault injection: the update never arrives; c_i /
                # residual / solver rows stay untouched
                self.dropped_total += 1
                self._dropped_since += 1
                continue
            self._deliver(p)
            if len(self._buffer) >= self.buffer_size:
                return self._aggregate()

    # ------------------------------------------------------------------
    # checkpoint / resume (checkpoint/checkpoint.py)
    # ------------------------------------------------------------------
    # In-flight and buffered updates are durably recorded: their stacked
    # payload rows ride the .npz under "async" and their dispatch records
    # ride the JSON metadata, so a restored engine replays the exact
    # event sequence without recomputing (deterministic resume even
    # though the updates were computed against broadcasts that no longer
    # exist).

    _META_FIELDS = ("delivered_since", "dropped_since", "dispatched_since",
                    "occ_sum", "occ_n", "ver_positions")

    def _payload_keys(self) -> Tuple[str, ...]:
        keys = ["dy", "dc", "c_i", "loss"]
        if self.up.stateful:
            keys.append("residual")
        if self.solver.stateful:
            keys.append("solver")
        if self.priv.clips:
            keys.append("clipped")
        return tuple(keys)

    def _row_template(self) -> Dict[str, Any]:
        """Shape/dtype templates of one pending update's payload row."""
        x = jax.tree.map(jnp.asarray, self.trainer.server.x)
        c = jax.tree.map(jnp.asarray, self.trainer.server.c)
        scalar = jnp.zeros((), jnp.float32)
        tmpl = {"dy": x, "dc": c, "c_i": x, "loss": scalar}
        if self.up.stateful:
            tmpl["residual"] = tree_cast(x, jnp.float32)
        if self.solver.stateful:
            tmpl["solver"] = self.solver.init(self.spec, x)
        if self.priv.clips:
            tmpl["clipped"] = scalar
        return tmpl

    def _pending_in_order(self) -> Tuple[List[_Pending], List[_Pending]]:
        infl = sorted(self._inflight.values(), key=lambda p: p.dispatch.seq)
        return infl, list(self._buffer)

    def checkpoint_tree(self) -> Dict[str, Any]:
        """(P, ...) stacked payload rows of every pending update
        (in-flight first, by seq; then the buffer in delivery order) +
        the per-client dispatch counters."""
        infl, buf = self._pending_in_order()
        pend = infl + buf
        tmpl = self._row_template()
        tree: Dict[str, Any] = {}
        for key in self._payload_keys():
            if pend:
                rows = [jax.tree.map(lambda a: np.asarray(a[p.row]),
                                     p.payload[key]) for p in pend]
                tree[key] = jax.tree.map(lambda *r: np.stack(r), *rows)
            else:
                tree[key] = jax.tree.map(
                    lambda a: np.zeros((0,) + a.shape, a.dtype), tmpl[key])
        tree["dispatch_k"] = self.sim.dispatch_k.copy()
        return tree

    def checkpoint_meta(self) -> Dict[str, Any]:
        """JSON-serializable event state: dispatch records of every
        pending update + the simulator scalars and counters."""
        infl, buf = self._pending_in_order()

        def rec(p: _Pending) -> Dict[str, Any]:
            d = p.dispatch
            return {"seq": d.seq, "client": d.client, "k": d.k,
                    "time": d.time, "latency": d.latency,
                    "dropped": d.dropped, "complete_t": d.complete_t,
                    "version": p.version, "size": p.size}

        meta = {"version": self.version,
                "clock": self.sim.clock,
                "seq": self.sim.seq,
                "dropped_total": self.dropped_total,
                "last_agg_clock": self._last_agg_clock,
                "inflight": [rec(p) for p in infl],
                "buffer": [rec(p) for p in buf]}
        for f in self._META_FIELDS:
            meta[f] = getattr(self, "_" + f)
        return meta

    def pending_template(self, meta: Dict[str, Any]) -> Dict[str, Any]:
        """The checkpoint_tree-shaped template for ``meta``'s pending
        count (load_checkpoint matches shapes against it)."""
        p_count = len(meta["inflight"]) + len(meta["buffer"])
        tmpl = self._row_template()
        tree = {key: jax.tree.map(
                    lambda a: np.zeros((p_count,) + a.shape, a.dtype),
                    tmpl[key])
                for key in self._payload_keys()}
        tree["dispatch_k"] = np.zeros(self.spec.num_clients, np.int64)
        return tree

    def restore(self, tree: Dict[str, Any], meta: Dict[str, Any]) -> None:
        """Rebuild pending updates + simulator state; the trainer-side
        state (server, stores, RNGs, round counter) is restored by
        ``checkpoint.load_trainer`` around this call."""
        recs = list(meta["inflight"]) + list(meta["buffer"])
        n_inflight = len(meta["inflight"])
        payload = {key: jax.tree.map(np.asarray, tree[key])
                   for key in self._payload_keys()}
        pend = []
        for row, r in enumerate(recs):
            d = Dispatch(int(r["seq"]), int(r["client"]), int(r["k"]),
                         float(r["time"]), float(r["latency"]),
                         bool(r["dropped"]), float(r["complete_t"]))
            pend.append(_Pending(d, int(r["version"]), row, payload,
                                 float(r["size"])))
        self.version = int(meta["version"])
        self._inflight = {p.dispatch.seq: p for p in pend[:n_inflight]}
        self._buffer = pend[n_inflight:]
        self.dropped_total = int(meta["dropped_total"])
        self._last_agg_clock = float(meta["last_agg_clock"])
        for f in self._META_FIELDS:
            setattr(self, "_" + f, int(meta[f]))
        self._bcast = None
        self.sim.restore(float(meta["clock"]), int(meta["seq"]),
                         tree["dispatch_k"],
                         [p.dispatch for p in pend[:n_inflight]])
