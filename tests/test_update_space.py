"""UpdateSpace-registry contract tests (DESIGN.md §17).

The ninth registry maps full parameters <-> the trainable-delta pytree
every engine operates on. The suite asserts, across the four execution
modes:

  * ``full`` is bit-for-bit the pre-registry trajectory — a spec that
    never mentions the update-space fields and an explicit
    ``update_space='full'`` produce identical metrics and state in the
    sync, pipelined, scanned and async engines (and no ``update_space``
    marker appears in history),
  * ``lora`` scanned == host loop bitwise — R host-loop rounds on the
    scanned engine's RNG contract (delta-space grad fn, delta-shaped
    ``{c_i[, residual][, solver]}`` store rows) match one scanned chunk
    exactly, including mid-chunk checkpoint-resume and the cross-engine
    checkpoint (whose load verifies the frozen base bitwise),
  * hypothesis contracts — ``apply(base, init_deltas(...)) == base``
    bitwise, the closed-form ``grad_project`` equals both autodiff
    through ``apply`` and the generic vjp default, rank-0 degeneracy is
    rejected loudly, and per-round payload bytes are strictly ordered
    ``full > lora(2r) > lora(r)``,
  * the closed train->serve loop — a reduced-LM config federated-trains
    with lora rank 8 at >= 50x smaller ``bytes_up`` than the full
    baseline, and its merged checkpoint decodes through the
    ``launch/serve.py`` path (the ISSUE-10 acceptance test).
"""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    # Degrade per-test instead of importorskip'ing the module: the
    # registry / engine / integration tests below need no hypothesis
    # and must run everywhere. The skip reason matches check_skips.py's
    # missing-optional-dependency pattern so CI still proves the
    # property tests execute there.
    def given(**kw):
        return lambda fn: pytest.mark.skip(
            reason="could not import 'hypothesis'")(fn)

    def settings(**kw):
        return lambda fn: fn

    class st:  # noqa: N801 — stands in for hypothesis.strategies
        integers = staticmethod(lambda a, b: None)
        floats = staticmethod(lambda a, b: None)

from functools import partial

from repro.checkpoint import (
    load_serving_params,
    load_trainer,
    save_trainer,
)
from repro.configs.base import FedRoundSpec
from repro.core import (
    ClientRoundState,
    ClientStateStore,
    FederatedTrainer,
    FullSpace,
    LoRASpace,
    UpdateSpace,
    device_sample_ids,
    get_update_space,
    init_server_state,
    make_grad_fn,
    register_update_space,
    resolve_update_space,
    run_round,
    update_space_names,
)
from repro.core.compression import round_comm_bytes
from repro.core.update_space import DEFAULT_LORA_TARGETS, leaf_paths
from repro.data import (
    EmnistLikeFederated,
    SyntheticLMFederated,
    make_similarity_quadratics,
    quadratic_loss,
)
from repro.models.simple import mlp_init, mlp_loss

N, S, K, DIM = 8, 3, 2, 6
HIDDEN = 16
ROUNDS = 3

LORA_KW = dict(update_space="lora", lora_rank=2, update_targets="w1,w2")


def _spec(**kw):
    base = dict(algorithm="scaffold", num_clients=N, num_sampled=S,
                local_steps=K, local_batch=4, eta_l=0.1, eta_g=0.7)
    base.update(kw)
    return FedRoundSpec(**base)


def _mlp_init(key):
    return mlp_init(key, 784, 62, hidden=HIDDEN)


def _mlp_dataset():
    return EmnistLikeFederated(num_clients=N, samples=400,
                               similarity_pct=0.0, seed=0, test_samples=40)


def _mlp_trainer(spec, seed=0, **kw):
    return FederatedTrainer(mlp_loss, _mlp_init, spec, _mlp_dataset(),
                            seed=seed, **kw)


def _quad_trainer(spec, seed=0, **kw):
    ds = make_similarity_quadratics(N, DIM, delta=0.3, G=4.0, mu=0.3, seed=1)
    init = lambda key: {"x": jnp.ones((DIM,), jnp.float32)}
    return FederatedTrainer(quadratic_loss, init, spec, ds, seed=seed, **kw)


def _state(tr):
    ids = np.arange(tr.store.num_clients)
    leaves = (jax.tree.leaves(tr.x) + jax.tree.leaves(tr.c)
              + jax.tree.leaves(tr.server.opt_state)
              + jax.tree.leaves(tr.store.gather(ids)))
    if tr.residual_store is not None:
        leaves += jax.tree.leaves(tr.residual_store.gather(ids))
    if tr.solver_store is not None:
        leaves += jax.tree.leaves(tr.solver_store.gather(ids))
    return [np.asarray(leaf) for leaf in leaves]


def _assert_bitwise(a, b):
    assert len(a) == len(b)
    for xa, xb in zip(a, b):
        np.testing.assert_array_equal(xa, xb)


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


# ------------------------------------------------------------- registry


def test_registry_lists_builtins():
    assert update_space_names() == ["full", "head_only", "lora"]
    assert isinstance(get_update_space("full"), FullSpace)
    with pytest.raises(KeyError, match="known"):
        get_update_space("nope")
    assert resolve_update_space(_spec()) == "full"
    assert resolve_update_space(_spec(**LORA_KW)) == "lora"


def test_register_custom_subclass_inherits_validation():
    """The docs/REGISTRIES.md §9 worked example: a LoRASpace subclass
    registered under a new name keeps ``uses_rank``, so the spec accepts
    ``lora_rank`` for it (validation is attribute-driven, not
    name-matched)."""

    class LoRANoW2(LoRASpace):
        name = "lora_no_w2_test"

        def targets(self, spec, params):
            return [(p, l) for p, l in super().targets(spec, params)
                    if not p.endswith("w2")]

    from repro.core.update_space import _UPDATE_SPACES

    register_update_space(LoRANoW2())
    try:
        spec = _spec(update_space="lora_no_w2_test", lora_rank=2,
                     update_targets="w1,w2")
        space = get_update_space(resolve_update_space(spec))
        deltas = space.init_deltas(spec, _mlp_init(jax.random.key(0)),
                                   jax.random.key(4))
        assert list(deltas) == ["w1"]
    finally:
        _UPDATE_SPACES.pop("lora_no_w2_test", None)


def test_spec_validation_rejections():
    """Meaningless update-space combinations fail loudly at spec
    construction — including the rank-0 degeneracy (an adapter that
    trains nothing)."""
    with pytest.raises(AssertionError):
        _spec(update_space="nope")
    with pytest.raises(AssertionError, match="needs lora_rank >= 1"):
        _spec(update_space="lora")
    with pytest.raises(AssertionError, match="needs lora_rank >= 1"):
        _spec(update_space="lora", lora_rank=0)
    with pytest.raises(AssertionError, match="needs update_targets"):
        _spec(update_space="head_only")
    with pytest.raises(AssertionError, match="has no effect"):
        _spec(lora_rank=4)
    with pytest.raises(AssertionError, match="has no effect"):
        _spec(lora_alpha=1.0)
    with pytest.raises(AssertionError, match="has no effect"):
        _spec(update_targets="w1")


def test_rank_zero_rejected_in_space_too():
    """Defense in depth: the space itself rejects rank 0 even when driven
    by a raw spec-like object that bypassed FedRoundSpec validation."""
    shim = SimpleNamespace(lora_rank=0, lora_alpha=0.0, update_targets="")
    with pytest.raises(ValueError, match="rank 0 would train nothing"):
        get_update_space("lora").init_deltas(
            shim, _mlp_init(jax.random.key(0)))


def test_lora_on_vector_params_fails_loudly():
    """The paper's 1-D quadratics have no matmul weights: lora must name
    the offending leaves instead of silently training nothing."""
    with pytest.raises(ValueError, match=">=2-D"):
        _quad_trainer(_spec(algorithm="scaffold", update_space="lora",
                            lora_rank=2, update_targets="x"))


def test_lora_unmatched_targets_fail_loudly():
    with pytest.raises(ValueError, match="matched no parameters"):
        _mlp_trainer(_spec(update_space="lora", lora_rank=2,
                           update_targets="wq"))


# ----------------------------- full == pre-registry, all four engines


ENGINES = {
    "sync": {},
    "pipelined": dict(pipeline_depth=2),
    "scanned": dict(scan_rounds=2),
    "async": dict(async_buffer=S, max_inflight=S),
}


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_full_space_is_bitwise_pre_registry(engine):
    """update_space='full' (and the '' default) takes zero hooks: in every
    engine the trajectory is bit-for-bit the one from a spec that never
    mentions the update-space fields, no base is frozen, and no
    ``update_space`` marker rides the metrics."""
    kw = ENGINES[engine]
    a = _quad_trainer(_spec(), **kw)
    b = _quad_trainer(_spec(update_space="full", lora_rank=0,
                            lora_alpha=0.0, update_targets=""), **kw)
    assert a.base_params is None and b.base_params is None
    if engine == "scanned":
        assert a.scan_active and b.scan_active
    if engine == "async":
        assert a.async_active and b.async_active
    for _ in range(4):
        ma, mb = a.run_round(), b.run_round()
        assert ma == mb
        assert "update_space" not in ma
    _assert_bitwise(_state(a), _state(b))
    _assert_tree_equal(a.eval_params(), a.x)


# --------------------------------------- lora scanned == host loop


def _host_loop_lora(spec, ds, rounds, seed=0):
    """R host-loop rounds of the *delta-space* round on the scanned
    engine's RNG contract (the test_scan_engine.py helper generalised to
    a non-identity update space): the grad fn differentiates in delta
    space against the frozen base, and the ``{c_i[, residual][,
    solver]}`` store row families are templated off the delta tree —
    exactly what the trainer does.

    Returns ``(server, stores, hist)`` with the trainer's device-store
    layout for wholesale comparison."""
    from repro.core import (
        get_compressor,
        get_local_solver,
        resolve_compressor,
        resolve_local_solver,
    )
    from repro.core.compression import resolve_downlink
    from repro.core.tree import tree_cast

    space = get_update_space(resolve_update_space(spec))
    full = _mlp_init(jax.random.key(seed))
    deltas0 = space.init_deltas(spec, full, jax.random.key(seed + 4))
    grad_fn = make_grad_fn(mlp_loss, space=space, spec=spec,
                           base_params=full)
    data = ds.device_data()
    bf = jax.jit(ds.device_batch_fn(spec.local_steps, spec.local_batch))
    skey, dkey = jax.random.key(seed), jax.random.key(seed + 1)
    comp = get_compressor(resolve_compressor(spec))
    solver = get_local_solver(resolve_local_solver(spec))
    keyed = (comp.needs_key
             or get_compressor(resolve_downlink(spec)).needs_key)
    ckey = jax.random.key(seed + 2) if keyed else None
    samp = jax.jit(partial(device_sample_ids, num_clients=spec.num_clients,
                           num_sampled=spec.num_sampled))
    rj = jax.jit(lambda s, c, b, k: run_round(grad_fn, spec, s, c, b,
                                              comp_key=k))
    server = init_server_state(spec, deltas0)
    c_store = ClientStateStore(deltas0, spec.num_clients)
    res_store = (ClientStateStore(tree_cast(deltas0, jnp.float32),
                                  spec.num_clients)
                 if comp.stateful else None)
    slot_store = (ClientStateStore(solver.init(spec, deltas0),
                                   spec.num_clients)
                  if solver.stateful else None)
    hist = []
    for t in range(rounds):
        ids = np.asarray(samp(skey, t))
        batches = bf(data, jnp.asarray(ids), jax.random.fold_in(dkey, t))
        clients = ClientRoundState(
            c_i=jax.tree.map(jnp.asarray, c_store.gather(ids)),
            uplink_residual=(jax.tree.map(jnp.asarray, res_store.gather(ids))
                             if res_store is not None else None),
            solver_slots=(jax.tree.map(jnp.asarray, slot_store.gather(ids))
                          if slot_store is not None else None))
        ck = jax.random.fold_in(ckey, t) if keyed else None
        out = rj(server, clients, batches, ck)
        server = out.server
        c_store.scatter(ids, out.clients.c_i)
        if res_store is not None:
            res_store.scatter(ids, out.clients.uplink_residual)
        if slot_store is not None:
            slot_store.scatter(ids, out.clients.solver_slots)
        hist.append({k: float(v) for k, v in out.metrics.items()})
    all_ids = np.arange(spec.num_clients)
    if res_store is not None or slot_store is not None:
        stores = {"c_i": c_store.gather(all_ids)}
        if res_store is not None:
            stores["residual"] = res_store.gather(all_ids)
        if slot_store is not None:
            stores["solver"] = slot_store.gather(all_ids)
    else:
        stores = c_store.gather(all_ids)
    return server, stores, hist


@pytest.mark.parametrize("compress,solver", [
    ("none", "sgd"),
    ("int8_ef", "sgd"),
    ("none", "momentum"),
    ("int8_ef", "adam"),
], ids=["plain", "residual-rows", "solver-rows", "residual+solver-rows"])
def test_lora_scanned_matches_host_loop(compress, solver):
    """One scanned chunk of R delta-space rounds == R host-loop rounds,
    bitwise — server deltas, delta-shaped control variates, optimizer
    slots, and the whole delta-shaped ``{c_i[, residual][, solver]}``
    device store."""
    spec = _spec(**LORA_KW, compress=compress, local_solver=solver,
                 local_momentum=0.9 if solver != "sgd" else 0.0)
    ds = _mlp_dataset()
    server_h, stores_h, hist_h = _host_loop_lora(spec, ds, ROUNDS)
    tr = _mlp_trainer(spec, scan_rounds=ROUNDS)
    assert tr.scan_active, tr.scan_fallback_reason
    tr.run(ROUNDS)
    _assert_tree_equal(server_h.x, tr.x)
    _assert_tree_equal(server_h.c, tr.c)
    _assert_tree_equal(server_h.opt_state, tr.server.opt_state)
    _assert_tree_equal(stores_h, tr.device_store)
    assert all(h["update_space"] == "lora" for h in tr.history)
    assert hist_h == [
        {k: v for k, v in h.items() if k not in ("round", "update_space")}
        for h in tr.history]


def test_lora_delta_shapes_and_bytes():
    """The engine state is delta-shaped end to end: c/c_i rows carry the
    {A, B} factor tree, and the per-round bytes metrics equal the exact
    host-side accounting of the *delta* payload — several times smaller
    than the full baseline's."""
    spec = _spec(**LORA_KW)
    tr = _mlp_trainer(spec)
    shapes = {p: jnp.shape(l) for p, l in leaf_paths(tr.x)}
    assert shapes == {"w1.A": (784, 2), "w1.B": (2, HIDDEN),
                      "w2.A": (HIDDEN, 2), "w2.B": (2, 62)}
    row = tr.store.gather(np.arange(1))
    assert (jax.tree.structure(row) == jax.tree.structure(tr.x)
            and all(np.shape(r)[1:] == np.shape(x) for r, x in
                    zip(jax.tree.leaves(row), jax.tree.leaves(tr.x))))
    m = tr.run_round()
    exact = round_comm_bytes(spec, tr.x, stateful_clients=True)
    assert m["bytes_up"] == exact["bytes_up"]
    assert m["bytes_down"] == exact["bytes_down"]
    full = round_comm_bytes(_spec(), _mlp_init(jax.random.key(0)),
                            stateful_clients=True)
    assert full["bytes_up"] > 4 * m["bytes_up"]


def test_lora_checkpoint_resume_mid_chunk(tmp_path):
    """Checkpoint after 5 rounds (mid-chunk for scan_rounds=3), restore,
    continue — bitwise equal to the unbroken run, with the delta-shaped
    residual + solver store rows riding the same .npz keys."""
    spec = _spec(**LORA_KW, compress="int8_ef", local_solver="momentum")
    unbroken = _mlp_trainer(spec, scan_rounds=3)
    unbroken.run(8)
    a = _mlp_trainer(spec, scan_rounds=3)
    a.run(5)
    path = str(tmp_path / "ck.npz")
    save_trainer(path, a)
    b = _mlp_trainer(spec, scan_rounds=3)
    load_trainer(path, b)
    assert b.round_idx == 5
    b.run(3)
    _assert_tree_equal(unbroken.x, b.x)
    _assert_tree_equal(unbroken.c, b.c)
    _assert_tree_equal(unbroken.server.opt_state, b.server.opt_state)
    _assert_tree_equal(unbroken.device_store, b.device_store)


def test_lora_checkpoint_crosses_engines(tmp_path):
    """A scanned lora checkpoint restores into a host-loop trainer: the
    load verifies the frozen base bitwise (a stale base would silently
    poison every jitted closure) and the delta stores transfer."""
    spec = _spec(**LORA_KW)
    a = _mlp_trainer(spec, scan_rounds=2)
    a.run(2)
    path = str(tmp_path / "ck.npz")
    save_trainer(path, a)
    host = _mlp_trainer(spec)
    load_trainer(path, host)
    _assert_tree_equal(a.x, host.x)
    _assert_tree_equal(a.base_params, host.base_params)
    a.sync_host_store()
    _assert_tree_equal(a.store.gather(np.arange(N)),
                       host.store.gather(np.arange(N)))


def test_checkpoint_space_mismatch_refused(tmp_path):
    spec = _spec(**LORA_KW)
    a = _mlp_trainer(spec)
    a.run(1)
    path = str(tmp_path / "ck.npz")
    save_trainer(path, a)
    with pytest.raises(ValueError, match="update_space='lora'"):
        load_trainer(path, _mlp_trainer(_spec()))
    # same space, different frozen base (different seed): refused too
    with pytest.raises(ValueError, match="base"):
        load_trainer(path, _mlp_trainer(spec, seed=1))


# ------------------------------------------------- engine cross-checks


def test_lora_pipelined_and_async_match_sync():
    """The delta-space round is engine-agnostic: pipelined and the async
    degenerate limit reproduce the sync trainer bitwise."""
    spec = _spec(**LORA_KW)
    sync = _mlp_trainer(spec)
    pipe = _mlp_trainer(spec, pipeline_depth=2)
    poof = _mlp_trainer(spec, async_buffer=S, max_inflight=S)
    assert poof.async_active
    for _ in range(ROUNDS):
        ms, mp, ma = sync.run_round(), pipe.run_round(), poof.run_round()
        assert ms == mp
        assert ms["update_space"] == ma["update_space"] == "lora"
        for key in ("loss", "bytes_up", "bytes_down", "round"):
            assert ms[key] == ma[key], (key, ms[key], ma[key])
    _assert_bitwise(_state(sync), _state(pipe))
    _assert_bitwise(_state(sync), _state(poof))


def test_head_only_trains_only_the_head():
    """head_only freezes everything outside the selection: the merged
    eval params keep the frozen leaves bitwise while the trained head
    moves."""
    spec = _spec(update_space="head_only", update_targets="w2,b2")
    tr = _mlp_trainer(spec)
    base = jax.tree.map(np.asarray, tr.base_params)
    tr.run(2)
    merged = tr.eval_params()
    np.testing.assert_array_equal(np.asarray(merged["w1"]), base["w1"])
    np.testing.assert_array_equal(np.asarray(merged["b1"]), base["b1"])
    assert not np.array_equal(np.asarray(merged["w2"]), base["w2"])
    assert tr.update_space.num_params(tr.x) < sum(
        v.size for v in jax.tree.leaves(base))


def test_delta_tree_partition_specs():
    """dist layer: a stacked-layer LoRA delta tree ("layers.wq/A" with
    (L, in, r) leaves) partitions under the same shape-driven rules as
    the full parameters — the layer-stack dim stays unsharded."""
    from repro.dist import partition_params
    from repro.launch.mesh import make_debug_mesh

    deltas = {
        "layers.wq": {"A": jnp.zeros((4, 64, 8), jnp.float32),
                      "B": jnp.zeros((4, 8, 64), jnp.float32)},
        "unembed": {"A": jnp.zeros((64, 8), jnp.float32),
                    "B": jnp.zeros((8, 256), jnp.float32)},
    }
    mesh = make_debug_mesh(1, 1)
    sh = partition_params(jax.eval_shape(lambda: deltas), mesh, "fsdp")
    assert jax.tree.structure(sh) == jax.tree.structure(deltas)
    for spec in jax.tree.leaves(
            jax.tree.map(lambda s: s.spec, sh),
            is_leaf=lambda x: hasattr(x, "index")):
        assert spec[0] is None  # stack / leading dim unsharded at (4,...)


# ------------------------------------------------- hypothesis contracts


def _rand_params(seed, d, h, c):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return {"w1": jax.random.normal(k1, (d, h), jnp.float32),
            "w2": jax.random.normal(k2, (h, c), jnp.float32)}


@settings(max_examples=20, deadline=None)
@given(rank=st.integers(1, 4), seed=st.integers(0, 2 ** 16),
       alpha=st.floats(0.5, 4.0))
def test_lora_apply_grad_project_round_trip(rank, seed, alpha):
    """init is merge-neutral (apply(base, init) == base bitwise, B = 0),
    and the closed-form grad_project is the exact chain rule: it matches
    both autodiff through apply and the generic vjp default."""
    shim = SimpleNamespace(lora_rank=rank, lora_alpha=alpha,
                           update_targets="w1,w2")
    space = get_update_space("lora")
    base = _rand_params(seed, 12, 7, 5)
    init = space.init_deltas(shim, base, jax.random.key(seed))
    _assert_tree_equal(space.apply(shim, base, init), base)
    # move off B=0 so both factor gradients are non-trivial
    deltas = jax.tree.map(
        lambda x: x + jax.random.normal(jax.random.key(seed + 1), x.shape,
                                        x.dtype) * 0.1, init)

    def f(full):
        return (jnp.sum(full["w1"] ** 2) * 0.5
                + jnp.sum(jnp.sin(full["w2"])))

    auto = jax.grad(lambda d: f(space.apply(shim, base, d)))(deltas)
    full_g = jax.grad(f)(space.apply(shim, base, deltas))
    closed = space.grad_project(shim, base, deltas, full_g)
    generic = UpdateSpace.grad_project(space, shim, base, deltas, full_g)
    for got in (closed, generic):
        assert jax.tree.structure(got) == jax.tree.structure(auto)
        for xa, xb in zip(jax.tree.leaves(auto), jax.tree.leaves(got)):
            np.testing.assert_allclose(np.asarray(xa), np.asarray(xb),
                                       rtol=1e-5, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_head_only_round_trip(seed):
    shim = SimpleNamespace(update_targets="w2")
    space = get_update_space("head_only")
    base = _rand_params(seed, 9, 5, 3)
    init = space.init_deltas(shim, base)
    _assert_tree_equal(space.apply(shim, base, init), base)
    full_g = {"w1": jnp.ones((9, 5)), "w2": jnp.full((5, 3), 2.0)}
    proj = space.grad_project(shim, base, init, full_g)
    _assert_tree_equal(proj, {"w2": full_g["w2"]})


@settings(max_examples=15, deadline=None)
@given(rank=st.integers(1, 7))
def test_payload_bytes_strictly_ordered(rank):
    """bytes_up is strictly ordered full > lora(2r) > lora(r): the
    communicated payload provably shrinks with the adapter rank."""
    full_x = _mlp_init(jax.random.key(0))
    space = get_update_space("lora")

    def up(spec, x):
        return round_comm_bytes(spec, x, stateful_clients=True)["bytes_up"]

    b_full = up(_spec(), full_x)
    sizes = []
    for r in (2 * rank, rank):
        spec = _spec(update_space="lora", lora_rank=r,
                     update_targets="w1,w2")
        sizes.append(up(spec, space.init_deltas(spec, full_x)))
    assert b_full > sizes[0] > sizes[1] > 0


def test_default_targets_cover_dense_stack():
    assert DEFAULT_LORA_TARGETS == ("wq", "wk", "wv", "wo", "w_gate",
                                    "w_up", "w_down")


# --------------------------------- closed train -> serve loop (ISSUE-10)


def test_train_merge_decode_end_to_end(tmp_path):
    """The acceptance loop: a reduced-LM config federated-trains with
    lora rank 8 (bytes_up >= 50x below the full baseline), checkpoints
    base+deltas, and the merged checkpoint decodes through the
    launch/serve.py path."""
    import dataclasses

    from repro.configs import get_reduced
    from repro.launch.serve import checkpoint_params, generate
    from repro.models import model as M

    # vocab bumped so the untargeted embedding dominates the full
    # payload: full/lora(8) = ~82x here (the default reduced vocab of
    # 512 only reaches ~20x)
    cfg = dataclasses.replace(get_reduced("llama3.2-3b"), vocab_size=16384)
    spec = _spec(num_clients=4, num_sampled=2, local_batch=2,
                 update_space="lora", lora_rank=8)
    ds = SyntheticLMFederated(4, cfg.vocab_size, seq_len=16, seed=0)
    tr = FederatedTrainer(partial(M.loss_fn, cfg),
                          partial(M.init_params, cfg), spec, ds, seed=0)
    m = tr.run_round()
    assert m["update_space"] == "lora"
    full_bytes = round_comm_bytes(
        _spec(num_clients=4, num_sampled=2, local_batch=2),
        tr.base_params, stateful_clients=True)["bytes_up"]
    assert full_bytes >= 50 * m["bytes_up"], (full_bytes, m["bytes_up"])

    path = str(tmp_path / "lora_lm.npz")
    save_trainer(path, tr)
    served = load_serving_params(path)
    _assert_tree_equal(served, tr.eval_params())

    params = checkpoint_params(cfg, path)  # shape/dtype-validated merge
    prompts = jax.random.randint(jax.random.key(1), (2, 4), 0,
                                 cfg.vocab_size)
    out = generate(cfg, params, prompts, max_new=4)
    assert out.shape == (2, 4)
    assert bool(jnp.all((out >= 0) & (out < cfg.vocab_size)))


def test_frozen_base_is_a_program_argument():
    """The scanned chunk program takes the frozen base as a parameter: its
    argument bytes include every base byte, and its HLO holds no constant
    as large as a base weight (a closed-over base is compiled in as one,
    bloating every program and its compile-cache key by the model size)."""
    import re

    from repro.configs import get_reduced
    from repro.models import model as M

    cfg = get_reduced("llama3.2-3b")
    spec = _spec(num_clients=4, num_sampled=2, local_batch=1,
                 update_space="lora", lora_rank=8)
    ds = SyntheticLMFederated(4, cfg.vocab_size, seq_len=16, seed=0)
    tr = FederatedTrainer(partial(M.loss_fn, cfg),
                          partial(M.init_params, cfg), spec, ds, seed=0,
                          scan_rounds=2)
    assert tr.scan_active
    compiled = tr._scan_fn.lower(
        tr.server, tr.device_store, tr._device_data, tr.device_sampler.key,
        tr._data_base_key, None, None, tr._device_sizes, 0, 2,
        tr.base_params).compile()
    base_leaves = jax.tree.leaves(tr.base_params)
    base_bytes = sum(leaf.nbytes for leaf in base_leaves)
    assert compiled.memory_analysis().argument_size_in_bytes >= base_bytes
    smallest_weight = min(leaf.size for leaf in base_leaves if leaf.ndim >= 2)
    consts = re.findall(r"= \w+\[([\d,]*)\]\S* constant\(",
                        compiled.as_text())
    sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
             for dims in consts]
    assert sizes and max(sizes) < smallest_weight, (max(sizes),
                                                    smallest_weight)


def test_list_registries_prints_nine(capsys):
    from repro.launch.train import main as train_main

    assert train_main(["--list-registries"]) is None
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 9
    assert "update_spaces: full head_only lora" in lines
