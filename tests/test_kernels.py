"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed in interpret mode (kernel body runs on CPU)."""
import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.scaffold_update.ops import (
    scaffold_momentum_update,
    scaffold_momentum_update_packed,
    scaffold_update,
)
from repro.kernels.scaffold_update.ref import (
    scaffold_momentum_update_ref,
    scaffold_update_ref,
)
from repro.kernels.swa_attention.ops import swa_attention
from repro.kernels.swa_attention.ref import swa_attention_ref

SHAPES = [(64,), (1000,), (17, 33), (4, 256, 128), (3, 5, 7, 11)]
DTYPES = [jnp.float32, jnp.bfloat16]
ETAS = [0.0, 0.05, 1.0]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("eta", ETAS)
def test_scaffold_update_kernel(shape, dtype, eta):
    key = jax.random.key(sum(shape))
    ks = jax.random.split(key, 3)
    y = jax.random.normal(ks[0], shape, dtype)
    g = jax.random.normal(ks[1], shape, dtype)
    corr = jax.random.normal(ks[2], shape, dtype)
    out_k = scaffold_update(y, g, corr, eta, interpret=True)
    out_r = scaffold_update_ref(y, g, corr, eta)
    assert out_k.shape == shape and out_k.dtype == dtype
    tol = 1e-6 if dtype == jnp.float32 else 5e-3
    err = jnp.max(jnp.abs(out_k.astype(jnp.float32)
                          - out_r.astype(jnp.float32)))
    assert float(err) < tol


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("eta,beta", [(0.05, 0.9), (1.0, 0.0), (0.0, 0.5)])
def test_scaffold_momentum_update_kernel(shape, dtype, eta, beta):
    """The fused heavy-ball variant (momentum local solver, DESIGN.md
    §12) matches its fp32-accumulating oracle for both outputs; the
    moment slot is fp32 like the solver keeps it."""
    key = jax.random.key(sum(shape) + 1)
    ks = jax.random.split(key, 4)
    y = jax.random.normal(ks[0], shape, dtype)
    g = jax.random.normal(ks[1], shape, dtype)
    corr = jax.random.normal(ks[2], shape, dtype)
    m = jax.random.normal(ks[3], shape, jnp.float32)
    out_y, out_m = scaffold_momentum_update(y, g, corr, m, eta, beta,
                                            interpret=True)
    ref_y, ref_m = scaffold_momentum_update_ref(y, g, corr, m, eta, beta)
    assert out_y.shape == shape and out_y.dtype == dtype
    assert out_m.shape == shape and out_m.dtype == jnp.float32
    tol = 1e-6 if dtype == jnp.float32 else 5e-3
    for a, b in ((out_y, ref_y), (out_m, ref_m)):
        err = jnp.max(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)))
        assert float(err) < tol


def test_scaffold_momentum_update_packed_matches_per_leaf():
    """The packed pytree path (one pallas_call per dtype group) slices
    back out exactly the per-leaf kernel results, mixed dtypes included."""
    ks = jax.random.split(jax.random.key(7), 8)
    tree_y = {"a": jax.random.normal(ks[0], (37,), jnp.float32),
              "b": {"w": jax.random.normal(ks[1], (5, 9), jnp.bfloat16)}}
    tree_g = {"a": jax.random.normal(ks[2], (37,), jnp.float32),
              "b": {"w": jax.random.normal(ks[3], (5, 9), jnp.bfloat16)}}
    tree_c = {"a": jax.random.normal(ks[4], (37,), jnp.float32),
              "b": {"w": jax.random.normal(ks[5], (5, 9), jnp.bfloat16)}}
    tree_m = jax.tree.map(
        lambda a: jax.random.normal(ks[6], a.shape, jnp.float32), tree_y)
    out_y, out_m = scaffold_momentum_update_packed(
        tree_y, tree_g, tree_c, tree_m, 0.1, 0.9, interpret=True)
    for path in (("a",), ("b", "w")):
        get = lambda t: t[path[0]] if len(path) == 1 else t[path[0]][path[1]]  # noqa: E731
        leaf_y, leaf_m = scaffold_momentum_update(
            get(tree_y), get(tree_g), get(tree_c), get(tree_m), 0.1, 0.9,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(get(out_y), jnp.float32),
                                      np.asarray(leaf_y, jnp.float32))
        np.testing.assert_array_equal(np.asarray(get(out_m)),
                                      np.asarray(leaf_m))


def test_fedprox_prox_term_fp32_agreement():
    """Satellite fix: the FedProx prox add accumulates in fp32, so for
    sub-fp32 params the fused and jnp update paths round identically to
    the fp32 oracle — one rounding, at the final cast to the param dtype
    (previously the prox term was cast back to the bf16 grad dtype and
    the two paths diverged from the oracle)."""
    from repro.core.local_solver import get_local_solver, run_local_steps
    from types import SimpleNamespace

    dim, eta, mu = 33, 0.1, 0.7
    ks = jax.random.split(jax.random.key(3), 4)
    y0 = {"w": jax.random.normal(ks[0], (dim,), jnp.bfloat16)}
    x0 = {"w": jax.random.normal(ks[1], (dim,), jnp.bfloat16)}
    gfix = {"w": jax.random.normal(ks[2], (dim,), jnp.bfloat16)}
    corr = {"w": jax.random.normal(ks[3], (dim,), jnp.bfloat16)}
    batches = {"w": jnp.zeros((1, 1), jnp.float32)}  # K=1 dummy

    def grad_fn(params, batch):
        return gfix, {"loss": jnp.zeros((), jnp.float32)}

    from repro.kernels.scaffold_update.ops import force_interpret

    spec = SimpleNamespace(eta_l=eta)
    outs = {}
    for fused in (False, True):
        # fused=True runs the actual Pallas kernel body (interpret mode)
        ctx = force_interpret() if fused else contextlib.nullcontext()
        with ctx:
            y, _, _ = run_local_steps(
                grad_fn, spec, y0, batches,
                solver=get_local_solver("sgd"), correction=corr,
                prox_mu=mu, prox_center=x0, use_fused_update=fused)
        outs[fused] = np.asarray(y["w"].astype(jnp.float32))
    f32 = lambda t: t["w"].astype(jnp.float32)  # noqa: E731
    g32 = f32(gfix) + mu * (f32(y0) - f32(x0))
    oracle = (f32(y0) - eta * (g32 + f32(corr))).astype(jnp.bfloat16)
    oracle = np.asarray(oracle.astype(jnp.float32))
    np.testing.assert_array_equal(outs[False], oracle)
    np.testing.assert_array_equal(outs[True], oracle)


# ---------------------------------------------------------------------------
# the K-step megakernel (DESIGN.md §15)
# ---------------------------------------------------------------------------

from repro.kernels.scaffold_update.megakernel import scaffold_local_loop  # noqa: E402
from repro.kernels.scaffold_update.ref import scaffold_local_loop_ref  # noqa: E402

MEGA_SOLVERS = ("sgd", "momentum", "sgd_sched")


def _quad_case(d, K, bsz, dtype, seed=0):
    """A random quadratics local-round problem (params scaled so K steps
    at eta~0.05 stay well away from bf16 overflow)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    y = (0.5 * jax.random.normal(ks[0], (d,))).astype(dtype)
    corr = (0.1 * jax.random.normal(ks[1], (d,))).astype(dtype)
    A = (0.3 * jax.random.normal(ks[2], (K, bsz, d, d))).astype(dtype)
    b = (0.3 * jax.random.normal(ks[3], (K, bsz, d))).astype(dtype)
    m = 0.1 * jax.random.normal(ks[4], (d,), jnp.float32)
    return y, corr, A, b, m


def _eta_table(solver, K):
    if solver == "sgd_sched":  # a genuinely per-step-varying table
        return jnp.linspace(0.08, 0.01, K, dtype=jnp.float32)
    return jnp.full((K,), 0.05, jnp.float32)


@pytest.mark.parametrize("solver", MEGA_SOLVERS)
@pytest.mark.parametrize("dtype", DTYPES)
# d=100 exercises the lane-only padding (not a multiple of 128); d=130
# exercises rows > 1
@pytest.mark.parametrize("d", [100, 130])
def test_megakernel_matches_ref(solver, dtype, d):
    """The fused K-step kernel (interpret mode = actual kernel body)
    reproduces the lax.scan oracle's trajectory and per-step losses."""
    K, bsz = 6, 2
    y, corr, A, b, m0 = _quad_case(d, K, bsz, dtype, seed=d)
    eta = _eta_table(solver, K)
    use_m = solver == "momentum"
    y_k, m_k, loss_k = scaffold_local_loop(
        {"x": y}, {"x": corr}, {"A": A, "b": b}, eta,
        m={"x": m0} if use_m else None, beta=0.9 if use_m else 0.0,
        interpret=True)
    y_r, m_r, loss_r = scaffold_local_loop_ref(
        y, corr, eta, A, b, m=m0 if use_m else None,
        beta=0.9 if use_m else 0.0)
    assert y_k["x"].shape == (d,) and y_k["x"].dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    err = jnp.max(jnp.abs(y_k["x"].astype(jnp.float32)
                          - y_r.astype(jnp.float32)))
    assert float(err) < tol
    np.testing.assert_allclose(np.asarray(loss_k), np.asarray(loss_r),
                               rtol=1e-4 if dtype == jnp.float32 else 3e-2)
    if use_m:
        assert m_k["x"].dtype == jnp.float32
        err_m = jnp.max(jnp.abs(m_k["x"] - m_r))
        assert float(err_m) < tol


def test_megakernel_k1_degenerate():
    """K=1 collapses to exactly one corrected step."""
    d = 100
    y, corr, A, b, _ = _quad_case(d, 1, 3, jnp.float32, seed=1)
    eta = jnp.full((1,), 0.05, jnp.float32)
    y_k, _, losses = scaffold_local_loop(
        {"x": y}, {"x": corr}, {"A": A, "b": b}, eta, interpret=True)
    Am = jnp.mean(A[0], axis=0)
    Am = 0.5 * (Am + Am.T)
    bm = jnp.mean(b[0], axis=0)
    g = Am @ y + bm + corr
    np.testing.assert_allclose(np.asarray(y_k["x"]),
                               np.asarray(y - 0.05 * g), atol=1e-5)
    assert losses.shape == (1,)


@pytest.mark.parametrize("solver", MEGA_SOLVERS)
def test_megakernel_run_local_steps_equivalence(solver):
    """run_local_steps with spec.use_megakernel dispatches into the fused
    loop and matches the per-step (jnp and fused-kernel) trajectories."""
    import dataclasses

    from repro.configs.base import FedRoundSpec
    from repro.core.controller import make_grad_fn
    from repro.core.local_solver import run_local_steps
    from repro.data import quadratic_loss
    from repro.kernels.scaffold_update.ops import force_interpret

    d, K = 100, 5
    y, corr, A, b, _ = _quad_case(d, K, 2, jnp.float32, seed=2)
    y0 = {"x": y}
    batches = {"A": A, "b": b}
    grad_fn = make_grad_fn(quadratic_loss)
    assert grad_fn.megakernel_grad == "quadratic"
    spec = FedRoundSpec(
        algorithm="scaffold", num_clients=4, num_sampled=2, local_steps=K,
        local_batch=2, eta_l=0.05, local_solver=solver, local_momentum=0.9,
        eta_l_schedule="cosine" if solver == "sgd_sched" else "")
    out = {}
    for mega in (False, True):
        sp = dataclasses.replace(spec, use_megakernel=mega)
        # interpret mode: the mega variant runs the actual kernel body
        with force_interpret():
            y_K, _, loss = run_local_steps(
                grad_fn, sp, y0, batches, correction={"x": corr},
                use_fused_update=True)
        out[mega] = (np.asarray(y_K["x"]), float(loss))
    np.testing.assert_allclose(out[True][0], out[False][0], atol=1e-5)
    np.testing.assert_allclose(out[True][1], out[False][1], rtol=1e-5)


def test_megakernel_launch_count_collapse():
    """The whole point: K pallas launches per round -> 1 (per dtype
    group), counted through scan trip counts via jaxpr inspection."""
    import dataclasses

    from repro.configs.base import FedRoundSpec
    from repro.core.controller import make_grad_fn
    from repro.core.local_solver import run_local_steps
    from repro.data import quadratic_loss
    from repro.kernels.scaffold_update.ops import (
        count_pallas_launches,
        force_interpret,
    )

    d, K = 64, 7
    grad_fn = make_grad_fn(quadratic_loss)
    y0 = {"x": jnp.ones((d,), jnp.float32)}
    corr = {"x": jnp.zeros((d,), jnp.float32)}
    batches = {"A": jnp.ones((K, 1, d, d), jnp.float32),
               "b": jnp.ones((K, 1, d), jnp.float32)}
    spec = FedRoundSpec(algorithm="scaffold", num_clients=4, num_sampled=2,
                        local_steps=K, local_batch=1, eta_l=0.05)
    counts = {}
    with force_interpret():
        for mega in (False, True):
            sp = dataclasses.replace(spec, use_megakernel=mega)
            counts[mega] = count_pallas_launches(
                lambda y, bt, c, sp=sp: run_local_steps(
                    grad_fn, sp, y, bt, correction=c,
                    use_fused_update=True)[0],
                y0, batches, corr)
    assert counts[False] == K
    assert counts[True] == 1


def test_megakernel_incompatibility_gate():
    """The capability dispatch rejects exactly the inexpressible combos,
    with the reason strings engines surface in round metrics."""
    from repro.core.controller import make_grad_fn
    from repro.core.local_solver import (
        get_local_solver,
        megakernel_incompatibility,
    )
    from repro.data import quadratic_loss

    grad_fn = make_grad_fn(quadratic_loss)
    ok = lambda **kw: megakernel_incompatibility(  # noqa: E731
        grad_fn, get_local_solver("sgd"), **kw)
    assert ok() is None
    d = 8
    good_batches = {"A": jnp.ones((2, 1, d, d)), "b": jnp.ones((2, 1, d))}
    assert ok(params={"x": jnp.ones((d,))}, batches=good_batches) is None
    # adam has no fused variant
    reason = megakernel_incompatibility(grad_fn, get_local_solver("adam"))
    assert "adam" in reason
    # a grad fn without the marker is not kernel-expressible
    plain = make_grad_fn(lambda p, b: (jnp.sum(p["x"] ** 2), {}))
    assert "megakernel_grad" in megakernel_incompatibility(
        plain, get_local_solver("sgd"))
    # FedProx's prox term is not in the kernel
    assert "prox" in ok(prox_mu=0.5)
    # multi-leaf / non-1D params
    assert "single 1-D leaf" in ok(params={"a": jnp.ones((d,)),
                                           "c": jnp.ones((d,))})
    assert "single 1-D leaf" in ok(params={"x": jnp.ones((2, d))})
    # non-quadratic batches
    assert "quadratic" in ok(batches={"tokens": jnp.ones((2, 1, 4))})


def test_scanned_round_megakernel_fallback_metrics():
    """Trainer-level dispatch: quadratics + sgd runs the megakernel
    (empty fallback reason in every round's metrics, trajectory matches
    the per-step trainer); adam falls back loudly with the reason set."""
    import dataclasses

    from repro.configs.base import FedRoundSpec
    from repro.core import FederatedTrainer
    from repro.data import make_similarity_quadratics, quadratic_loss

    ds = make_similarity_quadratics(8, 12, delta=0.3, G=8.0, mu=0.3, seed=0)
    spec = FedRoundSpec(algorithm="scaffold", num_clients=8, num_sampled=2,
                        local_steps=3, local_batch=1, eta_l=0.1,
                        use_megakernel=True)
    init = lambda key: {"x": jnp.ones((12,), jnp.float32)}  # noqa: E731

    def make(sp, **kw):
        return FederatedTrainer(quadratic_loss, init, sp, ds, seed=0,
                                use_fused_update=True, **kw)

    tr = make(spec, scan_rounds=4)
    assert tr.megakernel_fallback_reason == ""
    tr.run(4)
    assert all(m["megakernel_fallback_reason"] == "" for m in tr.history)

    base = make(dataclasses.replace(spec, use_megakernel=False),
                scan_rounds=4)
    assert base.megakernel_fallback_reason is None
    base.run(4)
    assert "megakernel_fallback_reason" not in base.history[-1]
    np.testing.assert_allclose(np.asarray(tr.x["x"]),
                               np.asarray(base.x["x"]), atol=1e-5)

    with pytest.warns(UserWarning, match="megakernel"):
        tr_adam = make(dataclasses.replace(spec, local_solver="adam"),
                       scan_rounds=4)
    assert "adam" in tr_adam.megakernel_fallback_reason
    tr_adam.run(4)
    assert "adam" in tr_adam.history[-1]["megakernel_fallback_reason"]


SWA_CASES = [
    # (B, S, Hq, Hkv, D, window)
    (2, 256, 4, 2, 64, 128),
    (1, 512, 2, 1, 64, 128),
    (2, 256, 4, 4, 32, 64),
    (1, 384, 6, 3, 64, 128),
    (2, 128, 2, 1, 128, 64),
]


@pytest.mark.parametrize("case", SWA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_attention_kernel(case, dtype):
    b, s, hq, hkv, d, w = case
    ks = jax.random.split(jax.random.key(s + w), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    out_k = swa_attention(q, k, v, w, interpret=True)
    qt, kt, vt = (jnp.moveaxis(a, 1, 2) for a in (q, k, v))
    out_r = jnp.moveaxis(swa_attention_ref(qt, kt, vt, w), 1, 2)
    assert out_k.shape == out_r.shape
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    err = jnp.max(jnp.abs(out_k.astype(jnp.float32)
                          - out_r.astype(jnp.float32)))
    assert float(err) < tol


def test_swa_matches_model_layer_semantics():
    """Kernel semantics == the model's sliding-window attention path."""
    from repro.models.layers import dense_attention

    b, s, h, d, w = 1, 256, 2, 64, 128
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    out_model = dense_attention(q, k, v, mask_kind="sliding", window=w)
    out_kernel = swa_attention(q, k, v, w, interpret=True)
    assert float(jnp.max(jnp.abs(out_model - out_kernel))) < 2e-5


# (B, S, Hq, Hkv, D, window): hymba's 5:1 GQA with window = 2 x block
# (block_size(640, 256) == 128), and a batch of two at 2:1
SWA_GRAD_CASES = [(1, 640, 5, 1, 64, 256), (2, 256, 4, 2, 64, 128)]


def _swa_inputs(case, dtype):
    b, s, hq, hkv, d, w = case
    ks = jax.random.split(jax.random.key(s + w), 4)
    return (jax.random.normal(ks[0], (b, s, hq, d), dtype),
            jax.random.normal(ks[1], (b, s, hkv, d), dtype),
            jax.random.normal(ks[2], (b, s, hkv, d), dtype),
            jax.random.normal(ks[3], (b, s, hq, d), dtype))


def _assert_close(got, want, dtype):
    """Each array within a share of the reference's largest entry."""
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for g, r in zip(got, want):
        assert g.shape == r.shape and g.dtype == dtype
        r = r.astype(jnp.float32)
        err = jnp.max(jnp.abs(g.astype(jnp.float32) - r)) / jnp.max(jnp.abs(r))
        assert float(err) < tol


@pytest.mark.parametrize("case", SWA_GRAD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_attention_grad_matches_dense(case, dtype):
    """dq / dk / dv of the kernel's VJP == the model's dense sliding-window
    attention differentiated in float32 on the same inputs."""
    from repro.models.layers import dense_attention

    w = case[-1]
    q, k, v, g = _swa_inputs(case, dtype)
    out, vjp = jax.vjp(lambda q, k, v: swa_attention(q, k, v, w,
                                                     interpret=True), q, k, v)
    f32 = partial(jnp.asarray, dtype=jnp.float32)
    out_r, vjp_r = jax.vjp(
        lambda q, k, v: dense_attention(q, k, v, mask_kind="sliding",
                                        window=w), f32(q), f32(k), f32(v))
    _assert_close((out, *vjp(g)), (out_r, *vjp_r(f32(g))), dtype)


def _layer_stack_loss(attn, xs):
    """The model's layer scan: each layer remat'd under ``jax.checkpoint``
    with nothing saved, as ``transformer.apply_stack`` runs it."""
    def body(carry, layer):
        q, k, v = layer
        out = jax.checkpoint(
            attn, policy=jax.checkpoint_policies.nothing_saveable)(
                q + carry, k, v)
        return carry + out, None

    carry, _ = jax.lax.scan(body, jnp.zeros_like(xs[0][0]), xs)
    return jnp.sum(jnp.sin(carry))


def test_swa_attention_grad_under_checkpoint_in_scan():
    from repro.models.layers import dense_attention

    b, s, hq, hkv, d, w = SWA_GRAD_CASES[0]
    layers = 2
    ks = jax.random.split(jax.random.key(7), 3)
    xs = (jax.random.normal(ks[0], (layers, b, s, hq, d)),
          jax.random.normal(ks[1], (layers, b, s, hkv, d)),
          jax.random.normal(ks[2], (layers, b, s, hkv, d)))
    kern = partial(swa_attention, window=w, interpret=True)
    dense = partial(dense_attention, mask_kind="sliding", window=w)
    got = jax.value_and_grad(partial(_layer_stack_loss, kern))(xs)
    want = jax.value_and_grad(partial(_layer_stack_loss, dense))(xs)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    _assert_close(got[1], want[1], jnp.float32)


def test_swa_attention_grad_under_vmap():
    """Clients in parallel (``client_parallel``): the op under ``jax.vmap``."""
    from repro.models.layers import dense_attention

    case = SWA_GRAD_CASES[1]
    w = case[-1]
    q, k, v, g = _swa_inputs(case, jnp.float32)  # batch axis as 2 clients
    q, k, v, g = (a[:, None] for a in (q, k, v, g))

    def grads(attn):
        def one(q, k, v, g):
            out, vjp = jax.vjp(attn, q, k, v)
            return (out, *vjp(g))
        return jax.vmap(one)(q, k, v, g)

    _assert_close(grads(partial(swa_attention, window=w, interpret=True)),
                  grads(partial(dense_attention, mask_kind="sliding",
                                window=w)), jnp.float32)
