"""The plain reference against itself and the program, at a tiny size on
the CPU, float32 throughout."""
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]

import harness  # noqa: E402
import tiny  # noqa: E402
import weights  # noqa: E402
from reference import lm  # noqa: E402


@pytest.mark.parametrize("chunk", [4, 12])
def test_ssd_chunked_is_the_recurrence(chunk):
    ks = jax.random.split(jax.random.key(0), 5)
    b, t, h, p, n = 2, 12, 6, 4, 5
    xh = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, t, n))
    cm = jax.random.normal(ks[4], (b, t, n))
    with jax.default_matmul_precision("highest"):
        chunked = lm.ssd_chunked(xh, dt, a, bm, cm, chunk=chunk)
        rec = lm.ssd_recurrent(xh, dt, a, bm, cm)
    np.testing.assert_allclose(chunked, rec, rtol=1e-5, atol=1e-5)


def _tiny_model(name):
    config = tiny.config(name)
    cfg = harness.program_model(config)
    base = weights.make_base(harness.base_shapes(cfg), seed=5)
    return config, cfg, base


@pytest.mark.parametrize("name", ["hymba-1.5b", "mamba2-2.7b"])
def test_loss_and_lora_grad_match_program(name):
    """Loss and delta-space gradient of the merged model: the program's
    ``make_grad_fn`` over its LoRA space against the reference's autodiff
    through its own merge."""
    from repro.core.controller import make_grad_fn
    from repro.core.update_space import get_update_space
    from repro.models import model as M

    config, cfg, base = _tiny_model(name)
    traffic = tiny.traffic("silo-2k")
    spec = harness.round_spec(config, traffic)
    space = get_update_space("lora")
    shapes = jax.eval_shape(lambda: space.init_deltas(spec, base, jax.random.key(1)))
    deltas = weights.make_adapter(shapes, seed=5, b_std=0.05)
    data = harness.dataset(config, traffic, seed=5)
    batch = jax.tree.map(lambda a: a[0, 0], harness.batches_fn(data, traffic, 5)(
        jnp.arange(2, dtype=jnp.int32), 0))
    scale = traffic["lora_alpha"] / traffic["lora_rank"]
    with jax.default_matmul_precision("highest"):
        g_prog, metrics = jax.jit(make_grad_fn(
            partial(M.loss_fn, cfg), space=space, spec=spec, base_params=base))(
                deltas, batch)
        l_ref, g_ref = jax.value_and_grad(
            lambda d: lm.loss(config["shapes"], base, d, batch, scale))(deltas)
    np.testing.assert_allclose(metrics["loss"], l_ref, rtol=1e-5)
    for (path, gp), gr in zip(jax.tree_util.tree_flatten_with_path(g_prog)[0],
                              jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(gp, gr, rtol=2e-4, atol=2e-6, err_msg=str(path))


def test_merge_matches_program():
    """The reference's LoRA projection, h W + (alpha/r) (h A) B with W
    unmerged, against h times the program's merged weight."""
    from repro.core.update_space import get_update_space

    config, cfg, base = _tiny_model("hymba-1.5b")
    spec = harness.round_spec(config, tiny.traffic("silo-2k"))
    space = get_update_space("lora")
    shapes = jax.eval_shape(lambda: space.init_deltas(spec, base, jax.random.key(1)))
    deltas = weights.make_adapter(shapes, seed=6, b_std=0.1)
    merged = space.apply(spec, base, deltas)
    per_layer, _ = lm.split_deltas(deltas)
    first = lambda t: jax.tree.map(lambda a: a[0], t)  # noqa: E731
    lin = lm.linear(first(base["layers"][0]), first(per_layer),
                    spec.lora_alpha / spec.lora_rank, None)
    for part, leaf in (("attn", "wq"), ("attn", "wv"), ("mlp", "w_down")):
        w = merged["layers"][0][part][leaf][0]
        h = jax.random.normal(jax.random.key(7), (2, 5, w.shape[0]))
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(lin(f"{part}.{leaf}", h), h @ w,
                                       rtol=1e-5, atol=1e-5)
