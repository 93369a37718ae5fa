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


@pytest.mark.parametrize("rope_theta,window,scale", [
    (10000.0, 3, None),   # Y: RoPE, sliding window
    (10000.0, None, None),  # F: RoPE, full causal
    (None, None, 1 / 128),  # no RoPE, a given scale
])
def test_attention_matches_program_core(rope_theta, window, scale):
    """The reference's attention against the program's dense attention
    core on the same projections, at each setting a kind may give."""
    from repro.models import layers as L

    s = {"n_heads": 4, "n_kv_heads": 2, "head_dim": 8}
    b, t, d = 2, 7, 16
    ks = jax.random.split(jax.random.key(3), 5)
    w = {name: jax.random.normal(k, shape) / 4 for k, (name, shape) in zip(ks, [
        ("wq", (d, 32)), ("wk", (d, 16)), ("wv", (d, 16)), ("wo", (32, d))])}
    h = jax.random.normal(ks[4], (b, t, d))
    pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    with jax.default_matmul_precision("highest"):
        got = lm.attention(s, lm.linear({"attn": w}, {}, 1.0, None), h, None,
                           rope_theta=rope_theta, window=window, scale=scale)
        q, k, v = ((h @ w[n]).reshape(b, t, -1, 8) for n in ("wq", "wk", "wv"))
        if rope_theta is not None:
            q, k = L.apply_rope(q, pos, rope_theta), L.apply_rope(k, pos, rope_theta)
        out = L.dense_attention(q, k, v, mask_kind="sliding" if window else "causal",
                                window=window or 0, scale=scale)
        want = out.reshape(b, t, 32) @ w["wo"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("factors", [{}, {"embedding_multiplier": 12.0,
                                           "logits_scaling": 16.0}])
def test_model_multipliers(factors):
    """With no layers the loss is the tied head's cross-entropy on the
    normed embeddings, times and over the factors a configuration gives."""
    ks = jax.random.split(jax.random.key(4), 3)
    embed = jax.random.normal(ks[0], (11, 8))
    base = {"embed": embed, "ln_final": {"scale": 0.1 * jax.random.normal(ks[1], (8,))},
            "layers": []}
    tokens = jax.random.randint(ks[2], (2, 5), 0, 11)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    with jax.default_matmul_precision("highest"):
        got = lm.loss({"groups": [], **factors}, base, {}, batch, 1.0)
        x = lm.rms_norm(embed[tokens] * factors.get("embedding_multiplier", 1.0),
                        base["ln_final"]["scale"])
        logits = x @ embed.T / factors.get("logits_scaling", 1.0)
        want = jnp.mean(jax.nn.logsumexp(logits, -1)
                        - jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0])
    np.testing.assert_allclose(got, want, rtol=1e-6)


def _tiny_model(name):
    config = tiny.config(name) if isinstance(name, str) else name
    cfg = harness.program_model(config)
    base = weights.make_base(harness.base_shapes(cfg), seed=5)
    return config, cfg, base


def _match_program(config):
    """Loss and delta-space gradient of the merged model: the program's
    ``make_grad_fn`` over its LoRA space against the reference's autodiff
    through its own merge."""
    from repro.core.controller import make_grad_fn
    from repro.core.update_space import get_update_space
    from repro.models import model as M

    config, cfg, base = _tiny_model(config)
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
    assert jax.tree.structure(g_prog) == jax.tree.structure(g_ref)
    for (path, gp), gr in zip(jax.tree_util.tree_flatten_with_path(g_prog)[0],
                              jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(gp, gr, rtol=2e-4, atol=2e-6, err_msg=str(path))
    return g_prog


@pytest.mark.parametrize("name", ["hymba-1.5b", "mamba2-2.7b"])
def test_loss_and_lora_grad_match_program(name):
    _match_program(name)


@pytest.mark.parametrize("targets", ["wq,wv,w_down,w_in,w_out", "wq,w_down"])
def test_mixed_stack_loss_and_lora_grad_match_program(targets):
    """A stack of three groups, M M | F | M (program pattern MMFM): one
    scan per group, each group's factors under its own index; with the
    second targets the M groups hold none."""
    config = tiny.mixed([{"kind": "M", "count": 2}, {"kind": "F", "count": 1},
                         {"kind": "M", "count": 1}], targets)
    grads = _match_program(config)
    groups = {path.split(".")[1] for path in grads}
    assert groups == ({"0", "1", "2"} if "w_in" in targets else {"1"}), sorted(grads)


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
    per_group, _ = lm.split_deltas(deltas)
    assert list(per_group) == [0]
    first = lambda t: jax.tree.map(lambda a: a[0], t)  # noqa: E731
    lin = lm.linear(first(base["layers"][0]), first(per_group[0]),
                    spec.lora_alpha / spec.lora_rank, None)
    for part, leaf in (("attn", "wq"), ("attn", "wv"), ("mlp", "w_down")):
        w = merged["layers"][0][part][leaf][0]
        h = jax.random.normal(jax.random.key(7), (2, 5, w.shape[0]))
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(lin(f"{part}.{leaf}", h), h @ w,
                                       rtol=1e-5, atol=1e-5)
