"""The benchmark's weights, made on the device from ``--seed``.

Both the program under test and the plain reference take their weights
from here, so neither uses what the other made. The layout (which leaves
exist, their shapes and dtypes) is the program's parameter interface;
the values are the benchmark's own:

  matrices    N(0, 1) / sqrt(fan_in), fan_in the second-to-last axis
  embed       N(0, 0.02)
  norm scale  N(0, 0.1)  (RMSNorm stores w - 1)
  a_log       log U(1, 16)
  dt_bias     softplus^-1 of dt, log dt ~ U(log 1e-3, log 1e-1)
  d_skip      1
  conv_b      N(0, 0.1)

The LoRA adapter is a resumed one: A ~ N(0, 1) / sqrt(in) and B ~
N(0, b_std), so the merge and both factor gradients are non-zero from the
first local step. So is the SCAFFOLD state: every client's c_i has
entries N(0, ci_std[factor]), of the size of that factor's gradient, and
the server's c is their mean over the clients, as the algorithm keeps it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def jax_seed(seed: int) -> int:
    """The seed as the 32 bits a JAX key holds (larger seeds wrap)."""
    return int(seed) % (2 ** 32)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def _fill(key, name: str, shape, dtype):
    f32 = jnp.float32
    if name == "embed":
        v = 0.02 * jax.random.normal(key, shape, f32)
    elif name in ("scale", "conv_b"):
        v = 0.1 * jax.random.normal(key, shape, f32)
    elif name == "bias":
        v = jnp.zeros(shape, f32)
    elif name == "a_log":
        v = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        v = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "d_skip":
        v = jnp.ones(shape, f32)
    elif len(shape) >= 2:
        v = jax.random.normal(key, shape, f32) / math.sqrt(shape[-2])
    else:
        v = 0.1 * jax.random.normal(key, shape, f32)
    return v.astype(dtype)


def make_tree(shapes, seed: int, salt: int):
    """Values for a tree of ``ShapeDtypeStruct`` leaves, by leaf name, in
    one jitted call on the default device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _fill(jax.random.fold_in(key, i), _leaf_name(p), s.shape, s.dtype)
            for i, (p, s) in enumerate(flat)])

    key = jax.random.fold_in(jax.random.key(jax_seed(seed)), salt)
    return jax.jit(build)(key)


def make_base(base_shapes, seed: int):
    """The frozen base parameters."""
    return make_tree(base_shapes, seed, salt=1)


def make_adapter(delta_shapes, seed: int, b_std: float):
    """LoRA factors ``{path: {"A", "B"}}`` for the delta-tree layout."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(delta_shapes)

    def build(key):
        leaves = []
        for i, (p, s) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            v = jax.random.normal(k, s.shape, jnp.float32)
            if _leaf_name(p) == "A":
                v = v / math.sqrt(s.shape[-2])
            else:
                v = b_std * v
            leaves.append(v.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    key = jax.random.fold_in(jax.random.key(jax_seed(seed)), 2)
    return jax.jit(build)(key)


def make_state(delta_shapes, num_clients: int, seed: int, ci_std):
    """``(c, store)``: the server's control variate and the clients'
    c_i, a tree of (num_clients, ...) leaves, for the delta-tree layout;
    ``ci_std`` maps "A"/"B" to the entries' standard deviation."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(delta_shapes)

    def build(key):
        rows = [(ci_std[_leaf_name(p)]
                 * jax.random.normal(jax.random.fold_in(key, i),
                                     (num_clients,) + s.shape, jnp.float32)
                 ).astype(s.dtype) for i, (p, s) in enumerate(flat)]
        c = [r.mean(axis=0, dtype=jnp.float32).astype(r.dtype) for r in rows]
        return (jax.tree_util.tree_unflatten(treedef, c),
                jax.tree_util.tree_unflatten(treedef, rows))

    key = jax.random.fold_in(jax.random.key(jax_seed(seed)), 3)
    return jax.jit(build)(key)
