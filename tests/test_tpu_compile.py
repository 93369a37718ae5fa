"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached. Each test lowers a kernel at
real widths for one v5e chip and asserts that the compiled program holds
a ``tpu_custom_call``, i.e. that Mosaic accepted the kernel instead of
some fallback running. What interpret mode cannot show (illegal block
shapes, unsupported contractions, too much VMEM) fails here.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the suite runs
under several workers that all import this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.scaffold_update import megakernel as mk
from repro.kernels.scaffold_update import ops
from repro.kernels.swa_attention import ops as swa

# hymba-1.5b's MLP gate stack: 32 layers of (d_model 1600, d_ff 5504)
STACK = (32, 1600, 5504)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep these off it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch, no_persistent_cache):
    """The wrappers pick the kernel by asking for the default backend,
    which is the CPU here: steer them onto the kernel branch."""
    monkeypatch.setattr(ops, "_is_tpu", lambda: True)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("solver", ["sgd", "momentum"])
def test_packed_update_compiles_full_width(solver, one_chip, as_tpu):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    y, g = sds(STACK, jnp.bfloat16), sds(STACK, jnp.bfloat16)
    corr = sds(STACK, jnp.float32)
    if solver == "sgd":
        compiled = _compile(
            lambda y, g, c: ops.scaffold_update_packed(
                {"w_gate": y}, {"w_gate": g}, {"w_gate": c}, 0.01),
            y, g, corr)
    else:
        compiled = _compile(
            lambda y, g, c, m: ops.scaffold_momentum_update_packed(
                {"w_gate": y}, {"w_gate": g}, {"w_gate": c}, {"w_gate": m},
                0.01, 0.9),
            y, g, corr, sds(STACK, jnp.float32))
    _assert_kernel(compiled)


@pytest.mark.parametrize("d", [20, 300])
@pytest.mark.parametrize("solver", ["sgd", "momentum"])
def test_megakernel_compiles(solver, d, one_chip, as_tpu):
    """d=20 is one lane row (the paper's Fig. 3 quadratics), d=300 spans
    three rows."""
    K, bsz = 4, 2

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    momentum = solver == "momentum"

    def loop(y, c, A, b, eta, m):
        return mk.scaffold_local_loop(
            {"x": y}, {"x": c}, {"A": A, "b": b}, eta,
            m={"x": m} if momentum else None, beta=0.9 if momentum else 0.0)

    compiled = _compile(loop, sds((d,)), sds((d,)), sds((K, bsz, d, d)),
                        sds((K, bsz, d)), sds((K,)), sds((d,)))
    _assert_kernel(compiled)


@pytest.fixture
def swa_as_tpu(monkeypatch, no_persistent_cache):
    monkeypatch.setattr(swa, "_is_tpu", lambda: True)


def _kernel_op_names(text):
    """The ``op_name`` of every Mosaic kernel in a compiled program."""
    return re.findall(r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"',
                      text, re.S)


def test_swa_attention_compiles_hymba_shapes(one_chip, swa_as_tpu):
    """hymba-1.5b: 25 query / 5 kv heads of width 64, window 1024, at a
    2048-token sequence; value and gradient under ``jax.checkpoint``, as
    the model's remat'd layer scan takes them: the forward kernel, then
    in the backward the remat'd forward and the fused dq / dkv kernel."""
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = jax.checkpoint(lambda q, k, v: swa.swa_attention(q, k, v, 1024))(
            q, k, v)
        return jnp.sum(out.astype(jnp.float32))

    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                        sds((1, 2048, 25, 64)), sds((1, 2048, 5, 64)),
                        sds((1, 2048, 5, 64)))
    names = _kernel_op_names(compiled.as_text())
    forward = [n for n in names if "transpose(" not in n]
    backward = [n for n in names if "transpose(" in n]
    assert any("splash_mha_fwd" in n for n in forward), names
    for phase in ("splash_mha_fwd", "splash_mha_dkv"):
        assert any(phase in n for n in backward), (phase, names)


@pytest.mark.parametrize("window,seq,kernel", [
    (128, 256, True), (128, 384, True),
    (128, 200, False),  # seq not a multiple of 128: dense sliding
    (128, 128, False),  # seq < 2 * window: dense sliding
    (64, 256, False),   # window not a multiple of 128: the jnp band
])
def test_window_layer_selects_kernel_by_shape(window, seq, kernel, one_chip,
                                              swa_as_tpu):
    from repro.configs import get_reduced
    from repro.models import layers as L

    cfg = dataclasses.replace(get_reduced("hymba-1.5b"),
                              sliding_window=window)
    p = jax.eval_shape(lambda: L.init_attention(cfg, jax.random.key(0),
                                                jnp.float32))
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=one_chip), p)
    x = jax.ShapeDtypeStruct((1, seq, cfg.d_model), jnp.float32,
                             sharding=one_chip)
    pos = jax.ShapeDtypeStruct((1, seq), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda p, x, pos: L.attention_block(
        cfg, p, x, pos, kind="W")).lower(p, x, pos).as_text()
    assert ("tpu_custom_call" in text) == kernel


def test_moe_share_compiles_to_grouped_matmuls(one_chip, no_persistent_cache):
    """granite-4.0-h-small's MoE tail at its widths (d 4096, 9 of 72
    experts of 768 held, top-10, 2048 tokens), forward and the gradient
    into its input: the TPU compiler takes the share's grouped matmuls as
    its own ragged-dot kernels."""
    from repro.configs import get_config
    from repro.models import layers as L

    cfg = get_config("granite-4.0-h-small")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, held_experts=9))
    put = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    p = put(jax.eval_shape(lambda: L.init_moe(cfg, jax.random.key(0), jnp.bfloat16)))
    x = put(jax.ShapeDtypeStruct((1, 2048, cfg.d_model), jnp.bfloat16))

    def loss(p, x):
        out, _ = L.moe_block(cfg, p, x)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=1)).lower(p, x).compile().as_text()
    assert "tpu_custom_call" in text and "ragged-dot" in text
