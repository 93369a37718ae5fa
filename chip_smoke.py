#!/usr/bin/env python3
"""Bring-up smoke test: the SCAFFOLD round on one TPU chip.

    python chip_smoke.py

One process, no children. It exits non-zero, before any phase, unless
JAX's first device is a TPU. Every phase ends in ``block_until_ready``
and reports its first call (compile included) apart from a steady call,
plus the device's ``peak_bytes_in_use`` so far. A failed check raises.
Phase (c) runs first, so the peak it reports is its own.

  (a) kernels, compiled: the packed SCAFFOLD updates (sgd and heavy-ball)
      on hymba-1.5b's LoRA delta tree and on one full-width bf16 layer
      stack, against the jnp oracles; the K-step megakernel (sgd and
      momentum) at d=20 and d=300 against its ``lax.scan`` oracle.
  (b) the paper's Fig. 3 quadratics through ``FederatedTrainer`` on the
      scanned engine with the fused update and the megakernel, SCAFFOLD
      and FedAvg, against the per-step path.
  (c) hymba-1.5b at its published widths and depth: a LoRA (rank 8)
      round on the scanned engine, 4 rounds in chunks of 2, frozen bf16
      base, random weights from a seed.

The last line of standard output is the JSON verdict
``{"ok": true, "device": {...}}``; nothing follows it.
"""
from __future__ import annotations

import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import FedRoundSpec  # noqa: E402
from repro.core import (  # noqa: E402
    FederatedTrainer,
    get_update_space,
    resolve_update_space,
)
from repro.data import (  # noqa: E402
    SyntheticLMFederated,
    make_paper_fig3,
    quadratic_loss,
)
from repro.kernels.scaffold_update import megakernel as mk  # noqa: E402
from repro.kernels.scaffold_update import ops, ref  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.util import use_repo_compile_cache  # noqa: E402

SEED = 0
ETA, BETA = 0.01, 0.9
MEGA_TOL = 1e-5  # README's bound for the megakernel against its oracle
# hymba-1.5b's MLP gate stack: 32 layers of (d_model 1600, d_ff 5504)
STACK = (32, 1600, 5504)
FIG3_ROUNDS = 50
# phase (c): N=8 clients, S=4 sampled, K=2 local steps of batch 1 at
# 2048 tokens (the windowed layers take local_attention_jnp), 4 rounds
# in scan chunks of 2, eval on 2 sequences
LM_ARCH, LM_SEQ = "hymba-1.5b", 2048
LM_CLIENTS, LM_SAMPLED, LM_STEPS = 8, 4, 2
LM_ROUNDS, LM_CHUNK, LM_EVAL_BATCH = 4, 2, 2


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def report(phase: str, first_s: float, steady_s: float, **checks) -> None:
    extra = " ".join(f"{k}={v}" for k, v in checks.items())
    print(f"phase {phase}: first_call_s={first_s:.3f} "
          f"steady_s={steady_s:.3f} peak_bytes_in_use={peak_bytes()} "
          f"{extra}", flush=True)


def timed(fn, *args):
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


def first_and_steady(fn, *args):
    """Call twice: the first call compiles, the second is steady."""
    _, first = timed(fn, *args)
    out, steady = timed(fn, *args)
    return out, first, steady


def random_like(shapes, key, scale=0.1):
    """Seeded normal arrays of the given shapes/dtypes, made on device."""
    leaves, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        (scale * jax.random.normal(k, s.shape, jnp.float32)).astype(s.dtype)
        for k, s in zip(keys, leaves)])


def max_ulp(got, want) -> int:
    """0 when every leaf is bit-equal, 1 when the worst element is one
    unit in the last place off, 2 for anything further."""
    def leaf(g, w):
        up = jnp.nextafter(w, jnp.array(jnp.inf, w.dtype))
        down = jnp.nextafter(w, jnp.array(-jnp.inf, w.dtype))
        dist = jnp.where(g == w, 0, jnp.where((g == up) | (g == down), 1, 2))
        return jnp.max(dist)
    return int(max(jax.tree.leaves(jax.jit(
        lambda g, w: jax.tree.map(leaf, g, w))(got, want))))


# ---------------------------------------------------------------- (a)


def lm_spec():
    """Phase (c)'s federated job: LoRA rank 8 on the default targets,
    the S sampled clients scanned one after another."""
    return FedRoundSpec(algorithm="scaffold", num_clients=LM_CLIENTS,
                        num_sampled=LM_SAMPLED, local_steps=LM_STEPS,
                        local_batch=1, eta_l=0.01,
                        strategy="client_sequential", update_space="lora",
                        lora_rank=8)


def lora_delta_shapes():
    cfg = get_config(LM_ARCH)
    spec = lm_spec()
    space = get_update_space(resolve_update_space(spec))
    base = jax.eval_shape(partial(M.init_params, cfg), jax.random.key(0))
    return jax.eval_shape(
        lambda p: space.init_deltas(spec, p, jax.random.key(4)), base)


def check_packed(name, y_shapes, g_shapes, c_shapes, m_shapes):
    """The packed kernels against the per-leaf oracles. The kernel and
    XLA may each contract ``y - eta*(g + corr)`` to a fused multiply-add
    or not, so one unit in the last place is allowed; nothing more."""
    y, g, corr, m = (random_like(s, jax.random.key(SEED + i))
                     for i, s in enumerate((y_shapes, g_shapes, c_shapes,
                                            m_shapes)))
    out, first, steady = first_and_steady(
        jax.jit(lambda y, g, c: ops.scaffold_update_packed(y, g, c, ETA)),
        y, g, corr)
    want = jax.jit(lambda y, g, c: ref.scaffold_update_tree_ref(
        y, g, c, ETA))(y, g, corr)
    ulp = max_ulp(out, want)
    assert ulp <= 1, f"{name} sgd: kernel off the oracle by >1 ulp"
    report(f"a.packed_sgd.{name}", first, steady, max_ulp=ulp)
    del out, want

    (oy, om), first, steady = first_and_steady(
        jax.jit(lambda y, g, c, m: ops.scaffold_momentum_update_packed(
            y, g, c, m, ETA, BETA)), y, g, corr, m)
    wy, wm = jax.jit(lambda y, g, c, m: ref.scaffold_momentum_update_tree_ref(
        y, g, c, m, ETA, BETA))(y, g, corr, m)
    ulp = max(max_ulp(oy, wy), max_ulp(om, wm))
    assert ulp <= 1, f"{name} momentum: kernel off the oracle by >1 ulp"
    report(f"a.packed_momentum.{name}", first, steady, max_ulp=ulp)


def check_megakernel(d: int, momentum: bool):
    """The K-step megakernel against its ``lax.scan`` oracle, both at
    full f32 matmul precision (the default would round the oracle's
    matvec through bf16 passes)."""
    K, bsz = 6, 2
    ks = jax.random.split(jax.random.key(SEED + d), 5)
    y = 0.5 * jax.random.normal(ks[0], (d,))
    corr = 0.1 * jax.random.normal(ks[1], (d,))
    A = jax.random.normal(ks[2], (K, bsz, d, d)) / np.sqrt(d)
    b = 0.3 * jax.random.normal(ks[3], (K, bsz, d))
    m0 = 0.1 * jax.random.normal(ks[4], (d,))
    eta = jnp.full((K,), 0.05, jnp.float32)
    beta = BETA if momentum else 0.0

    def kernel(y, corr, A, b, eta, m0):
        y_k, m_k, losses = mk.scaffold_local_loop(
            {"x": y}, {"x": corr}, {"A": A, "b": b}, eta,
            m={"x": m0} if momentum else None, beta=beta)
        return y_k["x"], None if m_k is None else m_k["x"], losses

    def oracle(y, corr, A, b, eta, m0):
        return ref.scaffold_local_loop_ref(
            y, corr, eta, A, b, m=m0 if momentum else None, beta=beta)

    args = (y, corr, A, b, eta, m0)
    with jax.default_matmul_precision("highest"):
        got, first, steady = first_and_steady(jax.jit(kernel), *args)
        want = jax.jit(oracle)(*args)
    errs = [float(jnp.max(jnp.abs(g - w))) for g, w in zip(got, want)
            if g is not None]
    assert max(errs) <= MEGA_TOL, (d, momentum, errs)
    solver = "momentum" if momentum else "sgd"
    report(f"a.megakernel_{solver}.d{d}", first, steady,
           max_abs_err=max(errs))


def phase_kernels():
    deltas = lora_delta_shapes()
    f32 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                       deltas)
    check_packed("hymba_lora_r8", f32, f32, f32, f32)
    bf16 = jax.ShapeDtypeStruct(STACK, jnp.bfloat16)
    f32s = jax.ShapeDtypeStruct(STACK, jnp.float32)
    check_packed("w_gate_stack_bf16", {"w": bf16}, {"w": bf16}, {"w": f32s},
                 {"w": f32s})
    for d in (20, 300):
        for momentum in (False, True):
            check_megakernel(d, momentum)


# ---------------------------------------------------------------- (b)


def fig3_trainer(algorithm: str, megakernel: bool, ds):
    spec = FedRoundSpec(algorithm=algorithm, num_clients=2, num_sampled=2,
                        local_steps=10, local_batch=1, eta_l=0.1,
                        use_megakernel=megakernel)
    tr = FederatedTrainer(
        quadratic_loss,
        lambda key: {"x": jnp.ones((ds.dim,), jnp.float32)},
        spec, ds, seed=SEED, scan_rounds=FIG3_ROUNDS // 2,
        use_fused_update=True)
    assert tr.scan_active, tr.scan_fallback_reason
    _, first = timed(lambda: (tr.run(FIG3_ROUNDS // 2), tr.x)[1])
    _, steady = timed(lambda: (tr.run(FIG3_ROUNDS // 2), tr.x)[1])
    if megakernel:
        assert all(m["megakernel_fallback_reason"] == ""
                   for m in tr.history), tr.history[-1]
    return tr, first, steady


def phase_quadratics():
    """Both paths at full f32 matmul precision, so that they are held to
    the megakernel's 1e-5 and not to bf16 rounding."""
    ds = make_paper_fig3(G=10.0)
    subopt = {}
    with jax.default_matmul_precision("highest"):
        for algorithm in ("scaffold", "fedavg"):
            mega, first, steady = fig3_trainer(algorithm, True, ds)
            per_step, _, _ = fig3_trainer(algorithm, False, ds)
            x_mega = np.asarray(mega.x["x"])
            x_step = np.asarray(per_step.x["x"])
            err = float(np.max(np.abs(x_mega - x_step)))
            assert err <= MEGA_TOL, (algorithm, err)
            subopt[algorithm] = ds.suboptimality(mega.x)
            assert np.isfinite(subopt[algorithm])
            report(f"b.fig3_{algorithm}", first, steady, rounds=FIG3_ROUNDS,
                   suboptimality=subopt[algorithm],
                   max_abs_err_vs_per_step=err)
    assert subopt["scaffold"] < subopt["fedavg"], subopt


# ---------------------------------------------------------------- (c)


def phase_lm():
    """hymba-1.5b LoRA rounds; ``client_sequential`` scans the S clients,
    which keeps the chunk program near 11 GB (its compile for one v5e:
    3.53 GB arguments, 7.80 GB temporaries)."""
    cfg = get_config(LM_ARCH)
    spec = lm_spec()
    data = SyntheticLMFederated(LM_CLIENTS, cfg.vocab_size, LM_SEQ,
                                seed=SEED)
    t = time.perf_counter()
    tr = FederatedTrainer(partial(M.loss_fn, cfg),
                          partial(M.init_params, cfg), spec, data,
                          seed=SEED, scan_rounds=LM_CHUNK)
    jax.block_until_ready((tr.base_params, tr.server))
    init_s = time.perf_counter() - t
    assert tr.scan_active, tr.scan_fallback_reason
    times = []
    for _ in range(LM_ROUNDS // LM_CHUNK):
        _, dt = timed(lambda: (tr.run(LM_CHUNK), tr.x)[1])
        times.append(dt)
    losses = [m["loss"] for m in tr.history]
    assert len(losses) == LM_ROUNDS and np.all(np.isfinite(losses)), losses

    space = tr.update_space
    batch = data.eval_batch(LM_EVAL_BATCH, np.random.default_rng(SEED + 7))
    eval_loss = jax.jit(lambda base, deltas, b: M.loss_fn(
        cfg, space.apply(spec, base, deltas), b)[0])
    ev, eval_s = timed(eval_loss, tr.base_params, tr.x, batch)
    assert np.isfinite(float(ev)), ev
    n_base = sum(leaf.size for leaf in jax.tree.leaves(tr.base_params))
    report("c.hymba_1_5b_lora", times[0], times[-1], init_s=f"{init_s:.3f}",
           eval_first_call_s=f"{eval_s:.3f}", base_params=n_base,
           layers=cfg.num_layers, d_model=cfg.d_model, seq=LM_SEQ,
           losses=[f"{v:.4f}" for v in losses], eval_loss=f"{float(ev):.4f}")


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} "
          f"compile_cache={use_repo_compile_cache()}", flush=True)
    # the LM round first: peak_bytes_in_use only grows, so the peak it
    # reports is its own, not the full-width layer stack's of (a)
    phase_lm()
    phase_kernels()
    phase_quadratics()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
