"""Plain reference of SCAFFOLD rounds (Karimireddy et al., ICML 2020,
Algorithm 1, option II, server step size 1) over LoRA factors.

For each round t: the cohort is S of the N clients, uniformly without
replacement; each sampled client i starts from y = x and runs K steps
y <- y - eta_l (g_i(y) + c - c_i), then sets
c_i+ = c_i - c + (x - y) / (K eta_l). The server takes
x <- x + mean_i (y_i - x) and c <- c + (S / N) mean_i (c_i+ - c_i).

Python loops over clients and steps; only the gradient and the update
are jitted. Two faults that the benchmark's check must catch can be
planted: ``drop_half`` takes the means over the first half of the cohort
only, and ``zero_ci`` reads every c_i as zero.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _zeros(tree):
    return jax.tree.map(jnp.zeros_like, tree)


@jax.jit
def _sgd(y, g, c, ci, eta):
    return jax.tree.map(lambda y_, g_, c_, ci_: y_ - eta * (g_ + c_ - ci_),
                        y, g, c, ci)


@jax.jit
def _client_end(x, y, c, ci, inv):
    ci_new = jax.tree.map(lambda ci_, c_, x_, y_: ci_ - c_ + inv * (x_ - y_),
                          ci, c, x, y)
    return (jax.tree.map(jnp.subtract, y, x),
            jax.tree.map(jnp.subtract, ci_new, ci), ci_new)


@jax.jit
def _mean(trees):
    return jax.tree.map(lambda *a: sum(a) / len(a), *trees)


def run_rounds(grad: Callable, x0, c0, store0, rounds: int, *,
               cohort: Callable, batches: Callable, num_clients: int,
               local_steps: int, eta_l: float, drop_half: bool = False,
               zero_ci: bool = False) -> List[Dict]:
    """From the server state ``(x0, c0)`` and the clients' c_i (a tree of
    (N, ...) leaves, row i client i's): ``grad(y, batch) -> (loss, g)``;
    ``cohort(t)`` the round's client ids; ``batches(ids, t)`` leaves
    (S, K, b, T). Returns, per round, the server state after it (``x``,
    ``c``), the new c_i of its cohort (``ids``, ``c_i``) and its mean
    local loss (``loss``)."""
    x, c = x0, c0
    written: Dict[int, object] = {}
    inv = 1.0 / (local_steps * eta_l)
    out = []
    for t in range(rounds):
        ids = [int(i) for i in np.asarray(cohort(t))]
        data = batches(jnp.asarray(ids, jnp.int32), t)
        dys, dcs, new_ci, losses = [], [], [], []
        for si, cid in enumerate(ids):
            ci = (_zeros(x0) if zero_ci else written[cid] if cid in written
                  else jax.tree.map(lambda a: a[cid], store0))
            y = x
            for k in range(local_steps):
                batch = jax.tree.map(lambda a: a[si, k], data)
                loss, g = grad(y, batch)
                losses.append(float(loss))
                y = _sgd(y, g, c, ci, eta_l)
            dy, dc, ci_new = _client_end(x, y, c, ci, inv)
            dys.append(dy)
            dcs.append(dc)
            new_ci.append(ci_new)
        kept = len(ids) // 2 if drop_half else len(ids)
        frac = len(ids) / num_clients
        x = jax.tree.map(jnp.add, x, _mean(dys[:kept]))
        c = jax.tree.map(lambda c_, d: c_ + frac * d, c, _mean(dcs[:kept]))
        for cid, ci_new in zip(ids, new_ci):
            written[cid] = ci_new
        out.append({"x": x, "c": c, "ids": ids, "c_i": new_ci,
                    "loss": float(np.mean(losses))})
    return out
