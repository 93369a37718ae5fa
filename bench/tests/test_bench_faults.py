"""With the timed path broken underneath, a whole run (all but the look
for a chip) reports ``correct`` false, for each fault a training cell on
one chip can have: a round that returns its state unchanged, half of the
cohort left out of the means, and every client's c_i read as zero. (No
exchange between chips exists on one chip, and a training cell produces
no tokens or answers.)"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from cells import run_tiny


def _unchanged(real):
    def run_rounds(grad_fn, spec, server, store, R, **kw):
        _, _, metrics = real(grad_fn, spec, server, store, R, **kw)
        return server, store, metrics
    return run_rounds


def _half_cohort(real):
    def run_round(grad_fn, spec, server, clients, batches, **kw):
        h = spec.num_sampled // 2
        take = lambda t: jax.tree.map(lambda a: a[:h], t)  # noqa: E731
        out = real(grad_fn, dataclasses.replace(spec, num_sampled=h), server,
                   dataclasses.replace(clients, c_i=take(clients.c_i)),
                   take(batches), **kw)
        c_i = jax.tree.map(lambda new, old: jnp.concatenate([new, old[h:]]),
                           out.clients.c_i, clients.c_i)
        return dataclasses.replace(
            out, clients=dataclasses.replace(out.clients, c_i=c_i))
    return run_round


def _zero_ci(real):
    def run_round(grad_fn, spec, server, clients, batches, **kw):
        zero = jax.tree.map(jnp.zeros_like, clients.c_i)
        return real(grad_fn, spec, server,
                    dataclasses.replace(clients, c_i=zero), batches, **kw)
    return run_round


@pytest.mark.parametrize("fault", ["unchanged", "half_cohort", "zero_ci"])
def test_fault_makes_run_incorrect(fault, monkeypatch):
    import repro.core.controller as controller
    import repro.core.rounds as rounds

    if fault == "unchanged":
        monkeypatch.setattr(controller, "run_rounds", _unchanged(controller.run_rounds))
    else:
        wrap = _half_cohort if fault == "half_cohort" else _zero_ci
        monkeypatch.setattr(rounds, "run_round", wrap(rounds.run_round))
    res = run_tiny("hymba-1.5b", "silo-2k")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("config,cell", [("hymba-1.5b", "hymba-silo-2k"),
                                         ("mamba2-2.7b", "mamba2-silo-2k")])
def test_control_is_not_correct(config, cell):
    """The control, the reference computed with float8_e4m3 matmul
    operands (below the configurations' bfloat16), put in the program's
    place, fails the cell's limits."""
    from types import SimpleNamespace

    import compare
    import harness
    import tiny

    conf, traffic = tiny.config(config), tiny.traffic("silo-2k")
    limits = harness.resolve(cell)[3]
    cfg = harness.program_model(conf)
    built = harness.build(conf, traffic, seed=3)
    shapes = SimpleNamespace(base_shapes=harness.base_shapes(cfg),
                             delta_shapes=built.delta_shapes)
    del built
    state0, ref = harness.reference_round(conf, traffic, 3, shapes)
    _, ctl = harness.reference_round(conf, traffic, 3, shapes, quant=jnp.float8_e4m3fn)
    nums = compare.numbers(state0, compare.as_program(ctl), ref)
    assert not compare.verdict(nums, limits), nums
