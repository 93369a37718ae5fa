"""client_step_ms (ms): one corrected local step at the cell's shapes,
the trainer's delta-space gradient (``make_grad_fn`` over the LoRA space:
merge, forward, backward, projection) plus the ``sgd`` solver's update,
jitted alone with the frozen base as an argument; the mean of calls that
each end on the device, adding up to at least 0.25 s. Moves
``round_s``."""


def read(ctx):
    from functools import partial

    import jax
    from repro.core.controller import make_grad_fn
    from repro.core.local_solver import get_local_solver
    from repro.models import model as M

    p = ctx.program
    loss_fn = partial(M.loss_fn, p.cfg)
    solver = get_local_solver("sgd")

    def step(base, y, corr, batch):
        grad_fn = make_grad_fn(loss_fn, space=p.space, spec=p.spec, base_params=base)
        grads, _ = grad_fn(y, batch)
        return solver.step(p.spec, {}, y, grads, corr, 0)[0]

    corr = jax.tree.map(jax.numpy.zeros_like, p.x)
    return 1e3 * ctx.time_calls(jax.jit(step), p.base, p.x, corr, p.batch)
