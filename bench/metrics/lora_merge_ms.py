"""lora_merge_ms (ms): the update space's merge alone,
``space.apply(spec, base, deltas)`` (W + alpha/r A @ B for every target,
cast to the base dtype), jitted with the base as an argument and timed
like ``client_step_ms``. Moves ``round_s``."""


def read(ctx):
    import jax

    p = ctx.program
    merge = jax.jit(lambda base, d: p.space.apply(p.spec, base, d))
    return 1e3 * ctx.time_calls(merge, p.base, p.x)
