"""idle_share (%): share of the traced window in which no operation ran
on the device, 1 - busy / window, from the profiler trace of the steady
rounds (``trace_reduce.summarize``). Moves ``round_s``."""


def read(ctx):
    if "idle_share" not in ctx.trace:
        return None
    return 100.0 * ctx.trace["idle_share"]
