"""step_idle_ms (ms): device-idle time per local step whose neighbouring ops
both lie under ``scaffold.local_step``: the chip waiting inside the
step's own program.
From the traced window's span reduction (``spans.reduce``,
``spans.layer_metrics``). Moves ``round_s``."""
import spans


def read(ctx):
    return spans.window_metric(ctx, "step_idle_ms")
