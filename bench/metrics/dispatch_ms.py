"""dispatch_ms (ms): host time in the ``scaffold.dispatch`` span per round:
the call of the jitted chunk.
From the traced window's span reduction (``spans.reduce``,
``spans.layer_metrics``). Moves ``round_s``."""
import spans


def read(ctx):
    return spans.window_metric(ctx, "dispatch_ms")
