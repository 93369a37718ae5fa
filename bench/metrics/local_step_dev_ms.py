"""local_step_dev_ms (ms): device time under ``scaffold.local_step`` per local step: the
merge, forward, backward, projection and solver step of one client
step, inside the round program (the inside counterpart of
``client_step_ms``).
From the traced window's span reduction (``spans.reduce``,
``spans.layer_metrics``). Moves ``round_s``."""
import spans


def read(ctx):
    return spans.window_metric(ctx, "local_step_dev_ms")
