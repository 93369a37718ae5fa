"""agg_dev_ms (ms): device time under ``scaffold.control``,
``scaffold.aggregate`` and ``scaffold.server`` per round: the c_i
updates, the dy/dc means and the server step.
From the traced window's span reduction (``spans.reduce``,
``spans.layer_metrics``). Moves ``round_s``."""
import spans


def read(ctx):
    return spans.window_metric(ctx, "agg_dev_ms")
