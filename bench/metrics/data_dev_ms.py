"""data_dev_ms (ms): device time under ``scaffold.sample`` and
``scaffold.batches`` per round: the cohort draw and its batches.
From the traced window's span reduction (``spans.reduce``,
``spans.layer_metrics``). Moves ``round_s``."""
import spans


def read(ctx):
    return spans.window_metric(ctx, "data_dev_ms")
