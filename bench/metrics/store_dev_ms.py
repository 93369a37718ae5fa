"""store_dev_ms (ms): device time under ``scaffold.gather`` and
``scaffold.scatter`` per round: the cohort's c_i rows read from and
written to the client store.
From the traced window's span reduction (``spans.reduce``,
``spans.layer_metrics``). Moves ``round_s``."""
import spans


def read(ctx):
    return spans.window_metric(ctx, "store_dev_ms")
