"""attention_dev_ms (ms): device time under ``model.attention`` per local step:
the attention core of every layer that has one (q/k/v after RoPE to the
output projection), forward and backward. Nothing where no op carries
the scope.
From the traced window's span reduction (``spans.reduce``,
``spans.layer_metrics``). Moves ``round_s``."""
import spans


def read(ctx):
    return spans.window_metric(ctx, "attention_dev_ms")
