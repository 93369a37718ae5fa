"""project_dev_ms (ms): device time under ``update_space.grad_project`` per
local step: the dense gradient projected onto the LoRA factors.
From the traced window's span reduction (``spans.reduce``,
``spans.layer_metrics``). Moves ``round_s``."""
import spans


def read(ctx):
    return spans.window_metric(ctx, "project_dev_ms")
