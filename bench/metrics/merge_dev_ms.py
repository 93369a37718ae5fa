"""merge_dev_ms (ms): device time under ``update_space.apply`` per local step:
the LoRA merge into the frozen base (the inside counterpart of
``lora_merge_ms``).
From the traced window's span reduction (``spans.reduce``,
``spans.layer_metrics``). Moves ``round_s``."""
import spans


def read(ctx):
    return spans.window_metric(ctx, "merge_dev_ms")
