"""fetch_ms (ms): host time per round in the ``scaffold.fetch`` span after the
chunk's last device op ended: copying the round's metrics to the host
once the device is done.
From the traced window's span reduction (``spans.reduce``,
``spans.layer_metrics``). Moves ``round_s``."""
import spans


def read(ctx):
    return spans.window_metric(ctx, "fetch_ms")
