"""mfu (%): required FLOPs of the window's rounds over the window's time,
chips and bf16 peak: ``flops.required_per_round`` (forward, activation
gradient and LoRA factor gradients; no embedding gather, no dense
gradient of frozen weights, no remat) times rounds, over window seconds
times chips times the peak of ``peaks.json`` for the device kind. Moves
``round_s``."""
import flops


def read(ctx):
    if ctx.rounds <= 0:
        return None
    required = flops.required_per_round(ctx.shapes, ctx.traffic) * ctx.rounds
    return 100.0 * required / (ctx.window_s * ctx.chips * flops.peak(ctx.device_kind))
