"""chunk_gap_ms (ms): device-idle time at each round boundary of the
scanned engine, from the last device op inside one ``bench.round``
annotation (around ``FederatedTrainer.run``) to the first op inside the
next, averaged over the boundaries; nothing where the window holds one
round. Moves ``round_s``."""


def read(ctx):
    return ctx.trace.get("chunk_gap_ms")
