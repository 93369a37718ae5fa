"""peak_hbm_gib (GiB): ``memory_stats()["peak_bytes_in_use"]`` of the
fullest chip, read after the window. Moves ``round_s`` (what does not fit
forces recomputation or a smaller cohort)."""


def read(ctx):
    return ctx.peak_bytes / 2 ** 30
