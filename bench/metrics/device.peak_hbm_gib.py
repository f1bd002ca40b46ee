"""Peak device memory in use over the run, on the fullest chip, in GiB
(`memory_stats()["peak_bytes_in_use"]` after the window)."""


def read(ctx):
    return ctx.memory_peak_bytes / 2 ** 30 if ctx.memory_peak_bytes else None
