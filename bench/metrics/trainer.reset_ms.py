"""Median milliseconds of the program's `trainer.reset` spans in the
window: a pooled trainer's reset to fresh weights for the next run (the
packed buffers built from the weights, the batch RNG and the counters).
Read from the program's span recorder (`repro.obs`); nothing where it has
none."""
import statistics


def read(ctx):
    try:
        from repro.obs import durations
    except ImportError:                  # a program without the recorder
        return None
    s = durations("trainer.reset", *ctx.window)
    return 1e3 * statistics.median(s) if s else None
