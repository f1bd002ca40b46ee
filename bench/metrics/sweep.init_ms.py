"""Median milliseconds of the program's `experiment.init` spans in the
window: the model's eager weight init in `Experiment.build`, once per
unit of a sweep cell. Read from the program's span recorder
(`repro.obs`); nothing where it has none, or in a repeat cell."""
import statistics


def read(ctx):
    try:
        from repro.obs import durations
    except ImportError:                  # a program without the recorder
        return None
    s = durations("experiment.init", *ctx.window)
    return 1e3 * statistics.median(s) if s else None
