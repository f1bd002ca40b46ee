"""Median milliseconds of `Experiment(spec).build(env=, trainer=)` per
window unit of a sweep cell: the schedule solve (AO), the model's init and
the pooled trainer's reset. Nothing to read in a repeat cell."""
import statistics


def read(ctx):
    s = ctx.window_spans("sweep.build")
    return 1e3 * statistics.median(s) if s else None
