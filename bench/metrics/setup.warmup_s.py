"""Seconds of set-up spent in the warm-up unit: one whole unit of the
window's kind, which compiles every program the window uses, or loads it
from the persistent compilation cache."""


def read(ctx):
    s = ctx.setup_spans("setup.warmup")
    return sum(s) if s else None
