"""Median host milliseconds per `RoundEngine.block_step` call in the
window: operand padding, uploads of the index arrays and the dispatch of
one block program (it returns before the device finishes)."""
import statistics


def read(ctx):
    s = ctx.window_spans("engine.dispatch")
    return 1e3 * statistics.median(s) if s else None
