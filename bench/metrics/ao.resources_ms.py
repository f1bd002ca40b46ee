"""Milliseconds per sweep unit in the program's `ao.resources` spans:
the AO schedule solve's (P2) subproblem, transmit powers and clock
frequencies given the selection and the pruning ratios. Summed inside
each of the benchmark's `sweep.build` spans in the window, median over
the units. Read from the program's span recorder (`repro.obs`); nothing
where it has none, or in a cell without `sweep.build`."""
import statistics


def read(ctx):
    try:
        from repro.obs import between
    except ImportError:                  # a program without the recorder
        return None
    mine = [r for r in between(*ctx.window) or () if r.name == "ao.resources"]
    w0, w1 = ctx.window
    builds = [(a, b) for n, a, b in ctx.spans.items
              if n == "sweep.build" and a >= w0 and b <= w1]
    if not builds or not mine:
        return None
    return 1e3 * statistics.median(
        sum(r.t1 - r.t0 for r in mine if r.t0 >= a and r.t1 <= b)
        for a, b in builds)
