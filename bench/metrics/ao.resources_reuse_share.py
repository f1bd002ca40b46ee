"""Share of the (P2) subproblem's selected (round, client) allocations
that the schedule solve took from one it had already solved in the same
call: 100 × (1 − Σ `p2.solved` / Σ `p2.pairs`) over the program's
`ao.resources` spans inside the benchmark's `sweep.build` spans in the
window. Read from the span recorder's counts (`repro.obs`); nothing where
it has none, in a cell without `sweep.build`, or from a program whose
spans carry no such counts."""


def read(ctx):
    try:
        from repro.obs import between
    except ImportError:                  # a program without the recorder
        return None
    w0, w1 = ctx.window
    builds = [(a, b) for n, a, b in ctx.spans.items
              if n == "sweep.build" and a >= w0 and b <= w1]
    counts = [r.counts for r in between(*ctx.window) or ()
              if r.name == "ao.resources" and r.counts
              and any(r.t0 >= a and r.t1 <= b for a, b in builds)]
    pairs = sum(c.get("p2.pairs", 0) for c in counts)
    if not pairs:
        return None
    return 100.0 * (1.0 - sum(c.get("p2.solved", 0) for c in counts) / pairs)
