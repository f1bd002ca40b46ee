"""Device->host reads per round in the window: the `d2h` counts the
program's spans carry, over the window's rounds. On the block path the
reads are counted by `trainer.materialize` (each round's loss slice and
survivor count) and `eval` (each test batch's loss and accuracy). Read
from the program's span recorder (`repro.obs`); nothing where it has
none."""


def read(ctx):
    try:
        from repro.obs import between
    except ImportError:                  # a program without the recorder
        return None
    recs = between(*ctx.window)
    if not recs or not ctx.rounds:
        return None
    return sum((r.counts or {}).get("d2h", 0) for r in recs) / ctx.rounds
