"""Host milliseconds per round in the program's `trainer.plan` spans in
the window: `FederatedTrainer.run`'s schedule bookkeeping before any
round runs (per-round delay and energy, fault draws, checkpoint rounds),
the block partition and the cohort schedule. Read from the program's span
recorder (`repro.obs`); nothing where it has none."""


def read(ctx):
    try:
        from repro.obs import durations
    except ImportError:                  # a program without the recorder
        return None
    s = durations("trainer.plan", *ctx.window)
    return 1e3 * sum(s) / ctx.rounds if s and ctx.rounds else None
