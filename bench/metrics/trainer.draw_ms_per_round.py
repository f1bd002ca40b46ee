"""Host milliseconds per round in the program's `trainer.draw` spans in
the window: each block's batch-index draws (one `rng.choice` per round
and selected client) and the stacking of its [K, C] operands, before the
dispatch. Read from the program's span recorder (`repro.obs`); nothing
where it has none."""


def read(ctx):
    try:
        from repro.obs import durations
    except ImportError:                  # a program without the recorder
        return None
    s = durations("trainer.draw", *ctx.window)
    return 1e3 * sum(s) / ctx.rounds if s and ctx.rounds else None
