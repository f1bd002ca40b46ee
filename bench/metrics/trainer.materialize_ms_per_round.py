"""Host milliseconds per round in the program's `trainer.materialize`
spans in the window, less the `trainer.wait` spans inside them (self
time): the device->host reads of each pending round's losses and
survivor count, the per-round metrics and counters, and the round-end
hooks. Read from the program's span recorder (`repro.obs`); nothing where
it has none."""


def read(ctx):
    try:
        from repro.obs import durations
    except ImportError:                  # a program without the recorder
        return None
    mat = durations("trainer.materialize", *ctx.window)
    if not mat or not ctx.rounds:
        return None
    wait = durations("trainer.wait", *ctx.window)
    return 1e3 * (sum(mat) - sum(wait)) / ctx.rounds
