"""Host milliseconds per round in the program's `trainer.slice` spans in
the window: right after each block's dispatch, the per-round slices of
its losses and survivor counts. A slice of a value the device has not
finished waits for it, so on the block path this holds most of the
host's wait for the device. Read from the program's span recorder
(`repro.obs`); nothing where it has none."""


def read(ctx):
    try:
        from repro.obs import durations
    except ImportError:                  # a program without the recorder
        return None
    s = durations("trainer.slice", *ctx.window)
    return 1e3 * sum(s) / ctx.rounds if s and ctx.rounds else None
