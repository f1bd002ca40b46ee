"""Host milliseconds per round in the program's `trainer.wait` spans in
the window: at each materialization, the wait for the device to finish
the pending blocks before their losses are read (device time seen from
the host). Read from the program's span recorder (`repro.obs`); nothing
where it has none."""


def read(ctx):
    try:
        from repro.obs import durations
    except ImportError:                  # a program without the recorder
        return None
    s = durations("trainer.wait", *ctx.window)
    return 1e3 * sum(s) / ctx.rounds if s and ctx.rounds else None
