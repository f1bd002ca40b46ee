"""Host milliseconds per round in the program's `trainer.unpack` spans in
the window: the pytree view of the packed weights (or global gradient),
one eager slice per leaf, built when the buffer changed and read (before
each eval call). Read from the program's span recorder (`repro.obs`);
nothing where it has none."""


def read(ctx):
    try:
        from repro.obs import durations
    except ImportError:                  # a program without the recorder
        return None
    s = durations("trainer.unpack", *ctx.window)
    return 1e3 * sum(s) / ctx.rounds if s and ctx.rounds else None
