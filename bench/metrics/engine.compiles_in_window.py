"""Round programs traced inside the window: the engine's `n_traces` after
the window minus before it. Set-up warms every program, so it is 0."""


def read(ctx):
    return ctx.compiles_in_window
