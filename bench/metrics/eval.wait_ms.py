"""Milliseconds per eval call that the host waits for the device: the
program's `eval.wait` spans (one per test batch: the batch's first read,
which waits for the device and brings one scalar back) in the window,
over the number of its `eval` spans. Read from the program's span
recorder (`repro.obs`); nothing where it has none, or in a cell without
evaluation."""


def read(ctx):
    try:
        from repro.obs import durations
    except ImportError:                  # a program without the recorder
        return None
    calls = durations("eval", *ctx.window)
    if not calls:
        return None
    return 1e3 * sum(durations("eval.wait", *ctx.window)) / len(calls)
