"""Host milliseconds per round inside `FederatedTrainer.run` outside the
engine's dispatch calls and the eval calls: bookkeeping, block planning,
batch-index draws, materialization waits, over the window's rounds."""


def read(ctx):
    run = sum(ctx.window_spans("trainer.run"))
    if not run or not ctx.rounds:
        return None
    inner = sum(ctx.window_spans("engine.dispatch")) + sum(
        ctx.window_spans("eval"))
    return 1e3 * (run - inner) / ctx.rounds
