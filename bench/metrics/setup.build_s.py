"""Seconds of set-up spent making the data, building the environment
(partition, phi, wireless system), solving the schedule and building the
trainer: the benchmark's span around `build_environment` and
`Experiment.build`."""


def read(ctx):
    s = ctx.setup_spans("setup.build")
    return sum(s) if s else None
