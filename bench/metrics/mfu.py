"""Model FLOP utilization of the traced unit, in percent: the FLOPs the
unit's training and evaluation require, counted from shapes
(bench/flops.py), over the traced window's seconds times the chips times
the chip's bfloat16 peak (bench/peaks.json). At the default precision the
chip multiplies float32 operands in one bfloat16 pass, so that is the
peak that bounds it.

Training counts three forward passes per real sample of each selected
client (padding clients of the bucket do not count), evaluation one
forward pass per test image at each evaluated round."""
from bench import flops


def read(ctx):
    t = ctx.trace
    if (t is None or t.window_s <= 0 or not ctx.traced_rounds
            or ctx.peaks is None):
        return None
    tr = ctx.traffic
    n = ctx.traced_rounds
    samples = sum(ctx.selected_per_round[:n]) * tr["batch"]
    work = flops.train_flops(ctx.cfg, samples)
    if tr["evaluate"]:
        evals = sum(1 for s in range(n)
                    if s % tr["eval_every"] == 0 or s == n - 1)
        work += flops.forward_flops(ctx.cfg) * ctx.cfg["data"]["test"] * evals
    return 100.0 * work / (t.window_s * ctx.chips * ctx.peaks["bf16_flops"])
