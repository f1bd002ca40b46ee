"""The analytic forward FLOPs of each configuration, against XLA's own
count of one forward pass of the system's model on the CPU.

The analytic count (bench/flops.py) is of dense convolutions with their
padding taps included, as the paper's Table I counts them: ResNet-44
194,872,576 FLOPs per 32x32x3 image and LeNet-5 1,386,000 per 28x28x1
image. XLA's `cost_analysis()` differs on two counts, which is why the
comparison has room:

* its convolution cost leaves out the taps that fall on SAME padding, so
  ResNet-44 (lax convolutions) reads about 5.5% lower;
* it counts elementwise work (normalization, ReLU, pooling, the loss) that
  the analytic count leaves out, so LeNet-5 (convolutions written as one
  matrix product over padded patches, padding taps counted) reads about 1%
  higher.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import flops

ROOT = Path(__file__).resolve().parents[2]


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def _xla_forward_flops(cfg) -> float:
    from repro.models import lenet_apply, lenet_init, resnet_apply, resnet_init
    h, w, c = cfg["data"]["image"]
    kw = cfg["program_model"]["kwargs"]
    if cfg["program_model"]["name"] == "resnet":
        init, apply = resnet_init, resnet_apply
    else:
        init, apply = lenet_init, lenet_apply
    params = init(jax.random.key(0), in_channels=c, **kw)
    x = jnp.zeros((1, h, w, c), jnp.float32)
    cost = jax.jit(apply).lower(params, x).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


@pytest.mark.parametrize("name,analytic,low,high", [
    ("resnet44-cifar10", 194_872_576, 0.92, 0.97),
    ("lenet5-mnist", 1_386_000, 1.0, 1.03),
])
def test_forward_flops_against_xla(name, analytic, low, high):
    cfg = _cfg(name)
    assert flops.forward_flops(cfg) == analytic
    ratio = _xla_forward_flops(cfg) / analytic
    assert low <= ratio <= high, ratio


def test_table_one_training_flops_per_sample():
    # Table I's CIFAR-10 row: 0.59 GFLOP per training sample
    assert abs(flops.train_flops(_cfg("resnet44-cifar10"), 1) / 1e9
               - 0.585) < 0.005

