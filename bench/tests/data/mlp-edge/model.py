"""A model planted by the tests as `bench/models/mlp-edge.py`: the
system's registered `mlp-edge` (one hidden ReLU layer) on the benchmark's
synthetic images, with the configuration's `model` {"name": "mlp-edge",
"hidden": h}."""
import math

import jax
import jax.numpy as jnp

from bench import data as images
from bench.reference import F32, normal


def make_data(cfg: dict, seed: int):
    return images.make_images(cfg, seed)


def dataset(cfg: dict, data):
    return images.images(cfg, data)


def forward(p, x, precision):
    x = x.reshape(x.shape[0], -1)
    h = jax.nn.relu(jnp.matmul(x, p["fc1"], precision=precision) + p["b1"])
    return jnp.matmul(h, p["fc2"], precision=precision) + p["b2"]


def init_params(cfg: dict, key):
    d = math.prod(cfg["data"]["image"])
    h = cfg["model"]["hidden"]
    nc = cfg["data"]["classes"]
    k1, k2 = jax.random.split(key)
    return {"fc1": normal(k1, (d, h), d), "b1": jnp.zeros((h,), F32),
            "fc2": normal(k2, (h, nc), h), "b2": jnp.zeros((nc,), F32)}


def forward_flops(cfg: dict) -> int:
    d = math.prod(cfg["data"]["image"])
    h = cfg["model"]["hidden"]
    return 2 * (d * h + h * cfg["data"]["classes"])


def test_size(files: dict) -> dict:
    return images.test_size(files)
