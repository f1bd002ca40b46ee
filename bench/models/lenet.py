"""LeNet-5 (LeCun et al. 1998) on the benchmark's synthetic images: the
configuration's `model` is {"name": "lenet", "widths": [c1, c2, f1, f2],
"kernel": k}."""
import jax
import jax.numpy as jnp

from bench import data as images
from bench.flops import conv as conv_flops
from bench.reference import F32, conv, normal


def make_data(cfg: dict, seed: int):
    return images.make_images(cfg, seed)


def dataset(cfg: dict, data):
    return images.images(cfg, data)


def _pool2(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(p, x, precision):
    """SAME-padded 5x5 convolutions: conv 6, pool, conv 16, pool, dense
    120, 84, classes; ReLU throughout."""
    x = _pool2(jax.nn.relu(conv(x, p["conv1"], 1, precision)))
    x = _pool2(jax.nn.relu(conv(x, p["conv2"], 1, precision)))
    x = x.reshape(x.shape[0], -1)
    mm = lambda a, b: jnp.matmul(a, b, precision=precision)
    x = jax.nn.relu(mm(x, p["fc1"]) + p["b1"])
    x = jax.nn.relu(mm(x, p["fc2"]) + p["b2"])
    return mm(x, p["fc3"]) + p["b3"]


def init_params(cfg: dict, key):
    """Normal weights with variance 1/fan_in, every bias 0."""
    m = cfg["model"]
    h, w, c_in = cfg["data"]["image"]
    nc = cfg["data"]["classes"]
    ks = iter(jax.random.split(key, 4096))
    c1, c2, f1, f2 = m["widths"]
    k = m["kernel"]
    flat = (h // 4) * (w // 4) * c2
    return {
        "conv1": normal(next(ks), (k, k, c_in, c1), k * k * c_in),
        "conv2": normal(next(ks), (k, k, c1, c2), k * k * c1),
        "fc1": normal(next(ks), (flat, f1), flat),
        "b1": jnp.zeros((f1,), F32),
        "fc2": normal(next(ks), (f1, f2), f1),
        "b2": jnp.zeros((f2,), F32),
        "fc3": normal(next(ks), (f2, nc), f2),
        "b3": jnp.zeros((nc,), F32),
    }


def forward_flops(cfg: dict) -> int:
    """Forward FLOPs per image: both convolutions and the dense layers."""
    m = cfg["model"]
    h, w, c = cfg["data"]["image"]
    nc = cfg["data"]["classes"]
    c1, c2, f1, f2 = m["widths"]
    k = m["kernel"]
    a, _, _ = conv_flops(h, w, k, c, c1)
    b, _, _ = conv_flops(h // 2, w // 2, k, c1, c2)
    flat = (h // 4) * (w // 4) * c2
    return a + b + 2 * (flat * f1 + f1 * f2 + f2 * nc)


def test_size(files: dict) -> dict:
    """800 training and 100 test images, 4 clients, 6 rounds, eval every
    3, blocks of at most 2 rounds."""
    return images.test_size(files)
