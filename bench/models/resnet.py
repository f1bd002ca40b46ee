"""CIFAR ResNet (He et al. 2016, Sec. 4.2) on the benchmark's synthetic
images: the configuration's `model` is {"name": "resnet", "depth": 6n+2,
"width": w}."""
import jax
import jax.numpy as jnp

from bench import data as images
from bench.flops import conv as conv_flops
from bench.reference import F32, conv, normal


def make_data(cfg: dict, seed: int):
    return images.make_images(cfg, seed)


def dataset(cfg: dict, data):
    return images.images(cfg, data)


def _norm_relu(x, scale, bias):
    """Per-sample, per-channel normalization over the spatial axes, then
    scale, shift and ReLU."""
    mu = x.mean(axis=(1, 2), keepdims=True)
    var = jnp.square(x - mu).mean(axis=(1, 2), keepdims=True)
    return jax.nn.relu((x - mu) / jnp.sqrt(var + 1e-5) * scale + bias)


def forward(p, x, precision):
    """A 3x3 stem, three stages of n basic blocks at widths w, 2w, 4w (the
    first block of stages 2 and 3 strides 2 and projects its shortcut with
    a strided 1x1 convolution, as the configuration's `layout` and
    `assumed` say, where Sec. 4.2 pads the identity with zeros), global
    average pooling and a linear head."""
    x = conv(x, p["stem"], 1, precision)
    for blk in p["blocks"]:
        stride = 2 if blk["conv1"].shape[2] != blk["conv1"].shape[3] else 1
        h = _norm_relu(conv(x, blk["conv1"], stride, precision),
                       blk["scale1"], blk["bias1"])
        h = _norm_relu(conv(h, blk["conv2"], 1, precision),
                       blk["scale2"], blk["bias2"])
        sc = conv(x, blk["proj"], stride, precision) if "proj" in blk else x
        x = jax.nn.relu(h + sc)
    x = x.mean(axis=(1, 2))
    return jnp.matmul(x, p["head"], precision=precision) + p["head_b"]


def init_params(cfg: dict, key):
    """Normal weights with variance 1/fan_in, every bias 0, norm scales 1
    except the second norm of each residual block, which starts at the
    configuration's `init.residual_scale`."""
    m = cfg["model"]
    c_in = cfg["data"]["image"][2]
    nc = cfg["data"]["classes"]
    ks = iter(jax.random.split(key, 4096))
    n = (m["depth"] - 2) // 6
    width = m["width"]
    rs = cfg.get("init", {}).get("residual_scale", 1.0)
    p = {"stem": normal(next(ks), (3, 3, c_in, width), 9 * c_in)}
    blocks, c = [], width
    for c_out in (width, 2 * width, 4 * width):
        for _ in range(n):
            blk = {
                "conv1": normal(next(ks), (3, 3, c, c_out), 9 * c),
                "conv2": normal(next(ks), (3, 3, c_out, c_out), 9 * c_out),
                "scale1": jnp.ones((c_out,), F32),
                "bias1": jnp.zeros((c_out,), F32),
                "scale2": jnp.full((c_out,), rs, F32),
                "bias2": jnp.zeros((c_out,), F32),
            }
            if c != c_out:
                blk["proj"] = normal(next(ks), (1, 1, c, c_out), c)
            blocks.append(blk)
            c = c_out
    p["blocks"] = blocks
    p["head"] = normal(next(ks), (c, nc), c)
    p["head_b"] = jnp.zeros((nc,), F32)
    return p


def forward_flops(cfg: dict) -> int:
    """Forward FLOPs per image: every convolution and the head."""
    m = cfg["model"]
    h, w, c = cfg["data"]["image"]
    nc = cfg["data"]["classes"]
    n = (m["depth"] - 2) // 6
    width = m["width"]
    total, h, w = conv_flops(h, w, 3, c, width)
    c = width
    for c_out in (width, 2 * width, 4 * width):
        for _ in range(n):
            stride = 2 if c != c_out else 1
            f1, ho, wo = conv_flops(h, w, 3, c, c_out, stride)
            f2, _, _ = conv_flops(ho, wo, 3, c_out, c_out)
            total += f1 + f2
            if c != c_out:
                total += conv_flops(h, w, 1, c, c_out, stride)[0]
            h, w, c = ho, wo, c_out
    return total + 2 * c * nc


def test_size(files: dict) -> dict:
    """Depth 8 (one block per stage), 800 training and 100 test images,
    4 clients, 6 rounds, eval every 3, blocks of at most 2 rounds."""
    files = images.test_size(files)
    cfg = files["config"]
    cfg["model"]["depth"] = 8
    cfg["program_model"]["kwargs"]["depth"] = 8
    return files
