"""The paper's evaluation models: LeNet (MNIST) and ResNet-CIFAR (CIFAR-10).

Functional conv nets over param dicts — used by the FEEL reproduction
(examples/feel_mnist.py, benchmarks/fig*). ResNet depth follows the CIFAR
recipe (depth = 6n+2; ResNet-110 => n=18); a shallower default (ResNet-20)
keeps CPU experiment turnaround sane — depth is a parameter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.layers import dense_init


def _conv_init(key, shape, dtype=jnp.float32):
    fan_in = int(np.prod(shape[:-1]))
    return dense_init(key, fan_in, shape, dtype)


def _conv(x, w, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _conv_im2col(x, w):
    """Stride-1 SAME conv as shifted-slice patches + one GEMM.

    Bit-identical to `_conv` in the forward pass, but much faster on
    XLA:CPU for LeNet-sized channel counts (the generic conv lowering is
    scalar-loop-bound there), and its VJP is pad/slice/GEMM — no
    select-and-scatter. The federated round engine spends its FLOPs here."""
    kh, kw, cin, cout = w.shape
    b, h, wd, _ = x.shape
    # XLA SAME padding: (k-1)//2 low, k//2 high (equal for odd kernels)
    xp = jnp.pad(x, ((0, 0), ((kh - 1) // 2, kh // 2),
                     ((kw - 1) // 2, kw // 2), (0, 0)))
    cols = [xp[:, i:i + h, j:j + wd, :]
            for i in range(kh) for j in range(kw)]
    patches = jnp.concatenate(cols, axis=-1)        # [B, H, W, kh*kw*cin]
    return patches @ w.reshape(kh * kw * cin, cout)


def _max_pool_2x2(x):
    """2x2/stride-2 VALID max pool via reshape (even spatial dims only).

    Equivalent to the reduce_window form; the gradient is an argmax mask
    instead of XLA's slow select-and-scatter path."""
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


# ---------------------------------------------------------------------------
# LeNet-5 (28x28x1 -> 10)
# ---------------------------------------------------------------------------

def lenet_init(key, *, num_classes: int = 10, in_channels: int = 1):
    ks = jax.random.split(key, 5)
    return {
        "conv1": _conv_init(ks[0], (5, 5, in_channels, 6)),
        "conv2": _conv_init(ks[1], (5, 5, 6, 16)),
        "fc1": dense_init(ks[2], 784, (7 * 7 * 16, 120), jnp.float32),
        "b1": jnp.zeros((120,)),
        "fc2": dense_init(ks[3], 120, (120, 84), jnp.float32),
        "b2": jnp.zeros((84,)),
        "fc3": dense_init(ks[4], 84, (84, num_classes), jnp.float32),
        "b3": jnp.zeros((num_classes,)),
    }


def lenet_apply(params, x):
    x = jax.nn.relu(_conv_im2col(x, params["conv1"]))
    x = _max_pool_2x2(x)
    x = jax.nn.relu(_conv_im2col(x, params["conv2"]))
    x = _max_pool_2x2(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"] + params["b1"])
    x = jax.nn.relu(x @ params["fc2"] + params["b2"])
    return x @ params["fc3"] + params["b3"]


# ---------------------------------------------------------------------------
# ResNet-CIFAR (depth = 6n+2), no batchnorm state: GroupNorm-free scale/shift
# (keeps the model purely functional; the paper's optimization machinery is
# agnostic to the normalization choice)
# ---------------------------------------------------------------------------

def resnet_init(key, *, depth: int = 20, num_classes: int = 10,
                in_channels: int = 3, width: int = 16):
    if (depth - 2) % 6:
        raise ValueError("CIFAR ResNet depth must be 6n+2")
    n = (depth - 2) // 6
    ks = iter(jax.random.split(key, 1000))
    params: dict = {"stem": _conv_init(next(ks), (3, 3, in_channels, width))}
    chans = [width, 2 * width, 4 * width]
    blocks = []
    c_in = width
    for stage, c_out in enumerate(chans):
        for b in range(n):
            stride = 2 if (stage > 0 and b == 0) else 1
            # stride is derivable in apply: 2 iff in/out channels differ
            blk = {
                "conv1": _conv_init(next(ks), (3, 3, c_in, c_out)),
                "conv2": _conv_init(next(ks), (3, 3, c_out, c_out)),
                "scale1": jnp.ones((c_out,)), "bias1": jnp.zeros((c_out,)),
                "scale2": jnp.ones((c_out,)), "bias2": jnp.zeros((c_out,)),
            }
            if stride != 1 or c_in != c_out:
                blk["proj"] = _conv_init(next(ks), (1, 1, c_in, c_out))
            blocks.append(blk)
            c_in = c_out
    params["blocks"] = blocks
    params["head"] = dense_init(next(ks), chans[-1], (chans[-1], num_classes),
                                jnp.float32)
    params["head_b"] = jnp.zeros((num_classes,))
    return params


def _norm_act(x, scale, bias):
    mu = x.mean(axis=(1, 2), keepdims=True)
    var = x.var(axis=(1, 2), keepdims=True)
    return jax.nn.relu((x - mu) / jnp.sqrt(var + 1e-5) * scale + bias)


def resnet_apply(params, x):
    x = _conv(x, params["stem"])
    for blk in params["blocks"]:
        stride = 2 if blk["conv1"].shape[2] != blk["conv1"].shape[3] else 1
        h = _norm_act(_conv(x, blk["conv1"], stride=stride),
                      blk["scale1"], blk["bias1"])
        h = _conv(h, blk["conv2"])
        sc = _conv(x, blk["proj"], stride=stride) if "proj" in blk else x
        x = jax.nn.relu(_norm_act(h, blk["scale2"], blk["bias2"]) + sc)
    x = x.mean(axis=(1, 2))
    return x @ params["head"] + params["head_b"]


# ---------------------------------------------------------------------------
# mlp-edge: a two-layer MLP (~100k params) over flattened images. The
# dispatch-bound edge model: one round is cheap enough that the per-round
# host overhead the block engine removes is a measurable fraction of the
# round — the regime real accelerators put any of these models in. Promoted
# from benchmarks/round_engine.py so the experiment API can register it.
# ---------------------------------------------------------------------------

def mlp_edge_init(key, *, hidden: int = 128, num_classes: int = 10,
                  in_dim: int = 784):
    k1, k2 = jax.random.split(key)
    return {"fc1": jax.random.normal(k1, (in_dim, hidden)) * 0.05,
            "b1": jnp.zeros((hidden,)),
            "fc2": jax.random.normal(k2, (hidden, num_classes)) * 0.05,
            "b2": jnp.zeros((num_classes,))}


def mlp_edge_apply(params, x):
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"] + params["b1"])
    return x @ params["fc2"] + params["b2"]


# ---------------------------------------------------------------------------
# Shared loss / eval helpers
# ---------------------------------------------------------------------------

def make_loss_fn(apply_fn):
    def loss(params, x, y):
        logits = apply_fn(params, x)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return (lse - gold).mean()
    # per-sample-weighted companion: FederatedTrainer picks this up so
    # ragged client batches (fewer samples than the batch size) can be
    # padded and stay on the packed round path (core/federated.py)
    loss.weighted = make_weighted_loss_fn(apply_fn)
    return loss


def make_weighted_loss_fn(apply_fn):
    """Mean CE with per-sample weights: sum(sw * ce) / sum(sw).

    With sw = 1 everywhere this is bit-identical to `make_loss_fn`'s plain
    mean (1.0*ce is exact, the reductions share shape and order, and the
    divisor sum(ones) == B exactly), so the packed engine can thread sample
    weights unconditionally. Zero-weight samples (the padding of a ragged
    client batch) are exactly dropped from both the value and the gradient;
    the result is the plain mean over the real samples, evaluated at the
    padded shape — both trainer backends use this same function for ragged
    clients, which is what makes them bit-for-bit comparable (XLA
    reassociates reductions per *shape*, so a mean over [B'] and a masked
    mean over [B] agree in exact arithmetic but not in fp32)."""
    def loss(params, x, y, sw):
        logits = apply_fn(params, x)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - gold) * sw) / jnp.sum(sw)
    return loss


def make_eval_fn(apply_fn, x_test, y_test, batch: int = 500):
    x_test = jnp.asarray(x_test)
    y_test = jnp.asarray(y_test)

    @jax.jit
    def _batch_eval(params, xb, yb):
        with jax.named_scope("eval"):
            logits = apply_fn(params, xb)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
            acc = (logits.argmax(-1) == yb).mean()
            return (lse - gold).mean(), acc

    def eval_fn(params):
        """(mean test loss, mean test accuracy) over the batches, each
        batch's pair read to the host: one `eval` span, a `d2h` count per
        read, and per batch an `eval.wait` around the first read, which
        waits for the device (a wait of its own before the reads would
        cost one more host-device round trip per batch)."""
        losses, accs = [], []
        with obs.span("eval") as sp:
            for i in range(0, len(y_test), batch):
                l, a = _batch_eval(params, x_test[i:i + batch],
                                   y_test[i:i + batch])
                with obs.span("eval.wait"):
                    losses.append(float(l))
                accs.append(float(a))
                sp.count("d2h", 2)
        return float(np.mean(losses)), float(np.mean(accs))

    return eval_fn
