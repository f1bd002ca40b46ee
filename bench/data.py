"""Synthetic image data at a configuration's dataset sizes, made on the
device from the seed in one jitted call.

The recipe is the usual stand-in for MNIST/CIFAR when the real files are
not at hand: each class is a mix of smooth random templates (low-pass
Fourier noise scaled to [0, 1]), each image a template times a contrast in
[0.6, 1] plus Gaussian noise, clipped to [0, 1] and standardized with the
training set's mean and deviation.

Labels are fixed, not drawn: sample i has class i mod classes. The
Dirichlet partition over clients reads only the labels, so with its seed
fixed in the traffic file every seed gives every client the same number of
samples and the same label mix, and the schedule solved from them is the
same; the seed changes the images, the weights and the batch draws.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

N_TEMPLATES = 4


@functools.partial(jax.jit, static_argnames=("n_train", "n_test", "shape",
                                             "classes"))
def _make(key, noise, *, n_train, n_test, shape, classes):
    h, w, c = shape
    kt, ki, km, kn = jax.random.split(key, 4)
    fy = jnp.fft.fftfreq(h)[:, None]
    fx = jnp.fft.fftfreq(w)[None, :]
    lowpass = 1.0 / (1.0 + 64.0 * (fy ** 2 + fx ** 2))
    t = jax.random.normal(kt, (classes, N_TEMPLATES, h, w, c))
    img = jnp.real(jnp.fft.ifft2(jnp.fft.fft2(t, axes=(2, 3))
                                 * lowpass[None, None, :, :, None],
                                 axes=(2, 3)))
    img = img - img.min(axis=(2, 3, 4), keepdims=True)
    img = (img / (img.max(axis=(2, 3, 4), keepdims=True) + 1e-9)
           ).astype(jnp.float32)
    n = n_train + n_test
    y = (jnp.arange(n) % classes).astype(jnp.int32)
    t_idx = jax.random.randint(ki, (n,), 0, N_TEMPLATES)
    mix = jax.random.uniform(km, (n, 1, 1, 1), jnp.float32, 0.6, 1.0)
    x = mix * img[y, t_idx] + noise * jax.random.normal(kn, (n, h, w, c))
    x = jnp.clip(x, 0.0, 1.0)
    mu = x[:n_train].mean()
    sd = x[:n_train].std() + 1e-8
    x = (x - mu) / sd
    return x[:n_train], y[:n_train], x[n_train:], y[n_train:]


def make_images(cfg: dict, seed: int):
    """(x_train, y_train, x_test, y_test) as host numpy arrays for the
    configuration's `data` block."""
    d = cfg["data"]
    key = jax.random.fold_in(jax.random.key(0), seed % (1 << 31))
    key = jax.random.fold_in(key, seed >> 31)
    out = _make(key, jnp.float32(d["noise"]), n_train=int(d["train"]),
                n_test=int(d["test"]), shape=tuple(d["image"]),
                classes=int(d["classes"]))
    return tuple(np.asarray(a) for a in out)
