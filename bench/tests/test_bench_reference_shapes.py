"""The reference's loss takes labels of any leading shape (one per image,
or one per position of a sequence), rows are matched on every byte, and a
configuration need not give the image recipe's noise."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, reference

ROOT = Path(__file__).resolve().parents[2]
B, S, D, V = 3, 5, 4, 7


def _lm_model(monkeypatch):
    """A next-token model for the reference: logits [B, S, V] from
    embeddings [B, S, D]."""
    from bench import models

    class LM:
        @staticmethod
        def forward(p, x, precision):
            return jnp.tanh(jnp.matmul(x, p["w"], precision=precision)) * 3

    monkeypatch.setattr(models, "of", lambda cfg: LM)
    return LM


def test_mean_next_token_cross_entropy_and_its_gradient(monkeypatch):
    lm = _lm_model(monkeypatch)
    cfg = {"model": {"name": "lm"}, "matmul_precision": "highest"}
    ref = reference.Reference(cfg, 0.1)
    rng = np.random.default_rng(0)
    p = {"w": jnp.asarray(rng.normal(size=(D, V)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
    y = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
    loss, grad = ref._grad(p, x, y)

    def by_hand(w):
        total = 0.0
        for b in range(B):
            for s in range(S):
                z = lm.forward({"w": w}, x[b, s][None], "highest")[0]
                total = total + jax.nn.logsumexp(z) - z[y[b, s]]
        return total / (B * S)

    want, want_grad = jax.value_and_grad(by_hand)(p["w"])
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grad["w"]), np.asarray(want_grad),
                               rtol=1e-5, atol=1e-7)


def test_one_label_per_row_is_the_same_computation(monkeypatch):
    """On [B] labels the loss and its gradient are bitwise those of the
    loss written for one label per row."""
    lm = _lm_model(monkeypatch)
    cfg = {"model": {"name": "lm"}, "matmul_precision": "highest"}
    ref = reference.Reference(cfg, 0.1)
    rng = np.random.default_rng(1)
    p = {"w": jnp.asarray(rng.normal(size=(D, V)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)
    y = jnp.asarray(rng.integers(0, V, size=(B,)), jnp.int32)

    def one_label_per_row(p, x, y):
        logits = lm.forward(p, x, jax.lax.Precision.HIGHEST)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return (lse - gold).mean()

    loss, grad = ref._grad(p, x, y)
    want, want_grad = jax.jit(jax.value_and_grad(one_label_per_row))(p, x, y)
    assert float(loss) == float(want)
    np.testing.assert_array_equal(np.asarray(grad["w"]),
                                  np.asarray(want_grad["w"]))


def test_rows_sharing_a_long_prefix_are_told_apart():
    """Token rows that share their first 64 values (a system prompt) but
    differ later are both found, and no row is foreign."""
    rng = np.random.default_rng(2)
    prefix = rng.integers(0, 1000, size=64)
    x = np.stack([np.concatenate([prefix, rng.integers(0, 1000, size=64)])
                  for _ in range(4)]).astype(np.int32)
    y = np.arange(4 * 128, dtype=np.int32).reshape(4, 128)
    index = harness.row_index(x)
    assert sorted(index.values()) == [0, 1, 2, 3]

    class Client:
        def __init__(self, rows):
            self.x, self.y = x[rows], y[rows]

    clients = [Client([3, 1]), Client([0, 2])]
    check = harness.Check(
        w0=None, losses=[], blocks=[], programs=set(),
        feed=[{"cids": np.array([0, 1]), "idxs": np.array([[0, 1], [1, 0]]),
               "lam": np.zeros(2)}])
    rounds, foreign = harness.reference_rounds(check, clients, (x, y), index)
    assert foreign == 0
    np.testing.assert_array_equal(rounds[0]["x"], x[[[3, 1], [2, 0]]])
    np.testing.assert_array_equal(rounds[0]["y"], y[[[3, 1], [2, 0]]])


def test_noise_is_passed_only_where_the_configuration_gives_it():
    from repro.api.spec import DataSpec
    cfg = json.loads((ROOT / "bench/configs/lenet5-mnist.json").read_text())
    traffic = json.loads(
        (ROOT / "bench/traffic/sweep-proposed.json").read_text())
    cfg["data"]["noise"] = 0.5
    assert harness.make_spec(cfg, traffic, 1).data.noise == 0.5
    del cfg["data"]["noise"]
    assert harness.make_spec(cfg, traffic, 1).data.noise == \
        DataSpec(dataset="x").noise
