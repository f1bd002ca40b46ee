"""The reference reduces the clients' masked gradients by the traffic's
aggregator, never falling back to the mean, and integer inputs (token
ids) reach the model as integers in every precision, the bfloat16
control's included."""
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference

DATA = Path(__file__).resolve().parent / "data"


def numpy_trimmed_mean(stack: np.ndarray, beta: float) -> np.ndarray:
    """Coordinate by coordinate, in float64: sort the n values, drop
    floor(beta * n) from each end, average the rest."""
    n = stack.shape[0]
    t = math.floor(beta * n)
    out = np.empty(stack.shape[1:])
    for idx in np.ndindex(*stack.shape[1:]):
        kept = sorted(stack[(slice(None),) + idx].astype(np.float64))[t:n - t]
        out[idx] = sum(kept) / len(kept)
    return out


@pytest.mark.parametrize("n", [5, 64])
@pytest.mark.parametrize("beta", [0.0, 0.1, 0.25])
def test_trimmed_mean_is_the_per_coordinate_definition(n, beta):
    rng = np.random.default_rng(1000 * n + int(100 * beta))
    stack = rng.standard_cauchy(size=(n, 3, 7)).astype(np.float32)
    got = np.asarray(reference.trimmed_mean(jnp.asarray(stack), beta))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, numpy_trimmed_mean(stack, beta),
                               rtol=2e-6, atol=1e-6)
    if math.floor(beta * n):          # a trim that drops the outliers
        assert not np.allclose(got, stack.mean(axis=0), rtol=1e-3)


def _embed_lookup(monkeypatch):
    """The tiny model file under tests/data, as the reference finds a
    configuration's model."""
    from bench import models
    spec = importlib.util.spec_from_file_location(
        "bench_test_embed_lookup", DATA / "embed-lookup" / "model.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(models, "of", lambda cfg: mod)
    return mod


CFG = {"model": {"name": "embed-lookup", "vocab": 5000, "dim": 4,
                 "classes": 8},
       "matmul_precision": "highest", "pruning": {"protected": []}}


def _one_round(rng, clients: int, ids: np.ndarray):
    """One round of `clients` clients with the given [B, S] token ids,
    lambda 0 (nothing pruned)."""
    return {"x": np.stack([ids] * clients),
            "y": rng.integers(0, 8, size=(clients,) + ids.shape).astype(
                np.int32),
            "lam": np.zeros(clients)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_token_ids_above_256_reach_the_model_unchanged(monkeypatch, dtype):
    """bfloat16 holds integers exactly only up to 256: ids cast to it
    would look up rows 256, 1000 and 4096. The broadcast gradient after
    one round is nonzero in the embedding rows of exactly the ids sent."""
    model = _embed_lookup(monkeypatch)
    ids = np.array([[257, 1001, 4097], [3, 257, 999]], np.int32)
    w0 = jax.tree.map(np.asarray, model.init_params(CFG, jax.random.key(0)))
    ref = reference.Reference(CFG, 0.1, dtype=dtype)
    out = ref.run(w0, [_one_round(np.random.default_rng(0), 2, ids)])
    (_, _, _, v), = out["blocks"]
    emb = np.asarray(v[0], np.float32)           # leaves: emb, head
    assert emb.shape == (5000, 4)
    assert sorted(np.flatnonzero(np.abs(emb).sum(axis=1))) == \
        sorted(set(ids.ravel()))


def test_the_reference_step_takes_the_trimmed_mean_of_the_clients(
        monkeypatch):
    """Five clients with different labels, beta 0.25: the broadcast
    gradient is the trimmed mean of the five masked gradients, one trimmed
    from each end, and not their mean."""
    model = _embed_lookup(monkeypatch)
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 5000, size=(4, 3)).astype(np.int32)
    rnd = _one_round(rng, 5, ids)
    w0 = jax.tree.map(np.asarray, model.init_params(CFG, jax.random.key(1)))
    ref = reference.Reference(CFG, 0.1, aggregator="trimmed_mean",
                              aggregator_kwargs={"beta": 0.25})
    (_, _, _, v), = ref.run(w0, [rnd])["blocks"]
    w = jax.tree.map(jnp.asarray, w0)
    ref._flags = reference.prunable_flags(CFG, w)
    grads = [ref._client(w, jax.tree.map(jnp.zeros_like, w), -jnp.inf,
                         jnp.asarray(rnd["x"][c]), jnp.asarray(rnd["y"][c]))[1]
             for c in range(5)]
    for name, got in zip(sorted(w), v):          # leaves in key order
        stack = np.stack([np.asarray(g[name]) for g in grads])
        np.testing.assert_allclose(got, numpy_trimmed_mean(stack, 0.25),
                                   rtol=1e-5, atol=1e-7)
        assert not np.allclose(got, stack.mean(axis=0), rtol=1e-3)


@pytest.mark.parametrize("aggregator, kwargs", [
    ("coord_median", {}), ("norm_clip", {"tau": 1.0}),
    ("multi_krum", {"f": 1}), ("no_such_rule", {})])
def test_an_aggregator_the_reference_does_not_state_is_an_error(
        aggregator, kwargs):
    with pytest.raises(ValueError, match=aggregator):
        reference.Reference(CFG, 0.1, aggregator=aggregator,
                            aggregator_kwargs=kwargs)


@pytest.mark.parametrize("aggregator, kwargs", [
    ("trimmed_mean", {}), ("trimmed_mean", {"beta": 0.1, "gamma": 1}),
    ("trimmed_mean", {"beta": 0.5}), ("mean", {"beta": 0.1})])
def test_a_setting_the_aggregator_lacks_or_does_not_know_is_an_error(
        aggregator, kwargs):
    with pytest.raises(ValueError, match=aggregator):
        reference.Reference(CFG, 0.1, aggregator=aggregator,
                            aggregator_kwargs=kwargs)
