"""A model planted by the tests at the reference alone: each token id
looks up its row of an embedding table, and a dense head gives the logits
of every position, as a language model's first and last layers do. The
configuration's `model` is {"name": "embed-lookup", "vocab": V, "dim": D,
"classes": C}. The lookup takes whatever ids it is given as integers, as
a gather does, so an id that reached it rounded would look up another
row."""
import jax
import jax.numpy as jnp


def forward(p, x, precision):
    """x [B, S] token ids -> logits [B, S, classes]."""
    h = jnp.take(p["emb"], x.astype(jnp.int32), axis=0)
    return jnp.matmul(h, p["head"], precision=precision)


def init_params(cfg: dict, key):
    m = cfg["model"]
    k1, k2 = jax.random.split(key)
    return {"emb": jax.random.normal(k1, (m["vocab"], m["dim"])),
            "head": jax.random.normal(k2, (m["dim"], m["classes"]))}
