"""Plain reference of the first rounds of a federated run.

A straightforward ``jax.numpy`` statement of the paper's parameter-efficient
FedSGD round (Sec. II-A, eqs. 2-7), written from the configuration files
and the paper alone: it imports nothing of the system under test and is
given only the initial weights the benchmark made, the batches, and the
schedule's selection and pruning ratios. Per round s and selected client n:

  1. importance Q = (v * w)^2 over the prunable weights (eq. 4), with v the
     previous round's global gradient (zero before round 0);
  2. k = floor(lambda_n * M_prunable); the threshold is the k-th smallest Q
     nudged one float up (`nextafter`), so exactly the k lowest are pruned;
     k = 0 prunes nothing. Weights with Q at or above the threshold stay;
  3. the client's mean cross-entropy gradient on its pruned model, masked
     (eq. 5);
  4. the server averages the masked gradients of the selected clients
     (eq. 6), steps w <- w - eta * mean (eq. 7), and broadcasts the mean as
     the next round's v. Where the traffic names a robust aggregator in
     place of the mean, the server reduces the same masked gradients by
     that rule (`AGGREGATORS`), steps by the result and broadcasts it.

The threshold is compared on the device, like everything else here: a
device that flushes subnormal floats to zero reads ``nextafter(0, inf)`` as
0, so a round whose k-th smallest importance is 0 keeps every weight.

The reference computes in the precision the configuration states:
float32 arrays, matrix products and convolutions at the configuration's
`matmul_precision` ("default": on a TPU, float32 operands rounded to
bfloat16 in one pass, accumulated in float32). ``dtype=jnp.bfloat16``
gives the control, the nearest precision below: the same arithmetic with
every array in bfloat16. Integer inputs (token ids) stay integers, in
int32, in every precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ---------------------------------------------------------------------------
# the models: one file each, bench/models/<model>.py, in the layout the
# configuration files describe; layers they share
# ---------------------------------------------------------------------------

def conv(x, w, stride, precision):
    """A SAME convolution of NHWC images with an HWIO kernel."""
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def normal(key, shape, fan_in):
    """Normal weights with variance 1/fan_in."""
    return jax.random.normal(key, shape, F32) / math.sqrt(fan_in)


def init_params(cfg: dict, key):
    """Weights for `cfg["model"]` from `key`, by its model's file. Jit it:
    one device call makes them."""
    from bench import models
    return models.of(cfg).init_params(cfg, key)


def leaf_names(tree) -> list[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(kp) for kp, _ in flat]


def prunable_flags(cfg: dict, tree) -> list[bool]:
    """A leaf is prunable unless its name holds one of the configuration's
    `protected` substrings."""
    prot = [s.lower() for s in cfg["pruning"]["protected"]]
    return [not any(s in n.lower() for s in prot) for n in leaf_names(tree)]


# ---------------------------------------------------------------------------
# the rounds
# ---------------------------------------------------------------------------

def trimmed_mean(stack, beta: float):
    """Per coordinate over the leading (client) axis of `stack`: sort, drop
    the floor(beta * n) smallest and the floor(beta * n) largest of the n
    values, and take the mean of the rest."""
    n = stack.shape[0]
    t = math.floor(beta * n)
    return jnp.sort(stack, axis=0)[t:n - t].mean(axis=0)


# the server's reductions of the selected clients' masked gradients that
# the reference states, by the name a traffic file gives
AGGREGATORS = ("mean", "trimmed_mean")


def _beta(aggregator: str, kwargs: dict) -> float | None:
    """The trimmed share of `aggregator`'s `kwargs`, or None for the mean.
    An aggregator the reference does not state, or a setting it does not
    know or lacks, is an error: it never falls back to the mean."""
    if aggregator not in AGGREGATORS:
        raise ValueError(f"the reference states no aggregator {aggregator!r}"
                         f"; it states {list(AGGREGATORS)}")
    want = {"beta"} if aggregator == "trimmed_mean" else set()
    if set(kwargs) != want:
        raise ValueError(f"the reference's {aggregator!r} takes the settings "
                         f"{sorted(want)}, got {sorted(kwargs)}")
    if not want:
        return None
    beta = float(kwargs["beta"])
    if not 0.0 <= beta < 0.5:
        raise ValueError(f"trimmed_mean beta must be in [0, 0.5), got {beta}")
    return beta


def as_input(a, dtype):
    """An input array in the reference's `dtype` where it holds floats;
    integers (token ids, labels) in int32, whatever the dtype."""
    a = np.asarray(a)
    return jnp.asarray(a, dtype if np.issubdtype(a.dtype, np.floating)
                       else jnp.int32)


class Reference:
    """The rounds of one configuration, in float32 at the configuration's
    matmul precision or (the control) in bfloat16, with the server's
    `aggregator` ("mean", or "trimmed_mean" with `aggregator_kwargs`
    {"beta": share}). `half_batch=True` is a planted fault: each client's
    gradient from the first half of its batch only."""

    def __init__(self, cfg: dict, eta: float, *, aggregator: str = "mean",
                 aggregator_kwargs: dict | None = None, dtype=F32,
                 half_batch: bool = False):
        self.cfg = cfg
        self.eta = float(eta)
        self.beta = _beta(aggregator, aggregator_kwargs or {})
        self.dtype = dtype
        self.half_batch = half_batch
        from bench import models
        fwd = models.of(cfg).forward
        prec = jax.lax.Precision[cfg["matmul_precision"].upper()]

        def loss(p, x, y):
            """Mean cross-entropy over every label: one per image, or one
            per position of a sequence."""
            logits = fwd(p, x, prec)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
            return (lse - gold).mean()

        self._grad = jax.jit(jax.value_and_grad(loss))
        self._thresholds = jax.jit(self._thresholds_impl)
        self._client = jax.jit(self._client_impl)
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        self._step = jax.jit(self._step_impl)
        self._trimmed_step = jax.jit(self._trimmed_step_impl)
        self._flags = None

    def _thresholds_impl(self, w, v, ks):
        leaves_w = jax.tree.leaves(w)
        leaves_v = jax.tree.leaves(v)
        q = jnp.concatenate([jnp.square(a * b).reshape(-1)
                             for a, b, f in zip(leaves_w, leaves_v,
                                                self._flags) if f])
        qs = jnp.sort(q)
        kth = qs[jnp.maximum(ks - 1, 0)]
        inf = jnp.asarray(jnp.inf, qs.dtype)
        return jnp.where(ks > 0, jnp.nextafter(kth, inf), -inf)

    def _client_impl(self, w, v, thr, x, y):
        treedef = jax.tree.structure(w)
        masks = [jnp.where(f, (jnp.square(a * b) >= thr).astype(a.dtype),
                           jnp.ones_like(a))
                 for a, b, f in zip(jax.tree.leaves(w), jax.tree.leaves(v),
                                    self._flags)]
        masks = jax.tree.unflatten(treedef, masks)
        pruned = jax.tree.map(lambda a, m: a * m, w, masks)
        loss, g = self._grad(pruned, x, y)
        return loss, jax.tree.map(lambda a, m: a * m, g, masks)

    def _step_impl(self, w, acc, n):
        mean = jax.tree.map(lambda a: a / n, acc)
        return jax.tree.map(lambda a, b: a - self.eta * b, w, mean), mean

    def _trimmed_step_impl(self, w, grads):
        ghat = jax.tree.map(lambda *g: trimmed_mean(jnp.stack(g), self.beta),
                            *grads)
        return jax.tree.map(lambda a, b: a - self.eta * b, w, ghat), ghat

    def run(self, w0, rounds: list[dict], *, v0=None,
            blocks: list[int] | None = None) -> dict:
        """Follow `rounds` (each {"x": [C,B,...], "y": [C,B,...], "lam":
        [C]}, the real clients only) from weights `w0` and broadcast
        gradient `v0` (zero before round 0). `blocks` splits the rounds
        into runs of the given lengths (default: one). Returns host
        copies, in the reference's dtype: the per-round mean loss and,
        after each block, (start, length, the weights, the broadcast
        gradient)."""
        dt = self.dtype
        w = jax.tree.map(lambda a: jnp.asarray(a, dt), w0)
        if self._flags is None:
            self._flags = prunable_flags(self.cfg, w)
        v = (jax.tree.map(jnp.zeros_like, w) if v0 is None
             else jax.tree.map(lambda a: jnp.asarray(a, dt), v0))
        m_prunable = sum(int(np.prod(a.shape)) for a, f in
                         zip(jax.tree.leaves(w), self._flags) if f)
        ends = np.cumsum(blocks if blocks else [len(rounds)])
        if ends[-1] != len(rounds):
            raise ValueError(f"blocks {blocks} do not cover {len(rounds)} "
                             "rounds")
        losses, snaps = [], []
        for s, r in enumerate(rounds):
            lam = np.asarray(r["lam"], np.float64)
            ks = jnp.asarray(np.floor(lam * m_prunable).astype(np.int32))
            thr = self._thresholds(w, v, ks)
            acc, grads, ls = None, [], []
            for c in range(len(lam)):
                x = as_input(r["x"][c], dt)
                y = jnp.asarray(r["y"][c], jnp.int32)
                if self.half_batch:
                    x, y = x[: len(y) // 2], y[: len(y) // 2]
                loss, g = self._client(w, v, thr[c], x, y)
                ls.append(loss)
                if self.beta is not None:
                    grads.append(g)
                else:        # the running sum in client order, over n
                    acc = g if acc is None else self._add(acc, g)
            if self.beta is not None:
                w, v = self._trimmed_step(w, grads)
            else:
                w, v = self._step(w, acc, jnp.asarray(len(lam), dt))
            losses.append(float(np.mean(np.asarray(
                [np.float32(l) for l in ls], np.float64))))
            if s + 1 in ends:
                start = snaps[-1][0] + snaps[-1][1] if snaps else 0
                snaps.append((start, s + 1 - start, _host(w), _host(v)))
        return {"losses": losses, "blocks": snaps}


def _host(tree) -> list[np.ndarray]:
    return [np.asarray(a) for a in jax.tree.leaves(tree)]


# ---------------------------------------------------------------------------
# the comparison, by norms of leaves taken in float64 one leaf at a time:
# the check keeps states in their own dtype and of the reference only norms
# ---------------------------------------------------------------------------

def change_norms(w: list, start: list) -> list[float]:
    """Per leaf, the norm of `w - start`, both widened to float64."""
    return [float(np.linalg.norm(np.subtract(a, b, dtype=np.float64)))
            for a, b in zip(w, start)]


def grad_norms(v: list) -> list[float]:
    """Per leaf, the norm of `v` widened to float64."""
    return [float(np.linalg.norm(np.asarray(a, np.float64))) for a in v]


def follow(ref: Reference, w0, rounds: list[dict], run: dict) -> dict:
    """`ref` over the blocks of `run` (the system, or a stand-in put in its
    place), each block from the state `run` held at its start: the weights
    and broadcast gradient it returned for the block before, or `w0` and
    zero for the first. So each block program is judged on its own input,
    and a difference does not carry over from one block to the next.

    Of the reference only what `compare` reads is kept: the per-round
    losses and, per block, (start, length, the norms of each leaf's change
    over the block, the norms of the broadcast gradient's leaves). Each
    block's state is dropped once they are taken."""
    treedef = jax.tree.structure(w0)
    w, v, start_w = w0, None, jax.tree.leaves(w0)
    losses, norms = [], []
    for start, n, w_end, v_end in run["blocks"]:
        r = ref.run(w, rounds[start:start + n], v0=v)
        losses += r["losses"]
        _, _, w_ref, v_ref = r["blocks"][0]
        norms.append((start, n, change_norms(w_ref, start_w),
                      grad_norms(v_ref)))
        w = jax.tree.unflatten(treedef, [np.asarray(a, np.float32)
                                         for a in w_end])
        v = jax.tree.unflatten(treedef, [np.asarray(a, np.float32)
                                         for a in v_end])
        start_w = w_end
    return {"losses": losses, "blocks": norms}


def _norm_gaps(got: list, want: list, keep: list[bool]) -> list:
    """|got - want| over the larger of the want leaf's norm and the median
    want leaf's norm, for each kept leaf, from per-leaf norms."""
    med = float(np.median(want))
    return [abs(a - b) / max(b, med) if max(b, med) > 0 else 0.0
            for a, b, k in zip(got, want, keep) if k]


def compare(run: dict, ref: dict, w0: list) -> dict:
    """The numbers `correct` holds to limits, `run` (the program, or a
    stand-in in its place, with its states after each block) against
    `ref`, the norms that `follow` took of the reference led over the same
    blocks from the same start states; `w0` are the leaves of the weights
    both started from:

    loss_gap    largest relative gap of the mean loss of a block's first
                round, which both sides compute from the same state;
    grad_gap    worst leaf's gap between the norms of the broadcast
                gradient (the optimizer's state) after the first block:
                the first gradient, where that block is one round;
    change_gap  worst leaf's gap between the norms of the weights' change
                over a block, worst block, leaving out leaves whose
                reference first gradient is under a thousandth of the
                median leaf's (their change is round-off).

    The later rounds of a long block are not compared one by one: a
    pruning mask that flips on a near-tie of importance in one of them
    makes the two sides part for the rest of the block (on the chip,
    3 of 12 LeNet-5 seeds in an eight-round block), while each block's first round and its change stay
    steady. `by_block` gives (start, length, first-round loss gap, gap of
    the gradient after it, change gap) of each block."""
    lp, lr = np.asarray(run["losses"]), np.asarray(ref["losses"])
    rel = np.abs(lp - lr) / np.abs(lr)
    gn = ref["blocks"][0][3]
    floor = 1e-3 * float(np.median(gn))
    keep = [g >= floor for g in gn]
    start_w = w0
    by_block = []
    for (s, n, w_run, v_run), (_, _, c_ref, g_ref) in zip(run["blocks"],
                                                          ref["blocks"]):
        g = max(_norm_gaps(grad_norms(v_run), g_ref, [True] * len(w0)))
        c = max(_norm_gaps(change_norms(w_run, start_w), c_ref, keep))
        by_block.append((s, n, float(rel[s]), g, c))
        start_w = w_run
    return {"loss_gap": max(b[2] for b in by_block),
            "grad_gap": by_block[0][3],
            "change_gap": max(b[4] for b in by_block),
            "leaves_left_out": int(len(keep) - sum(keep)),
            "by_block": by_block}
