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
     the next round's v.

The threshold is compared on the device, like everything else here: a
device that flushes subnormal floats to zero reads ``nextafter(0, inf)`` as
0, so a round whose k-th smallest importance is 0 keeps every weight.

The reference computes in the precision the configuration states:
float32 arrays, matrix products and convolutions at the configuration's
`matmul_precision` ("default": on a TPU, float32 operands rounded to
bfloat16 in one pass, accumulated in float32). ``dtype=jnp.bfloat16``
gives the control, the nearest precision below: the same arithmetic with
every array in bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ---------------------------------------------------------------------------
# models (NHWC images, HWIO kernels), in the layout the configuration files
# describe
# ---------------------------------------------------------------------------

def _conv(x, w, stride, precision):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _norm_relu(x, scale, bias):
    """Per-sample, per-channel normalization over the spatial axes, then
    scale, shift and ReLU."""
    mu = x.mean(axis=(1, 2), keepdims=True)
    var = jnp.square(x - mu).mean(axis=(1, 2), keepdims=True)
    return jax.nn.relu((x - mu) / jnp.sqrt(var + 1e-5) * scale + bias)


def resnet_forward(p, x, precision):
    """CIFAR ResNet (He et al. 2016, Sec. 4.2): a 3x3 stem, three stages of
    n basic blocks at widths w, 2w, 4w (the first block of stages 2 and 3
    strides 2 and projects its shortcut with a strided 1x1 convolution, as
    the configuration's `layout` and `assumed` say, where Sec. 4.2 pads the
    identity with zeros), global average pooling and a linear head."""
    x = _conv(x, p["stem"], 1, precision)
    for blk in p["blocks"]:
        stride = 2 if blk["conv1"].shape[2] != blk["conv1"].shape[3] else 1
        h = _norm_relu(_conv(x, blk["conv1"], stride, precision),
                       blk["scale1"], blk["bias1"])
        h = _norm_relu(_conv(h, blk["conv2"], 1, precision),
                       blk["scale2"], blk["bias2"])
        sc = _conv(x, blk["proj"], stride, precision) if "proj" in blk else x
        x = jax.nn.relu(h + sc)
    x = x.mean(axis=(1, 2))
    return jnp.matmul(x, p["head"], precision=precision) + p["head_b"]


def _pool2(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def lenet_forward(p, x, precision):
    """LeNet-5 (LeCun et al. 1998) with SAME-padded 5x5 convolutions:
    conv 6, pool, conv 16, pool, dense 120, 84, classes; ReLU throughout."""
    x = _pool2(jax.nn.relu(_conv(x, p["conv1"], 1, precision)))
    x = _pool2(jax.nn.relu(_conv(x, p["conv2"], 1, precision)))
    x = x.reshape(x.shape[0], -1)
    mm = lambda a, b: jnp.matmul(a, b, precision=precision)
    x = jax.nn.relu(mm(x, p["fc1"]) + p["b1"])
    x = jax.nn.relu(mm(x, p["fc2"]) + p["b2"])
    return mm(x, p["fc3"]) + p["b3"]


FORWARD = {"resnet": resnet_forward, "lenet": lenet_forward}


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, F32) / math.sqrt(fan_in)


def init_params(cfg: dict, key):
    """Weights for `cfg["model"]` from `key`: normal with variance 1/fan_in,
    every bias 0, norm scales 1 except the second norm of each residual
    block, which starts at the configuration's `init.residual_scale`. Jit
    it: one device call makes them."""
    m = cfg["model"]
    h, w, c_in = cfg["data"]["image"]
    nc = cfg["data"]["classes"]
    ks = iter(jax.random.split(key, 4096))
    if m["name"] == "lenet":
        c1, c2, f1, f2 = m["widths"]
        k = m["kernel"]
        flat = (h // 4) * (w // 4) * c2
        return {
            "conv1": _normal(next(ks), (k, k, c_in, c1), k * k * c_in),
            "conv2": _normal(next(ks), (k, k, c1, c2), k * k * c1),
            "fc1": _normal(next(ks), (flat, f1), flat),
            "b1": jnp.zeros((f1,), F32),
            "fc2": _normal(next(ks), (f1, f2), f1),
            "b2": jnp.zeros((f2,), F32),
            "fc3": _normal(next(ks), (f2, nc), f2),
            "b3": jnp.zeros((nc,), F32),
        }
    if m["name"] == "resnet":
        n = (m["depth"] - 2) // 6
        width = m["width"]
        rs = cfg.get("init", {}).get("residual_scale", 1.0)
        p = {"stem": _normal(next(ks), (3, 3, c_in, width), 9 * c_in)}
        blocks, c = [], width
        for c_out in (width, 2 * width, 4 * width):
            for _ in range(n):
                blk = {
                    "conv1": _normal(next(ks), (3, 3, c, c_out), 9 * c),
                    "conv2": _normal(next(ks), (3, 3, c_out, c_out),
                                     9 * c_out),
                    "scale1": jnp.ones((c_out,), F32),
                    "bias1": jnp.zeros((c_out,), F32),
                    "scale2": jnp.full((c_out,), rs, F32),
                    "bias2": jnp.zeros((c_out,), F32),
                }
                if c != c_out:
                    blk["proj"] = _normal(next(ks), (1, 1, c, c_out), c)
                blocks.append(blk)
                c = c_out
        p["blocks"] = blocks
        p["head"] = _normal(next(ks), (c, nc), c)
        p["head_b"] = jnp.zeros((nc,), F32)
        return p
    raise ValueError(f"no reference for model {m['name']!r}")


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

class Reference:
    """The rounds of one configuration, in float32 at the configuration's
    matmul precision or (the control) in bfloat16. `half_batch=True` is a
    planted fault: each client's gradient from the first half of its batch
    only."""

    def __init__(self, cfg: dict, eta: float, *, dtype=F32,
                 half_batch: bool = False):
        self.cfg = cfg
        self.eta = float(eta)
        self.dtype = dtype
        self.half_batch = half_batch
        fwd = FORWARD[cfg["model"]["name"]]
        prec = jax.lax.Precision[cfg["matmul_precision"].upper()]

        def loss(p, x, y):
            logits = fwd(p, x, prec)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            return (lse - gold).mean()

        self._grad = jax.jit(jax.value_and_grad(loss))
        self._thresholds = jax.jit(self._thresholds_impl)
        self._client = jax.jit(self._client_impl)
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        self._step = jax.jit(self._step_impl)
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

    def run(self, w0, rounds: list[dict], *, v0=None,
            blocks: list[int] | None = None) -> dict:
        """Follow `rounds` (each {"x": [C,B,...], "y": [C,B], "lam": [C]},
        the real clients only) from weights `w0` and broadcast gradient
        `v0` (zero before round 0). `blocks` splits the rounds into runs
        of the given lengths (default: one). Returns host copies: the
        per-round mean loss and, after each block, (start, length, the
        weights, the broadcast gradient)."""
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
            acc, ls = None, []
            for c in range(len(lam)):
                x = jnp.asarray(r["x"][c], dt)
                y = jnp.asarray(r["y"][c], jnp.int32)
                if self.half_batch:
                    x, y = x[: len(y) // 2], y[: len(y) // 2]
                loss, g = self._client(w, v, thr[c], x, y)
                ls.append(loss)
                acc = g if acc is None else self._add(acc, g)
            w, v = self._step(w, acc, jnp.asarray(len(lam), dt))
            losses.append(float(np.mean(np.asarray(
                [np.float32(l) for l in ls], np.float64))))
            if s + 1 in ends:
                start = snaps[-1][0] + snaps[-1][1] if snaps else 0
                snaps.append((start, s + 1 - start, _host(w), _host(v)))
        return {"losses": losses, "blocks": snaps}


def _host(tree) -> list[np.ndarray]:
    return [np.asarray(a, np.float64) for a in jax.tree.leaves(tree)]


def follow(ref: Reference, w0, rounds: list[dict], run: dict) -> dict:
    """`ref` over the blocks of `run` (the system, or a stand-in put in its
    place), each block from the state `run` held at its start: the weights
    and broadcast gradient it returned for the block before, or `w0` and
    zero for the first. So each block program is judged on its own input,
    and a difference does not carry over from one block to the next."""
    treedef = jax.tree.structure(w0)
    w, v = w0, None
    losses, snaps = [], []
    for start, n, w_end, v_end in run["blocks"]:
        r = ref.run(w, rounds[start:start + n], v0=v)
        losses += r["losses"]
        snaps.append((start, n) + tuple(r["blocks"][0][2:]))
        w = jax.tree.unflatten(treedef, [np.asarray(a, np.float32)
                                         for a in w_end])
        v = jax.tree.unflatten(treedef, [np.asarray(a, np.float32)
                                         for a in v_end])
    return {"losses": losses, "blocks": snaps}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def _leaf_norm_gaps(got: list, want: list, keep: list[bool]) -> list:
    """|norm(got leaf) - norm(want leaf)| over the larger of the want
    leaf's norm and the median want leaf's norm, for each kept leaf."""
    ng = [float(np.linalg.norm(a)) for a in got]
    nw = [float(np.linalg.norm(b)) for b in want]
    med = float(np.median(nw))
    return [abs(a - b) / max(b, med) if max(b, med) > 0 else 0.0
            for a, b, k in zip(ng, nw, keep) if k]


def compare(run: dict, ref: dict, w0: list) -> dict:
    """The numbers `correct` holds to limits, `run` (the program, or a
    stand-in in its place) against `ref`, the reference that `follow` led
    over the same blocks from the same start states:

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
    gn = [float(np.linalg.norm(g)) for g in ref["blocks"][0][3]]
    floor = 1e-3 * float(np.median(gn))
    keep = [g >= floor for g in gn]
    start_w = w0
    by_block = []
    for (s, n, w_run, v_run), (_, _, w_ref, v_ref) in zip(run["blocks"],
                                                          ref["blocks"]):
        g = max(_leaf_norm_gaps(v_run, v_ref, [True] * len(w0)))
        c = max(_leaf_norm_gaps([a - b for a, b in zip(w_run, start_w)],
                                [a - b for a, b in zip(w_ref, start_w)],
                                keep))
        by_block.append((s, n, float(rel[s]), g, c))
        start_w = w_run
    return {"loss_gap": max(b[2] for b in by_block),
            "grad_gap": by_block[0][3],
            "change_gap": max(b[4] for b in by_block),
            "leaves_left_out": int(len(keep) - sum(keep)),
            "by_block": by_block}
