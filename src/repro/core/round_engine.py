"""Device-resident federated round engine (paper Sec. II-A, eqs. 2-7).

One jitted ``round_step`` executes an entire FedSGD round on device over the
packed ``[R, 128]`` parameter buffer (core/packing.py):

  1. importance Q = (w * v)^2 (eq. 4) over the packed buffer;
  2. the global pruning threshold — the k-th smallest prunable importance,
     k = floor(lambda * M_prunable) — via an on-device exponent-histogram +
     binary search over fp32 bit patterns (`kth_smallest_threshold`; no
     sort, no host `np.partition`, no device->host parameter transfer);
  3. fused importance+keep-mask Pallas launch (kernels/pruning_mask.py) —
     one kernel for the whole model instead of one per leaf; when every
     selected client shares lambda the threshold and mask are computed once
     (no per-client recompute), otherwise the batched kernel emits all
     per-client masks from a single read of (w, v);
  4. per-client mini-batch gradients on the pruned model (eq. 5) over the
     stacked client batches — gradients are taken directly with respect to
     the packed buffer (unpacking is differentiable) and masked on device
     (pruned coordinates are never "uploaded");
  5. fused weighted aggregate+update launch: combine the stacked gradients
     with per-client 0/1 weights (eq. 6) and take the FedSGD step (eq. 7)
     in one pass; the mean gradient doubles as the next round's broadcast v.

Shape stability (no retrace storms)
-----------------------------------
Schedules from `solve_p1` select a different client count C every round,
and a naive jit retraces `round_step` per distinct C. The engine instead
pads the client axis to a *bucket* size — ``shards * next_pow2(ceil(C /
shards))`` — and threads a per-client validity weight ``cw[C_pad]`` (1 for
real clients, 0 for padding) through the weighted aggregate, so a whole
training run compiles at most ``log2(C_max)+1`` traces per lambda family
(`n_traces` counts them; tests assert the bound). Padding clients replicate
the last real client's batch and are skipped in the aggregate via
``where(cw > 0, acc + cw*g, acc)`` — they can never perturb the update,
not even by a NaN.

Ragged clients (fewer samples than the batch size) are handled one level
down with the same trick: the trainer pads the *sample* axis and passes
per-sample 0/1 weights consumed by a weighted loss (`sample_weights`), so
stragglers stay on the packed path (see core/federated.py — the weighted
mean with 0/1 weights is the plain mean over the real samples).

Multi-device sharding
---------------------
With more than one local device (or ``REPRO_ROUND_SHARDS``), the client
axis of steps 4-5 is sharded over the ``data`` axis of a host mesh
(`launch/mesh.make_host_mesh`, model=1) via `shard_map`: parameters, the
global gradient, and the mask are replicated; each shard scans its local
clients and reduces a weighted *partial sum* of masked gradients; a single
in-graph `psum` per round combines the partials, feeding the fused FedSGD
update computed redundantly (replicated) on every device. Parameters stay
device-resident and replicated round over round — one collective per
round, nothing syncs to host. CPU tests force a multi-device host with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (scripts/test.sh
sharded leg).

Numerics
--------
The single-device bucketed path reproduces the reference trainer
**bit-for-bit** on fp32 models (tests/test_packing.py): with 0/1 weights
the weighted aggregate accumulates the real clients in reference order
(`acc + 1.0*g` is exact) and the update's `eta*g` product is fenced from
FMA contraction (`kernels/ops.rounded_step`). The sharded path reassociates
only the cross-shard reduction (per-shard partials + psum), so it is
trajectory-equivalent within ~1 ulp per round, not bit-identical. Only the
integers k = floor(lambda * M_prunable) and the scalar 1/C are computed on
host (O(1) arithmetic on the schedule); parameters never leave the device.

With ``donate=True`` (used by `FederatedTrainer`, which owns the buffers)
the parameter / global-gradient buffers are donated to the step on
accelerator backends and updated in place round over round; the default
keeps ``round_step`` purely functional.

Multi-round blocks (``block_step``)
-----------------------------------
``round_step`` still pays one dispatch + one stacked-batch host->device
upload per round. ``block_step`` removes both: client datasets live on
device in a `ClientStore` (core/client_store.py), batches are gathered on
device from host-drawn index arrays ``[K, C, B]`` (the indices come from
the trainer's existing numpy RNG, so the batch sequence — and bit-for-bit
parity — is preserved), the schedule is stacked into ``[K]``-leading
arrays (client ids, ks, client weights, 1/C), and a `lax.scan` over the
round axis runs K rounds in ONE jitted dispatch, carrying (w, v). Per-round
losses come back as a ``[K, C_b]`` device array that drops into the
trainer's lazy-materialization path. K is bucketed the same power-of-two
way as the client axis (the trainer decomposes arbitrary block lengths into
pow2 chunks instead of padding — padded rounds would cost full gradient
FLOPs), so AO-driven varying (C, K, lambda) schedules stay within
``(log2(C_max)+1) * (log2(K_max)+1)`` traces per lambda family
(`n_traces` / `buckets_used` / `k_buckets_used` account for it). On a mesh
each scan step wraps the same shard_map region the per-round sharded path
uses — still exactly one `psum` per round, with the store replicated so
every device gathers from local memory.
"""
from __future__ import annotations

import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.packing import ParamPack
from repro.kernels import ops

PyTree = Any


def kth_smallest_threshold(q: jnp.ndarray, prunable: jnp.ndarray,
                           k: jnp.ndarray, *,
                           coarse: str | None = None,
                           hist_impl: str = "auto") -> jnp.ndarray:
    """Threshold such that exactly k prunable entries are strictly below it.

    Matches `pruning.global_threshold` bit-for-bit: the k-th smallest
    prunable importance, nudged one ulp up (`nextafter`), computed entirely
    on device. `k` may be a scalar or a [C] vector of per-client counts
    (one pass amortized across clients).

    Exact selection without a sort: importance scores are non-negative, and
    for non-negative IEEE-754 floats the value order equals the integer
    order of the bit patterns, so the k-th smallest is found by bisection
    over bit patterns with one masked count per step (O(n) per pass, no
    O(n log n) sort).

    `coarse="histogram"` prepends a 256-bin histogram over the *exponent
    byte* (``bits >> 23``; the sign bit is 0): one scan whose cumulative
    counts pin bits 30..23 of the answer, leaving a 23-step mantissa
    bisection — 24 data passes instead of 31. `"bisect"` is the plain
    31-step search. The default (None = auto) picks per backend: the
    histogram's scatter-add lowers to a fast on-chip accumulation on TPU
    but to a serial ~130 ns/element scatter on XLA:CPU — 3-7x slower than
    the seven count passes it saves (measured, see ROADMAP) — so CPU keeps
    the pure bisection. Both modes are exact and tested against the host
    oracle.

    `hist_impl` picks how the histogram pass is computed when
    ``coarse="histogram"``: "pallas" uses the tiled exponent-histogram
    kernel (per-lane bin counts, no scatter-add; requires a packed
    [R, 128*k] layout and raises on any other), "xla" the
    scatter-add mirror, "auto" pallas on TPU and xla elsewhere
    (`kernels/ops.packed_exponent_histogram`).
    """
    if coarse is None:
        coarse = "histogram" if jax.default_backend() == "tpu" else "bisect"
    if coarse not in ("histogram", "bisect"):
        raise ValueError(f"unknown coarse mode {coarse!r}")
    bits = jax.lax.bitcast_convert_type(q.reshape(-1), jnp.int32)
    valid = prunable.reshape(-1) > 0
    k = jnp.asarray(k, jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2   # (lo+hi)//2 overflows int32 for q >= 2.0
        below = jnp.where(valid, bits[..., :] <= mid[..., None], False)
        ge = below.sum(axis=-1) >= k
        return jnp.where(ge, lo, mid + 1), jnp.where(ge, mid, hi)

    if coarse == "histogram":
        # pass 1/24: exponent-byte histogram; cum[b] = #valid, top byte <= b.
        # The k-th smallest lives in the first bin whose cumulative count
        # reaches k, which pins bits 30..23 of the answer in one data scan.
        hist = ops.packed_exponent_histogram(q, prunable, impl=hist_impl)
        cum = jnp.cumsum(hist)
        # clamp: k beyond the valid count would return 256 and overflow the
        # shift; bin 255 then degrades to the same max-element answer the
        # pure bisection gives
        top = jnp.minimum(jnp.searchsorted(cum, k, side="left"),
                          255).astype(jnp.int32)
        lo0 = top << 23
        hi0 = lo0 | jnp.int32((1 << 23) - 1)
        steps = 23
    else:
        lo0 = jnp.zeros(k.shape, jnp.int32)
        hi0 = jnp.full(k.shape, jnp.int32(2**31 - 1))
        steps = 31
    lo, _ = jax.lax.fori_loop(0, steps, body, (lo0, hi0))
    kth = jax.lax.bitcast_convert_type(lo, jnp.float32)
    return jnp.where(k > 0, jnp.nextafter(kth, jnp.inf),
                     -jnp.asarray(jnp.inf, jnp.float32))


def bucket_capacity(n_clients: int, *, shards: int = 1, bucket: bool = True,
                    max_clients: int | None = None) -> int:
    """Padded client-axis size for a round selecting `n_clients` — the one
    bucketing formula, shared by `RoundEngine.bucket_size` and the eager
    reference robust reducer (core/federated.py pads its client stack to
    the same capacity so packed-vs-reference stays bitwise comparable on
    rank-based aggregators)."""
    per = -(-int(n_clients) // shards)
    if bucket:
        p2 = 1 << (per - 1).bit_length()
        if max_clients is not None:
            p2 = min(p2, max(per, -(-int(max_clients) // shards)))
        per = p2
    return per * shards


def resolve_shards(shards: int | None) -> int:
    """Data-shard count for the client axis: explicit arg, then the
    REPRO_ROUND_SHARDS env override (CPU tests under
    --xla_force_host_platform_device_count), then every local device.
    Public so callers that must know whether an engine will shard_map
    (e.g. the sweep service's collective-safety gate) resolve it the
    same way the engine will."""
    if shards is not None:
        return max(1, int(shards))         # explicit: let mesh build fail loud
    env = os.environ.get("REPRO_ROUND_SHARDS")
    if env:
        return min(max(1, int(env)), len(jax.devices()))
    return len(jax.devices())


_resolve_shards = resolve_shards


class RoundEngine:
    """Jitted packed-buffer FedSGD round (selection -> pruning -> aggregate).

    Parameters
    ----------
    loss_fn : loss(params_pytree, x, y) -> scalar; the engine differentiates
        it through `pack.unpack`, so gradients live on the packed buffer.
    pack : ParamPack describing the model layout.
    eta : FedSGD learning rate (compile-time constant).
    weighted_loss_fn : optional loss(params, x, y, sample_weights) -> scalar
        consuming per-sample 0/1 weights — required for ragged client
        batches to stay on the packed path (models.make_loss_fn attaches
        one as ``loss.weighted``). Without it sample weights are ignored.
    shards : client-axis shard count (None = REPRO_ROUND_SHARDS env, else
        all local devices; 1 disables sharding).
    bucket : pad the client axis to power-of-two per-shard buckets so
        varying selection sizes reuse compiles (True; False pads only to a
        multiple of the shard count).
    max_clients : total client population, if known (FederatedTrainer
        passes len(clients)). Caps the bucket ladder so full participation
        never pads past the population (e.g. C=20 of 20 buckets to 20, not
        32 — padding clients cost real gradient FLOPs).
    aggregator : optional core/aggregators.Aggregator — a Byzantine-robust
        reducer slotted in place of the weighted mean behind the same
        FMA-fenced update tail. None (the default) keeps the builtin mean
        path with byte-identical traces. A construction-time constant,
        like eta: it changes every round graph, so swapping it means a new
        engine (FederatedTrainer / Experiment.build handle pooling).
    """

    def __init__(self, loss_fn: Callable, pack: ParamPack, *, eta: float,
                 client_axis: str = "auto", kernel_impl: str = "auto",
                 donate: bool = False, weighted_loss_fn: Callable | None = None,
                 shards: int | None = None, bucket: bool = True,
                 max_clients: int | None = None, aggregator=None,
                 local_scheme=None):
        if client_axis not in ("auto", "unroll", "scan", "vmap"):
            raise ValueError(f"unknown client_axis {client_axis!r}")
        self.pack = pack
        self.eta = float(eta)
        self.client_axis = client_axis
        self.kernel_impl = kernel_impl
        self.bucket = bool(bucket)
        self.max_clients = int(max_clients) if max_clients else None
        self.aggregator = aggregator
        # local-update scheme (core/local.py): None = the single-gradient
        # FedSGD body (today's paths, byte-identical traces). A LocalScheme
        # swaps the client body for an inner lax.scan over E local steps —
        # a construction-time constant like eta/aggregator, so the step
        # axis pads to the STATIC pow2 bucket `steps_bucket` with a static
        # 0/1 step-validity vector (padded steps are exact no-ops and
        # consume no RNG), keeping the trace-family ladder bounded.
        self.local_scheme = local_scheme
        if local_scheme is not None:
            eb = local_scheme.steps_bucket
            self._sv = jnp.asarray(
                (np.arange(eb) < local_scheme.steps).astype(np.float32))
        else:
            self._sv = None
        self.shards = resolve_shards(shards)
        self.prunable = jnp.asarray(pack.prunable_mask())
        # compile accounting: one increment per (re)trace of a step impl —
        # bucketing bounds this by the number of distinct bucket sizes per
        # lambda family regardless of how C varies round to round
        self.n_traces = 0
        self.buckets_used: set[int] = set()
        self.k_buckets_used: set[int] = set()
        # device-array caches for the per-round / per-block auxiliary
        # inputs: all-ones sample weights by shape (block keys tagged
        # "blk" — same shape family, different rank) and the per-round
        # path's 0/1 client weights by (bucket, selected count). Both key
        # sets are bounded by the bucket ladder; block client weights are
        # instead derived on device from the [K] counts array (a cache
        # keyed by the full counts tuple would almost never hit under an
        # AO schedule and would grow without bound).
        self._sw_cache: dict[tuple, jnp.ndarray] = {}
        self._cw_cache: dict[tuple, jnp.ndarray] = {}

        if self.shards > 1:
            # client axis sharded over the data axis of a host mesh; layered
            # under launch/ so importing core never touches device state
            from repro.launch.mesh import make_host_mesh
            self.mesh = make_host_mesh(model=1, data=self.shards)
        else:
            self.mesh = None

        if weighted_loss_fn is not None:
            def packed_loss(wp, x, y, sw):
                return weighted_loss_fn(pack.unpack(wp), x, y, sw)
        else:
            def packed_loss(wp, x, y, sw):
                return loss_fn(pack.unpack(wp), x, y)

        self._value_and_grad = jax.value_and_grad(packed_loss)
        # donate=True lets XLA update the parameter / global-gradient
        # buffers in place on accelerators, but the caller must then treat
        # the passed-in (w, v) as consumed — reading them after round_step
        # raises a deleted-buffer error. Only enable it for owners of the
        # buffers (FederatedTrainer does); the default keeps round_step
        # purely functional. CPU does not implement donation, so skip it
        # there to avoid per-compile warnings.
        donate_args = ((0, 1) if donate
                       and jax.default_backend() in ("tpu", "gpu") else ())
        self._donate_args = donate_args
        # Fault-injection entry points (dropout rides the plain entries via
        # host-folded client weights; corruption and block fault masks need
        # extra traced operands) are built LAZILY per (kind, noisy) so
        # fault-free runs never pay their jit scaffolding.
        self._fault_steps: dict[tuple, Callable] = {}
        # survivor count of the most recent round_step ([] scalar) or
        # block_step ([K]) — weighted clients whose summed gradient passed
        # the isfinite guard; the trainer materializes it lazily alongside
        # the losses to drive the n_quarantined / n_skipped_rounds counters
        self.last_n_ok = None
        # robust-aggregation diagnostic of the most recent dispatch
        # ([] scalar / [K] int32; constant 0 on the mean path) — clients
        # trimmed / clipped / excluded, same lazy materialization contract
        self.last_agg_stat = None
        # FedDyn only: the updated per-client correction state [C, R, L]
        # of the most recent dispatch — stays on device; the trainer (the
        # buffer's owner) adopts it after each step
        self.last_h = None
        if self.mesh is None:
            round_shared, round_multi = self._round_shared, self._round_multi
            self._step_shared = jax.jit(self._shared_impl,
                                        donate_argnums=donate_args)
            self._step_multi = jax.jit(self._multi_impl,
                                       donate_argnums=donate_args)
        else:
            round_shared = self._round_shared_sharded
            round_multi = self._round_multi_sharded
            self._step_shared = jax.jit(self._shared_sharded_impl,
                                        donate_argnums=donate_args)
            self._step_multi = jax.jit(self._multi_sharded_impl,
                                       donate_argnums=donate_args)
        # block dispatches wrap the SAME per-round bodies in the scan
        # scaffold, so block and per-round modes can never diverge
        self._blk_shared = jax.jit(self._make_block_impl(round_shared),
                                   donate_argnums=donate_args)
        self._blk_multi = jax.jit(self._make_block_impl(round_multi),
                                  donate_argnums=donate_args)

        # Noisy-aggregation variants: separate jit entry points (the noise
        # operand changes the traced graph), wrapping the same round
        # bodies, so the noiseless traces stay byte-identical to before.
        def _noisy_step(fn):
            def impl(w, v, xs, ys, sw, cw, inv, k, noise):
                self.n_traces += 1
                return fn(w, v, xs, ys, sw, cw, inv, k, noise=noise)
            return impl

        self._step_shared_nz = jax.jit(_noisy_step(round_shared),
                                       donate_argnums=donate_args)
        self._step_multi_nz = jax.jit(_noisy_step(round_multi),
                                      donate_argnums=donate_args)
        self._blk_shared_nz = jax.jit(
            self._make_block_impl(round_shared, noisy=True),
            donate_argnums=donate_args)
        self._blk_multi_nz = jax.jit(
            self._make_block_impl(round_multi, noisy=True),
            donate_argnums=donate_args)

    # -- jitted bodies ------------------------------------------------------

    @property
    def _axis(self) -> str:
        # "auto" = scan: O(1) program size in the client count, and it
        # empirically beats the unrolled loop once the whole round is fused
        # into one program, with the same bit-for-bit results.
        return "scan" if self.client_axis == "auto" else self.client_axis

    def _grads_shared(self, pruned, mask, xs, ys, sw):
        """Shared-lambda client axis: every client sees the same pruned
        buffer / mask [R, L] (never materialized per client). sw [C, B] are
        per-sample weights for the weighted loss. Returns (losses [C],
        masked grads [C, R, L])."""
        n_clients = xs.shape[0]
        ax = self._axis
        if ax == "unroll":
            out = [self._value_and_grad(pruned, xs[c], ys[c], sw[c])
                   for c in range(n_clients)]
            return (jnp.stack([l for l, _ in out]),
                    jnp.stack([g * mask for _, g in out]))
        if ax == "vmap":
            losses, grads = jax.vmap(
                lambda x, y, s: self._value_and_grad(pruned, x, y, s))(
                    xs, ys, sw)
            return losses, grads * mask

        def body(carry, inp):
            x, y, s = inp
            loss, g = self._value_and_grad(pruned, x, y, s)
            return carry, (loss, g * mask)

        _, (losses, grads) = jax.lax.scan(body, 0.0, (xs, ys, sw))
        return losses, grads

    def _grads_multi(self, w, masks, xs, ys, sw):
        """Per-client-lambda client axis: masks are [C, R, L]. Each client's
        pruned buffer w * masks[c] is formed inside its own step so the
        [C, R, L] stack of pruned models is never materialized."""
        n_clients = xs.shape[0]
        ax = self._axis
        if ax == "unroll":
            out = [self._value_and_grad(w * masks[c], xs[c], ys[c], sw[c])
                   for c in range(n_clients)]
            return (jnp.stack([l for l, _ in out]),
                    jnp.stack([g * masks[c] for c, (_, g) in enumerate(out)]))
        if ax == "vmap":
            losses, grads = jax.vmap(
                lambda m, x, y, s: self._value_and_grad(w * m, x, y, s))(
                    masks, xs, ys, sw)
            return losses, grads * masks

        def body(carry, inp):
            m, x, y, s = inp
            loss, g = self._value_and_grad(w * m, x, y, s)
            return carry, (loss, g * m)

        _, (losses, grads) = jax.lax.scan(body, 0.0, (masks, xs, ys, sw))
        return losses, grads

    # -- local-update scheme bodies (DESIGN.md §14) -------------------------
    #
    # With a LocalScheme the per-client body becomes an inner lax.scan over
    # the pow2-bucketed step axis: each step takes a masked gradient at the
    # CURRENT iterate, folds in the scheme's regularizer (FMA-fenced, so
    # the eager reference's per-op rounding is reproduced bit for bit),
    # accumulates the update direction into the upload, and steps the local
    # iterate. Padded steps (t >= E) are gated off by the static 0/1
    # validity vector — exact no-ops on (u, acc) via `where`, and they
    # replicate the last real step's batch so they consume no RNG.

    def _local_client(self, u0, mask, xs, ys, sw, hm=None):
        """One client's local trajectory. xs: [E_b, B, ...]; u0 the pruned
        start w*mask; hm the client's masked FedDyn correction state (or
        None). Returns (loss at step 0, upload = sum of step directions,
        FedDyn state delta or None).

        The upload accumulator starts at zeros, so every scheme's upload is
        `0 + d_0 + ...` — the add normalizes -0.0 direction coordinates to
        +0.0, and the eager reference accumulates from zeros the same way.
        """
        scheme = self.local_scheme
        coeff = scheme.coeff

        def body(carry, inp):
            u, acc = carry
            x, y, s, valid = inp
            loss, g = self._value_and_grad(u, x, y, s)
            g = g * mask
            if scheme.name == "fedavg":
                d = g
            else:
                d = ops.packed_local_delta(g, u, u0, coeff, hm=hm)
            acc = jnp.where(valid > 0, acc + d, acc)
            u = jnp.where(valid > 0,
                          u - ops.rounded_step(self.eta, d), u)
            return (u, acc), loss

        (u_e, upload), losses = jax.lax.scan(
            body, (u0, jnp.zeros_like(u0)), (xs, ys, sw, self._sv))
        if scheme.stateful:
            # FedDyn server-side state delta: h_i <- h_i - alpha*(u_E - u0),
            # the product fenced exactly like the per-step regularizer
            hd = ops.rounded_step(jnp.float32(scheme.alpha), u_e - u0)
            return losses[0], upload, hd
        return losses[0], upload, None

    def _locals_shared(self, pruned, mask, xs, ys, sw, hcs=None):
        """Shared-lambda local-step client axis (xs: [C, E_b, B, ...]).
        Returns (losses [C], uploads [C, R, L], hds [C, R, L] | None).
        hcs: per-selected-client FedDyn state [C, R, L] (or None); the
        mask multiply below is exact (mask is 0/1)."""
        hms = None if hcs is None else hcs * mask
        n_clients = xs.shape[0]
        ax = self._axis
        if ax == "unroll":
            out = [self._local_client(pruned, mask, xs[c], ys[c], sw[c],
                                      None if hms is None else hms[c])
                   for c in range(n_clients)]
            return tuple(None if out[0][i] is None
                         else jnp.stack([o[i] for o in out])
                         for i in range(3))
        if ax == "vmap":
            if hms is None:
                return jax.vmap(
                    lambda x, y, s: self._local_client(
                        pruned, mask, x, y, s))(xs, ys, sw)
            return jax.vmap(
                lambda x, y, s, hm: self._local_client(
                    pruned, mask, x, y, s, hm))(xs, ys, sw, hms)

        def body(carry, inp):
            x, y, s, hm = inp
            return carry, self._local_client(pruned, mask, x, y, s, hm)

        _, out = jax.lax.scan(body, 0.0, (xs, ys, sw, hms))
        return out

    def _locals_multi(self, w, masks, xs, ys, sw, hcs=None):
        """Per-client-lambda local-step client axis: each client's pruned
        start w*masks[c] is formed inside its own step (the [C, R, L] stack
        of pruned models is never materialized)."""
        hms = None if hcs is None else hcs * masks
        n_clients = xs.shape[0]
        ax = self._axis
        if ax == "unroll":
            out = [self._local_client(w * masks[c], masks[c], xs[c], ys[c],
                                      sw[c],
                                      None if hms is None else hms[c])
                   for c in range(n_clients)]
            return tuple(None if out[0][i] is None
                         else jnp.stack([o[i] for o in out])
                         for i in range(3))
        if ax == "vmap":
            if hms is None:
                return jax.vmap(
                    lambda m, x, y, s: self._local_client(
                        w * m, m, x, y, s))(masks, xs, ys, sw)
            return jax.vmap(
                lambda m, x, y, s, hm: self._local_client(
                    w * m, m, x, y, s, hm))(masks, xs, ys, sw, hms)

        def body(carry, inp):
            m, x, y, s, hm = inp
            return carry, self._local_client(w * m, m, x, y, s, hm)

        _, out = jax.lax.scan(body, 0.0, (masks, xs, ys, sw, hms))
        return out

    def _client_grads_shared(self, pruned, mask, xs, ys, sw):
        """Client body dispatch for the STATELESS schemes: the plain
        single-gradient body when no LocalScheme is set (today's traces,
        byte-identical), otherwise the local-step body with the FedDyn
        state path unused. FedDyn routes through the dyn round bodies
        instead (extra h/cid operands)."""
        with jax.named_scope("round.clients"):
            if self.local_scheme is None:
                return self._grads_shared(pruned, mask, xs, ys, sw)
            losses, uploads, _ = self._locals_shared(pruned, mask, xs, ys,
                                                     sw)
        return losses, uploads

    def _client_grads_multi(self, w, masks, xs, ys, sw):
        with jax.named_scope("round.clients"):
            if self.local_scheme is None:
                return self._grads_multi(w, masks, xs, ys, sw)
            losses, uploads, _ = self._locals_multi(w, masks, xs, ys, sw)
        return losses, uploads

    def _aggregate_update(self, w, v, grads, cw, inv, noise, cf=None,
                          poison=None):
        """Weighted aggregate + FedSGD tail, with graceful degradation and
        an optional noisy aggregation channel.

        `cf` (optional [C] per-client corruption factors, 1.0 = clean)
        scales each client's masked gradient before aggregation — the
        corrupt-upload fault axis (core/faults.py); a `1.0 * g` multiply
        is exact, so clean clients are bitwise unaffected. `poison`
        (optional [C, R, L] additive upload poison, zero = clean) is added
        after scaling — the GaussianPoison attack; note a clean client's
        `g + 0.0` normalizes -0.0 coordinates to +0.0, which the eager
        reference applies identically, so parity holds.

        The always-on non-finite guard (ops.packed_client_quarantine) then
        zeroes the weight of any client whose summed gradient went
        non-finite and renormalizes the mean over the survivors; with
        every upload finite it passes (cw, inv) through value-identically,
        so the default path stays bit-for-bit (tests/test_golden.py is the
        sensor). When NO client survives, `alive` selects the carried
        (w, v) — the round's update is skipped entirely, params unchanged.

        With a robust `aggregator` the quarantined weights feed
        `Aggregator.reduce` over the full stack instead of the weighted
        mean: the reducer emits a survivor-normalized aggregate plus its
        diagnostic count, applied through the same FMA-fenced tail with
        inv=1.0 (`ghat * 1.0` is exact, so the fence sequence is the
        bit-parity anchor on this path too).

        When `noise` (packed [R, L], zero on padding lanes) is traced in,
        the update consumes mean(g) + noise — the server never sees the
        clean aggregate (wireless/channel.py). The noiseless path keeps
        the fused kernel (the guard only rewrites its weight operands);
        the noisy path goes through the XLA mirror so the fenced mean
        product is materialized before the add (bit-parity with the eager
        reference sequence)."""
        with jax.named_scope("round.aggregate"):
            if cf is not None:
                grads = grads * cf.astype(jnp.float32)[:, None, None]
            if poison is not None:
                grads = grads + poison.astype(jnp.float32)
            cw_eff, inv_eff, n_ok, alive = ops.packed_client_quarantine(
                grads, cw, inv)
        if self.aggregator is not None:
            with jax.named_scope("round.aggregate"):
                ghat, ast = self.aggregator.reduce(grads, cw_eff)
            with jax.named_scope("round.update"):
                w2, g, step = ops.packed_apply_mean_update(
                    w, ghat, jnp.float32(1.0), self.eta, noise=noise)
        elif noise is None:
            ast = jnp.int32(0)
            # one fused kernel sums, averages and steps: its ops carry
            # the aggregate's scope
            with jax.named_scope("round.aggregate"):
                w2, g, step = ops.packed_fedsgd_update_weighted(
                    w, grads, cw_eff, inv_eff, self.eta,
                    impl=self.kernel_impl)
        else:
            ast = jnp.int32(0)
            with jax.named_scope("round.aggregate"):
                gsum = ops.packed_weighted_grad_sum(grads, cw_eff)
            with jax.named_scope("round.update"):
                w2, g, step = ops.packed_apply_mean_update(
                    w, gsum, inv_eff, self.eta, noise=noise)
        # all clients faulted: carry params and the broadcast v unchanged
        # (the reference server_step's empty-grads early return)
        with jax.named_scope("round.update"):
            w2 = jnp.where(alive, w2, w)
            g = jnp.where(alive, g, v)
        # cw_eff rides along for the stateful schemes: FedDyn only updates
        # the correction state of clients whose (post-fault) upload arrived
        # finite — exactly the quarantine's surviving weights
        return w2, g, step, n_ok, ast, cw_eff

    def _threshold_mask(self, w, v, k):
        """Shared-lambda prologue: the round's threshold and keep-mask."""
        with jax.named_scope("round.threshold"):
            q = (w * v) ** 2
            thr = kth_smallest_threshold(q, self.prunable, k)
        with jax.named_scope("round.masks"):
            _, mask = ops.packed_importance_mask(w, v, self.prunable, thr,
                                                 impl=self.kernel_impl)
        return thr, mask

    def _thresholds(self, w, v, ks):
        """Per-client-lambda prologue: one threshold per client, [C]."""
        with jax.named_scope("round.threshold"):
            return kth_smallest_threshold((w * v) ** 2, self.prunable, ks)

    def _client_masks(self, w, v, prunable, thr):
        """Per-client-lambda keep-masks [C, R, L], one threshold each."""
        with jax.named_scope("round.masks"):
            return ops.packed_importance_masks(w, v, prunable, thr,
                                               impl=self.kernel_impl)[1]

    def _replicated(self, fn, *args):
        """``fn(*args)``, which a mesh runs on every device's full copy of
        the replicated operands. XLA cannot partition a Pallas kernel, so on
        a mesh each replicated stretch of a round that may hold one — the
        threshold and mask prologue, the aggregate tails — runs in a
        shard_map of its own (same ops, so the same bits)."""
        if self.mesh is None:
            return fn(*args)
        return shard_map(fn, mesh=self.mesh, in_specs=(P(),) * len(args),
                         out_specs=P(), check_vma=False)(*args)

    def _round_shared(self, w, v, xs, ys, sw, cw, inv, k, noise=None,
                      cf=None, poison=None):
        """One shared-lambda round, given device batches — the single body
        traced by both the per-round jit and the block scan, so the two
        paths compile the identical round math (bit-for-bit contract)."""
        thr, mask = self._threshold_mask(w, v, k)
        pruned = w * mask
        losses, grads = self._client_grads_shared(pruned, mask, xs, ys, sw)
        # step stays an output of the jitted graph: see the weighted update
        w2, g, step, n_ok, ast, _ = self._aggregate_update(
            w, v, grads, cw, inv, noise, cf, poison)
        return w2, g, losses, thr, step, n_ok, ast

    def _round_multi(self, w, v, xs, ys, sw, cw, inv, ks, noise=None,
                     cf=None, poison=None):
        """One per-client-lambda round (see _round_shared)."""
        thr = self._thresholds(w, v, ks)                       # [C]
        masks = self._client_masks(w, v, self.prunable, thr)
        losses, grads = self._client_grads_multi(w, masks, xs, ys, sw)
        w2, g, step, n_ok, ast, _ = self._aggregate_update(
            w, v, grads, cw, inv, noise, cf, poison)
        return w2, g, losses, thr, step, n_ok, ast

    def _shared_impl(self, w, v, xs, ys, sw, cw, inv, k):
        self.n_traces += 1
        return self._round_shared(w, v, xs, ys, sw, cw, inv, k)

    def _multi_impl(self, w, v, xs, ys, sw, cw, inv, ks):
        self.n_traces += 1
        return self._round_multi(w, v, xs, ys, sw, cw, inv, ks)

    # -- FedDyn round bodies: per-client correction state -------------------
    #
    # FedDyn threads two extra traced operands through the round: the full
    # per-client state h [C_all, R, L] (or a cohort slab on the streamed
    # path) and the selected ids cid [C_b] indexing its rows. The state of
    # the selected clients is gathered (exact copy), its masked value joins
    # each local step's direction, and after the aggregate the server
    # scatter-updates h_i <- h_i - alpha*(u_E - u0) for every client whose
    # upload arrived finite (the quarantine's cw_eff). Padding clients
    # replicate the last real id with a scatter contribution of exact +0.0
    # — a bitwise no-op, because h rows can never hold -0.0 (they start at
    # +0.0 and x + (-hd) only yields -0.0 from a -0.0 operand).

    def _h_scatter(self, h, cid, hds, cw_eff):
        upd = jnp.where(cw_eff[:, None, None] > 0, -hds, jnp.float32(0.0))
        return h.at[cid].add(upd)

    def _round_shared_dyn(self, w, v, xs, ys, sw, cw, inv, k, h, cid,
                          noise=None, cf=None, poison=None):
        thr, mask = self._threshold_mask(w, v, k)
        pruned = w * mask
        with jax.named_scope("round.clients"):
            losses, uploads, hds = self._locals_shared(pruned, mask, xs, ys,
                                                       sw, h[cid])
        w2, g, step, n_ok, ast, cw_eff = self._aggregate_update(
            w, v, uploads, cw, inv, noise, cf, poison)
        h2 = self._h_scatter(h, cid, hds, cw_eff)
        return w2, g, losses, thr, step, n_ok, ast, h2

    def _round_multi_dyn(self, w, v, xs, ys, sw, cw, inv, ks, h, cid,
                         noise=None, cf=None, poison=None):
        thr = self._thresholds(w, v, ks)                       # [C]
        masks = self._client_masks(w, v, self.prunable, thr)
        with jax.named_scope("round.clients"):
            losses, uploads, hds = self._locals_multi(w, masks, xs, ys, sw,
                                                      h[cid])
        w2, g, step, n_ok, ast, cw_eff = self._aggregate_update(
            w, v, uploads, cw, inv, noise, cf, poison)
        h2 = self._h_scatter(h, cid, hds, cw_eff)
        return w2, g, losses, thr, step, n_ok, ast, h2

    # Mesh variants: state rows are gathered OUTSIDE the shard_map region
    # (h is replicated; the gather is exact and cheap) and enter sharded
    # along the client axis; inside, each shard runs its local clients'
    # step scans and the round's single collective becomes ONE tupled
    # all_gather of the raw (uploads, state deltas) stacks. The whole
    # aggregate tail — faults, quarantine, mean/robust reduce, update, h
    # scatter — then runs replicated on the gathered full-client stacks,
    # which makes the sharded FedDyn round BITWISE identical to the
    # unsharded one (same inputs, same ops — stronger than the mean path's
    # psum reassociation, same construction as the robust path).

    def _dyn_sharded_tail(self, w, v, ups, hds, cw, inv, h, cid, noise, cf,
                          poison):
        w2, g, step, n_ok, ast, cw_eff = self._aggregate_update(
            w, v, ups, cw, inv, noise, cf, poison)
        h2 = self._h_scatter(h, cid, hds, cw_eff)
        return w2, g, step, n_ok, ast, h2

    def _round_shared_dyn_sharded(self, w, v, xs, ys, sw, cw, inv, k, h,
                                  cid, noise=None, cf=None, poison=None):
        thr, mask = self._replicated(self._threshold_mask, w, v, k)
        pruned = w * mask
        hc = h[cid]

        def body(pruned_, mask_, xs_, ys_, sw_, hc_):
            with jax.named_scope("round.clients"):
                losses, ups, hds = self._locals_shared(pruned_, mask_, xs_,
                                                       ys_, sw_, hc_)
            ga, hda = jax.lax.all_gather((ups, hds), "data", axis=0,
                                         tiled=True)
            return losses, ga, hda

        # gather-then-reduce is replicated by construction but invisible to
        # the varying-axes check (see _round_shared_sharded)
        losses, ups, hds = shard_map(
            body, mesh=self.mesh,
            in_specs=(P(), P(), P("data"), P("data"), P("data"), P("data")),
            out_specs=(P("data"), P(), P()), check_vma=False)(
                pruned, mask, xs, ys, sw, hc)
        w2, g, step, n_ok, ast, h2 = self._replicated(
            self._dyn_sharded_tail, w, v, ups, hds, cw, inv, h, cid, noise,
            cf, poison)
        return w2, g, losses, thr, step, n_ok, ast, h2

    def _round_multi_dyn_sharded(self, w, v, xs, ys, sw, cw, inv, ks, h,
                                 cid, noise=None, cf=None, poison=None):
        thr = self._replicated(self._thresholds, w, v, ks)     # [C]
        hc = h[cid]

        def body(w_, v_, pr, thr_, xs_, ys_, sw_, hc_):
            masks = self._client_masks(w_, v_, pr, thr_)
            with jax.named_scope("round.clients"):
                losses, ups, hds = self._locals_multi(w_, masks, xs_, ys_,
                                                      sw_, hc_)
            ga, hda = jax.lax.all_gather((ups, hds), "data", axis=0,
                                         tiled=True)
            return losses, ga, hda

        losses, ups, hds = shard_map(
            body, mesh=self.mesh,
            in_specs=(P(), P(), P(), P("data"), P("data"), P("data"),
                      P("data"), P("data")),
            out_specs=(P("data"), P(), P()), check_vma=False)(
                w, v, self.prunable, thr, xs, ys, sw, hc)
        w2, g, step, n_ok, ast, h2 = self._replicated(
            self._dyn_sharded_tail, w, v, ups, hds, cw, inv, h, cid, noise,
            cf, poison)
        return w2, g, losses, thr, step, n_ok, ast, h2

    # -- block scaffold: lax.scan over the round axis -----------------------

    def _make_block_impl(self, round_fn, noisy: bool = False,
                         faulted: bool = False, poisoned: bool = False,
                         sharded_store: bool = False, dyn: bool = False):
        """K rounds per dispatch around any of the four per-round bodies:
        the scan carries (w, v) and consumes [K]-leading stacked schedule
        arrays; batches are gathered ON DEVICE from the ClientStore
        buffers (dx, dy) via host-drawn indices (`ClientStore.gather` is
        the reference form of the same expression), so no batch data
        crosses host->device inside a block. One scaffold serves the
        shared/multi x unsharded/sharded grid — each scan step is exactly
        the corresponding per-round body, which is what makes a block
        bit-for-bit equal to K round_step dispatches. With ``noisy`` the
        scan additionally consumes a [K, R, L] per-round noise stack (one
        upload per BLOCK, not per round — the zero-per-round-H2D property
        is preserved). With ``faulted`` it consumes two more [K, C]
        schedule operands the same way: host-drawn 0/1 fault weights `fw`
        (multiplied into the counts-derived client weights — an exact 0/1
        product, so dropped clients ride the padding-client path) and
        per-client corruption factors `cf` (1.0 = clean, exact). With
        ``poisoned`` a [K, C, R, L] additive upload-poison stack joins them
        (zeros = clean) — the one block operand whose size scales with the
        model; still a single per-block upload, never per-round. With
        ``sharded_store`` (streamed cohorts on a mesh, core/cohort_store.py)
        the store buffers are sharded over the data axis instead of
        replicated and `cid` carries shard-LOCAL row ids, so the batch
        gather runs inside its own collective-free shard_map
        (`_gather_sharded`) — each device reads only its own clients' rows
        and the sharded round bodies consume the already-data-sharded
        batches unchanged. With ``dyn`` (FedDyn) the per-client correction
        state h joins the scan CARRY right after (w, v) — each round's
        scatter-update feeds the next round's gather, all inside the one
        dispatch — and the updated state is returned alongside (w', v')."""

        def impl(w, v, *op):
            self.n_traces += 1
            if dyn:
                h, op = op[0], op[1:]
            dx, dy, cids, idxs, sw, counts, inv, ks = op[:8]
            rest = op[8:]
            # 0/1 client-validity weights straight from the per-round real
            # counts — built on device (exact 0.0/1.0, so the weighted
            # aggregate is unchanged bit for bit), because host-building
            # them per block would mean an uncacheable [K, C_b] upload for
            # every distinct counts vector an AO schedule produces
            cw = (jnp.arange(cids.shape[1])[None, :]
                  < counts[:, None]).astype(jnp.float32)
            if faulted:
                fw, cf, rest = rest[0], rest[1], rest[2:]
                cw = cw * fw
            else:
                cf = None
            if poisoned:
                po, rest = rest[0], rest[1:]
            else:
                po = None

            def body(carry, inp):
                if dyn:
                    w, v, h = carry
                else:
                    w, v = carry
                cid, ix, sw_k, cw_k, inv_k, k = inp[:6]
                nxt = 6
                cf_k = None
                if faulted:
                    cf_k, nxt = inp[nxt], nxt + 1
                po_k = inp[nxt] if poisoned else None
                if sharded_store:
                    xs, ys = self._gather_sharded(dx, dy, cid, ix)
                else:
                    # local-step blocks gather [C, E_b, B] index arrays —
                    # broadcast the id column across the extra axes
                    cidx = cid.reshape(cid.shape + (1,) * (ix.ndim - 1))
                    xs = dx[cidx, ix]
                    ys = dy[cidx, ix]
                if dyn:
                    w2, g, losses, thr, _, n_ok, ast, h2 = round_fn(
                        w, v, xs, ys, sw_k, cw_k, inv_k, k, h, cid,
                        noise=inp[-1] if noisy else None,
                        cf=cf_k, poison=po_k)
                    return (w2, g, h2), (losses, thr, n_ok, ast)
                w2, g, losses, thr, _, n_ok, ast = round_fn(
                    w, v, xs, ys, sw_k, cw_k, inv_k, k,
                    noise=inp[-1] if noisy else None,
                    cf=cf_k, poison=po_k)
                return (w2, g), (losses, thr, n_ok, ast)

            xss = ((cids, idxs, sw, cw, inv, ks)
                   + ((cf,) if faulted else ())
                   + ((po,) if poisoned else ()) + rest)
            if dyn:
                (w2, v2, h2), (losses, thrs, n_oks, asts) = jax.lax.scan(
                    body, (w, v, h), xss)
                return w2, v2, h2, losses, thrs, n_oks, asts
            (w2, v2), (losses, thrs, n_oks, asts) = jax.lax.scan(
                body, (w, v), xss)
            return w2, v2, losses, thrs, n_oks, asts

        return impl

    def _fault_entry(self, kind: str, noisy: bool,
                     poisoned: bool = False) -> Callable:
        """Lazily built jit entry points for rounds with fault operands:
        per-round corrupt steps take an extra [C] `cf` (plus a [C, R, L]
        `poison` stack when an additive attack is active — poisoned rounds
        always carry both, ones/zeros-filled defaults being exact no-ops);
        block fault steps take [K, C] `fw` + `cf` stacks and optionally a
        [K, C, R, L] poison stack (wired by _make_block_impl). Cached per
        (kind, noisy, poisoned) so fault runs stay on the same trace-count
        ladder as fault-free ones, one extra family per mode used."""
        key = (kind, noisy, poisoned)
        fn = self._fault_steps.get(key)
        if fn is not None:
            return fn
        shared = kind.endswith("shared")
        if self.mesh is None:
            round_fn = self._round_shared if shared else self._round_multi
        else:
            round_fn = (self._round_shared_sharded if shared
                        else self._round_multi_sharded)
        if kind.startswith("blk"):
            impl = self._make_block_impl(round_fn, noisy=noisy, faulted=True,
                                         poisoned=poisoned)
        elif poisoned and noisy:
            def impl(w, v, xs, ys, sw, cw, inv, k, cf, po, noise,
                     _fn=round_fn):
                self.n_traces += 1
                return _fn(w, v, xs, ys, sw, cw, inv, k, noise=noise, cf=cf,
                           poison=po)
        elif poisoned:
            def impl(w, v, xs, ys, sw, cw, inv, k, cf, po, _fn=round_fn):
                self.n_traces += 1
                return _fn(w, v, xs, ys, sw, cw, inv, k, cf=cf, poison=po)
        elif noisy:
            def impl(w, v, xs, ys, sw, cw, inv, k, cf, noise, _fn=round_fn):
                self.n_traces += 1
                return _fn(w, v, xs, ys, sw, cw, inv, k, noise=noise, cf=cf)
        else:
            def impl(w, v, xs, ys, sw, cw, inv, k, cf, _fn=round_fn):
                self.n_traces += 1
                return _fn(w, v, xs, ys, sw, cw, inv, k, cf=cf)
        fn = jax.jit(impl, donate_argnums=self._donate_args)
        self._fault_steps[key] = fn
        return fn

    def _gather_sharded(self, dx, dy, cid, ix):
        """Batch gather from a data-sharded cohort store: each shard fancy-
        indexes its OWN [rows_per_shard, N_max, ...] block with its shard-
        local ids/indices — no collective, and the outputs come back
        sharded P("data") along the client axis, exactly the layout the
        sharded round bodies' in_specs expect."""
        def gather(d, e, c, i):
            cx = c.reshape(c.shape + (1,) * (i.ndim - 1))
            return d[cx, i], e[cx, i]
        return shard_map(gather, mesh=self.mesh,
                         in_specs=(P("data"), P("data"), P("data"),
                                   P("data")),
                         out_specs=(P("data"), P("data")))(dx, dy, cid, ix)

    def _stream_entry(self, shared: bool, noisy: bool,
                      faulted: bool = False,
                      poisoned: bool = False) -> Callable:
        """Lazily built jit entries for blocks over a SHARDED cohort store
        (streamed fleet path on a mesh): the same block scaffold around the
        same sharded round bodies, with the store gather swapped for the
        shard-local one. Cached beside the fault entries so streamed runs
        pay one extra trace family per mode used, same ladder as before."""
        key = ("stream", shared, noisy, faulted, poisoned)
        fn = self._fault_steps.get(key)
        if fn is None:
            round_fn = (self._round_shared_sharded if shared
                        else self._round_multi_sharded)
            impl = self._make_block_impl(round_fn, noisy=noisy,
                                         faulted=faulted, poisoned=poisoned,
                                         sharded_store=True)
            fn = jax.jit(impl, donate_argnums=self._donate_args)
            self._fault_steps[key] = fn
        return fn

    def _dyn_entry(self, kind: str, noisy: bool, faulted: bool = False,
                   poisoned: bool = False) -> Callable:
        """Lazily built jit entries for the FedDyn (stateful) rounds: the
        same operand order as the plain/fault entries with the state pair
        ``(h, cid)`` appended after k, then the optional cf/poison/noise
        operands. Cached beside the fault entries per (kind, noisy,
        faulted, poisoned) so FedDyn runs stay on the one-extra-family-
        per-mode trace ladder. The state buffer is NOT donated: the
        trainer keeps ownership so a failed dispatch can't strand it."""
        key = ("dyn", kind, noisy, faulted, poisoned)
        fn = self._fault_steps.get(key)
        if fn is not None:
            return fn
        shared = kind.endswith("shared")
        if self.mesh is None:
            round_fn = (self._round_shared_dyn if shared
                        else self._round_multi_dyn)
        else:
            round_fn = (self._round_shared_dyn_sharded if shared
                        else self._round_multi_dyn_sharded)
        if kind.startswith("blk"):
            impl = self._make_block_impl(round_fn, noisy=noisy,
                                         faulted=faulted, poisoned=poisoned,
                                         dyn=True)
        else:
            def impl(w, v, xs, ys, sw, cw, inv, k, h, cid, *rest,
                     _fn=round_fn):
                self.n_traces += 1
                i = 0
                cf = po = noise = None
                if faulted:
                    cf, i = rest[i], i + 1
                if poisoned:
                    po, i = rest[i], i + 1
                if noisy:
                    noise = rest[i]
                return _fn(w, v, xs, ys, sw, cw, inv, k, h, cid,
                           noise=noise, cf=cf, poison=po)
        fn = jax.jit(impl, donate_argnums=self._donate_args)
        self._fault_steps[key] = fn
        return fn

    # -- sharded bodies: client axis over the mesh data axis ----------------
    #
    # Threshold and mask are computed replicated (cheap, deterministic —
    # every device derives the identical mask from the replicated (w, v)),
    # the per-client gradient scan runs on each shard's local clients, and
    # the shards meet in exactly ONE collective: a psum of the weighted
    # per-shard gradient sums. The FedSGD update then runs replicated so
    # (w, v) never need resharding between rounds.

    @staticmethod
    def _guarded_partial(losses, grads, cw, cf, poison=None):
        """Shard-local half of the non-finite guard + the round's single
        collective. Corruption factors (if any) scale the local gradients
        (additive poison joins after, like the single-device tail), the
        isfinite flags zero the weight of any client whose summed
        gradient went non-finite, and ONE tuple psum combines the weighted
        partial gradient sums with the [2] (weighted, surviving) counts —
        the per-round collective count stays at one."""
        with jax.named_scope("round.aggregate"):
            if cf is not None:
                grads = grads * cf.astype(jnp.float32)[:, None, None]
            if poison is not None:
                grads = grads + poison.astype(jnp.float32)
            fin = jnp.isfinite(grads).all(axis=(1, 2)).astype(jnp.float32)
            cwe = cw * fin                       # exact: fin is 0.0/1.0
            gsum = ops.packed_weighted_grad_sum(grads, cwe)
            cnt = jnp.stack([cw.sum(), cwe.sum()])
            gsum, cnt = jax.lax.psum((gsum, cnt), "data")
            return losses, gsum, cnt

    @staticmethod
    def _robust_partial(losses, grads, cw, cf, poison=None):
        """Shard-local half of the ROBUST sharded round: rank- and
        distance-based reducers need every client's gradient, not a
        partial sum, so the round's single collective becomes one tuple
        `all_gather` of the (post-fault, quarantine-weighted) local stacks
        along the client axis — replacing the mean path's psum, still
        exactly one collective per round. Tiled gathering over the evenly
        sharded axis reconstructs the single-device [C_b, R, L] stack in
        original client order, and the reducers are bucket-capacity
        invariant, so the sharded robust trajectory is bitwise identical
        to the unsharded one (stronger than the mean path, whose psum
        reassociates the sum — DESIGN.md §11)."""
        with jax.named_scope("round.aggregate"):
            if cf is not None:
                grads = grads * cf.astype(jnp.float32)[:, None, None]
            if poison is not None:
                grads = grads + poison.astype(jnp.float32)
            fin = jnp.isfinite(grads).all(axis=(1, 2)).astype(jnp.float32)
            cwe = cw * fin                       # exact: fin is 0.0/1.0
            ga, cwea = jax.lax.all_gather((grads, cwe), "data", axis=0,
                                          tiled=True)
            return losses, ga, cwea

    def _robust_tail(self, w, v, grads, cw_eff, noise):
        """Replicated robust tail: reduce the gathered full stack with the
        engine's aggregator and apply the same FMA-fenced inv=1.0 update
        as the single-device robust branch (bitwise-identical inputs ->
        bitwise-identical round)."""
        with jax.named_scope("round.aggregate"):
            ghat, ast = self.aggregator.reduce(grads, cw_eff)
            n_ok = cw_eff.sum()
        with jax.named_scope("round.update"):
            w2, g, step = ops.packed_apply_mean_update(
                w, ghat, jnp.float32(1.0), self.eta, noise=noise)
            alive = n_ok > 0.0
            w2 = jnp.where(alive, w2, w)
            g = jnp.where(alive, g, v)
        return w2, g, step, n_ok.astype(jnp.int32), ast

    def _guarded_tail(self, w, v, gsum, cnt, inv, noise):
        """Replicated guard tail for the sharded bodies: renormalize the
        mean over the cross-shard survivor count (host `inv` passes through
        value-identically when every weighted client survived — the same
        contract as ops.packed_client_quarantine), apply the update, and
        carry (w, v) unchanged when no client survived."""
        with jax.named_scope("round.update"):
            n_w, n_ok = cnt[0], cnt[1]
            inv_eff = jnp.where(
                n_ok == n_w, jnp.asarray(inv, jnp.float32),
                jnp.where(n_ok > 0.0, 1.0 / jnp.maximum(n_ok, 1.0), 0.0))
            w2, g, step = ops.packed_apply_mean_update(w, gsum, inv_eff,
                                                       self.eta, noise=noise)
            alive = n_ok > 0.0
            w2 = jnp.where(alive, w2, w)
            g = jnp.where(alive, g, v)
            return w2, g, step, n_ok.astype(jnp.int32)

    def _round_shared_sharded(self, w, v, xs, ys, sw, cw, inv, k, noise=None,
                              cf=None, poison=None):
        """Mesh variant of _round_shared: threshold / mask / FedSGD update
        replicated outside the client-sharded region (each in its own
        replicated shard_map where a Pallas kernel may run, see
        `_replicated`), per-shard gradient scan + the round's
        single collective inside (the mean path's psum, or the robust
        path's all_gather when an aggregator is set — the reducers need
        the full client stack). Traced by both the per-round jit and the
        block scan, like its single-device sibling. `noise` (replicated)
        joins the replicated update tail — the collective count is
        unchanged. `cf` / `poison` (per-client fault operands) shard with
        the client axis."""
        thr, mask = self._replicated(self._threshold_mask, w, v, k)
        pruned = w * mask

        robust = self.aggregator is not None
        partial = self._robust_partial if robust else self._guarded_partial

        def body(pruned, mask, xs, ys, sw, cw, *extra):
            # the replicated buffer enters as device-varying: a gradient
            # taken w.r.t. an invariant input is psum'd over the mesh by
            # shard_map's autodiff, which would sum every shard's clients
            # into each local gradient before the round's own psum
            pruned = jax.lax.pcast(pruned, "data", to="varying")
            losses, grads = self._client_grads_shared(pruned, mask, xs, ys,
                                                      sw)
            return partial(losses, grads, cw,
                           extra[0] if cf is not None else None,
                           extra[-1] if poison is not None else None)

        specs = (P(), P(), P("data"), P("data"), P("data"), P("data"))
        args = (pruned, mask, xs, ys, sw, cw)
        if cf is not None:
            specs, args = specs + (P("data"),), args + (cf,)
        if poison is not None:
            specs, args = specs + (P("data"),), args + (poison,)
        # the robust tail reduces the all_gather'd full stack identically
        # on every shard — genuinely replicated, but the varying-axes check
        # types an all_gather's result as varying (unlike psum's), so the
        # check is disabled on that path only
        losses, a, b = shard_map(
            body, mesh=self.mesh, in_specs=specs,
            out_specs=(P("data"), P(), P()), check_vma=not robust)(*args)
        if robust:
            w2, g, step, n_ok, ast = self._replicated(
                self._robust_tail, w, v, a, b, noise)
        else:
            w2, g, step, n_ok = self._guarded_tail(w, v, a, b, inv, noise)
            ast = jnp.int32(0)
        return w2, g, losses, thr, step, n_ok, ast

    def _round_multi_sharded(self, w, v, xs, ys, sw, cw, inv, ks, noise=None,
                             cf=None, poison=None):
        """Mesh variant of _round_multi (see _round_shared_sharded)."""
        thr = self._replicated(self._thresholds, w, v, ks)     # [C]

        robust = self.aggregator is not None
        partial = self._robust_partial if robust else self._guarded_partial

        def body(w_, v_, pr, thr_, xs_, ys_, sw_, cw_, *extra):
            # per-shard masks from the local thresholds: the batched
            # kernel reads the replicated (w, v) once, local masks only
            masks = self._client_masks(w_, v_, pr, thr_)
            losses, grads = self._client_grads_multi(w_, masks, xs_, ys_,
                                                     sw_)
            return partial(losses, grads, cw_,
                           extra[0] if cf is not None else None,
                           extra[-1] if poison is not None else None)

        specs = (P(), P(), P(), P("data"), P("data"), P("data"),
                 P("data"), P("data"))
        args = (w, v, self.prunable, thr, xs, ys, sw, cw)
        if cf is not None:
            specs, args = specs + (P("data"),), args + (cf,)
        if poison is not None:
            specs, args = specs + (P("data"),), args + (poison,)
        # unchecked: the batched mask kernel runs inside this region, and a
        # Pallas kernel cannot be traced under the varying-axes check (its
        # interpret mode mixes varying blocks with invariant grid indices).
        # Unchecked, autodiff inserts no psum either, so each gradient is
        # taken w.r.t. the shard's own pruned copy
        losses, a, b = shard_map(
            body, mesh=self.mesh, in_specs=specs,
            out_specs=(P("data"), P(), P()), check_vma=False)(*args)
        if robust:
            w2, g, step, n_ok, ast = self._replicated(
                self._robust_tail, w, v, a, b, noise)
        else:
            w2, g, step, n_ok = self._guarded_tail(w, v, a, b, inv, noise)
            ast = jnp.int32(0)
        return w2, g, losses, thr, step, n_ok, ast

    def _shared_sharded_impl(self, w, v, xs, ys, sw, cw, inv, k):
        self.n_traces += 1
        return self._round_shared_sharded(w, v, xs, ys, sw, cw, inv, k)

    def _multi_sharded_impl(self, w, v, xs, ys, sw, cw, inv, ks):
        self.n_traces += 1
        return self._round_multi_sharded(w, v, xs, ys, sw, cw, inv, ks)

    # -- public API ---------------------------------------------------------

    def bucket_size(self, n_clients: int) -> int:
        """Padded client-axis size for a round selecting `n_clients`:
        shards * next_pow2(ceil(n_clients / shards)), capped at the client
        population when known (padding clients cost real gradient FLOPs, so
        full participation must not pad past the roster). A training run
        compiles at most log2(C_max)+1 step traces per lambda family."""
        return bucket_capacity(n_clients, shards=self.shards,
                               bucket=self.bucket,
                               max_clients=self.max_clients)

    def init_buffers(self, params: PyTree) -> tuple[jnp.ndarray, jnp.ndarray]:
        w = self.pack.pack(params)
        return w, jnp.zeros_like(w)

    def round_step(self, w, v, xs, ys, lams, sample_weights=None,
                   noise=None, upload_weights=None, corrupt=None,
                   poison=None, h=None, client_ids=None):
        """One full round. xs: [C, B, ...], ys: [C, B], lams: [C] host-side
        pruning ratios for the selected clients; sample_weights: optional
        [C, B] 0/1 per-sample weights (ragged clients padded to B);
        noise: optional packed [R, L] aggregation-channel noise (zero on
        padding lanes) added to the mean gradient before the update — the
        noisy-uplink axis (wireless/channel.GaussianAggregateNoise).
        upload_weights: optional [C] 0/1 floats — 0 marks a client whose
        upload never arrived (dropout/straggler draw, core/faults.py); the
        client rides the padding-client path (weight 0) and the host mean
        scalar renormalizes over the survivors, so NO new trace is paid.
        corrupt: optional [C] per-client gradient factors (1.0 = clean,
        NaN = poisoned) — a traced operand, routed through the lazily
        built fault entry points.
        poison: optional [C, R, L] additive upload poison (zeros = clean
        client) — the GaussianPoison byzantine axis; it rides the same
        fault entries (a poisoned round always carries a `cf` operand
        too, ones-filled when no multiplicative fault fired).
        With a multi-step LocalScheme, xs/ys/sample_weights carry a step
        axis after the client axis — xs: [C, E, B, ...] with E =
        local_scheme.steps — padded here to the static pow2 step bucket
        (padded steps replicate the last real batch and are exact no-ops).
        FedDyn additionally requires `h` (the [C_all, R, L] correction
        state) and `client_ids` ([C] ids indexing its rows); the updated
        state lands in `last_h` (device array, never synced).
        Returns (w', v', losses [C], threshold, step) — all device arrays;
        nothing is synced to host (`last_n_ok` additionally holds the
        round's lazy survivor count). `step` is the applied update eta*v'
        (kept as an output so the update's multiply can never be
        FMA-contracted — the bit-for-bit contract with the reference
        trainer depends on it)."""
        lams = np.atleast_1d(np.asarray(lams, np.float64))
        if np.any((lams < 0.0) | (lams >= 1.0)):
            raise ValueError(f"lambda must be in [0,1), got {lams}")
        n_clients = int(xs.shape[0])
        if lams.shape[0] != n_clients:
            raise ValueError(
                f"{lams.shape[0]} lambdas for {n_clients} client batches")
        ks = np.floor(lams * self.pack.n_prunable).astype(np.int32)

        # pad the step axis to its static pow2 bucket first: padded steps
        # replicate the last real step's batch (no RNG consumed) and are
        # gated off by the validity vector inside the step scan
        ls = self.local_scheme
        if ls is not None:
            if int(xs.shape[1]) != ls.steps:
                raise ValueError(
                    f"expected {ls.steps} local-step batches per client, "
                    f"got {xs.shape[1]}")
            epad = ls.steps_bucket - ls.steps
            if epad:
                def pad_steps(a):
                    a = jnp.asarray(a)
                    reps = jnp.broadcast_to(
                        a[:, -1:], (a.shape[0], epad) + a.shape[2:])
                    return jnp.concatenate([a, reps], axis=1)
                xs, ys = pad_steps(xs), pad_steps(ys)
                if sample_weights is not None:
                    sample_weights = pad_steps(
                        jnp.asarray(sample_weights, jnp.float32))

        # pad the client axis to the bucket; padding clients replicate the
        # last real batch and carry weight 0, so they never touch the update
        c_b = self.bucket_size(n_clients)
        self.buckets_used.add(c_b)
        pad = c_b - n_clients
        if sample_weights is None:
            key = (c_b,) + tuple(int(s) for s in ys.shape[1:])
            sw = self._sw_cache.get(key)
            if sw is None:
                sw = self._sw_cache[key] = jnp.ones(key, jnp.float32)
        else:
            sw = jnp.asarray(sample_weights, jnp.float32)
        if pad:
            def tile(a):
                return jnp.concatenate(
                    [a, jnp.broadcast_to(a[-1:], (pad,) + a.shape[1:])])
            xs, ys = tile(xs), tile(ys)
            if sample_weights is not None:
                sw = tile(sw)
        if upload_weights is None:
            cw = self._cw_cache.get((c_b, n_clients))
            if cw is None:
                cw_host = np.zeros(c_b, np.float32)
                cw_host[:n_clients] = 1.0
                cw = self._cw_cache[(c_b, n_clients)] = jnp.asarray(cw_host)
            # 1/C on host, like the reference server_step's 1/len(grads)
            inv = np.float32(1.0 / n_clients)
        else:
            # fault draw folded into the same 0/1 weight operand padding
            # clients already use — identical trace, new operand values;
            # the mean renormalizes over the survivors exactly as the
            # reference server_step's 1/len(surviving grads) does
            uw = np.asarray(upload_weights, np.float32)
            if uw.shape != (n_clients,):
                raise ValueError(
                    f"upload_weights shape {uw.shape} != ({n_clients},)")
            cw_host = np.zeros(c_b, np.float32)
            cw_host[:n_clients] = uw
            cw = jnp.asarray(cw_host)
            surv = float(np.asarray(uw, np.float64).sum())
            inv = np.float32(1.0 / surv) if surv > 0 else np.float32(0.0)
        po = None
        if poison is not None:
            po = jnp.asarray(poison, jnp.float32)
            if po.shape[0] != n_clients:
                raise ValueError(
                    f"poison leading dim {po.shape[0]} != {n_clients}")
            if pad:
                # padding clients stay clean: additive identity is 0
                po = jnp.concatenate(
                    [po, jnp.zeros((pad,) + po.shape[1:], jnp.float32)])
        cf = None
        if corrupt is not None or po is not None:
            cf_host = np.ones(c_b, np.float32)   # padding clients clean
            if corrupt is not None:
                cf_host[:n_clients] = np.asarray(corrupt, np.float32)
            cf = jnp.asarray(cf_host)
        fargs = () if cf is None else (
            (cf,) + (() if po is None else (po,)))

        dyn = ls is not None and ls.stateful
        if dyn:
            if h is None or client_ids is None:
                raise ValueError(
                    "feddyn round_step requires the correction state h and "
                    "the selected client_ids")
            cid = np.asarray(client_ids, np.int32)
            if cid.shape != (n_clients,):
                raise ValueError(
                    f"client_ids shape {cid.shape} != ({n_clients},)")
            if pad:
                # padding clients replicate the last real id; their state
                # scatter contribution is exact +0.0 (weight 0), a no-op
                cid = np.concatenate([cid, np.full(pad, cid[-1], np.int32)])
            dargs = (h, jnp.asarray(cid))

        nz = () if noise is None else (jnp.asarray(noise),)
        if np.all(ks == ks[0]):
            k_dev = jnp.asarray(ks[0], jnp.int32)
            if dyn:
                out = self._dyn_entry("shared", noise is not None,
                                      cf is not None, po is not None)(
                    w, v, xs, ys, sw, cw, inv, k_dev, *dargs, *fargs, *nz)
            elif cf is not None:
                out = self._fault_entry("shared", noise is not None,
                                        po is not None)(
                    w, v, xs, ys, sw, cw, inv, k_dev, *fargs, *nz)
            else:
                out = (self._step_shared(w, v, xs, ys, sw, cw, inv, k_dev)
                       if noise is None else
                       self._step_shared_nz(w, v, xs, ys, sw, cw, inv, k_dev,
                                            *nz))
        else:
            ks_b = np.concatenate(
                [ks, np.full(pad, ks[-1], np.int32)]) if pad else ks
            ks_dev = jnp.asarray(ks_b)
            if dyn:
                out = self._dyn_entry("multi", noise is not None,
                                      cf is not None, po is not None)(
                    w, v, xs, ys, sw, cw, inv, ks_dev, *dargs, *fargs, *nz)
            elif cf is not None:
                out = self._fault_entry("multi", noise is not None,
                                        po is not None)(
                    w, v, xs, ys, sw, cw, inv, ks_dev, *fargs, *nz)
            else:
                out = (self._step_multi(w, v, xs, ys, sw, cw, inv, ks_dev)
                       if noise is None else
                       self._step_multi_nz(w, v, xs, ys, sw, cw, inv, ks_dev,
                                           *nz))
        if dyn:
            w2, g, losses, thr, step, n_ok, ast, h2 = out
            self.last_h = h2
        else:
            w2, g, losses, thr, step, n_ok, ast = out
        self.last_n_ok = n_ok
        self.last_agg_stat = ast
        if pad:
            losses = losses[:n_clients]
            if thr.ndim:                      # per-client thresholds
                thr = thr[:n_clients]
        return w2, g, losses, thr, step

    def block_step(self, w, v, store, cids, idxs, lams, counts,
                   sample_weights=None, noises=None, upload_weights=None,
                   corrupt=None, poisons=None, h=None):
        """K rounds in ONE jitted dispatch (`lax.scan` over the round axis).

        store : ClientStore — device-resident [C_all, N_max, ...] data.
        cids  : [K, C] int  — selected client ids per round in selected
            order; rounds with fewer than C clients are right-padded by
            replicating their last real id (exactly the per-round path's
            padding-client convention).
        idxs  : [K, C, B] int — host-drawn sample indices into each
            client's store rows. Drawing them from the same numpy RNG
            stream as `_sample_batch` keeps the batch sequence — and the
            bit-for-bit contract with the reference loop — intact.
        lams  : [K, C] float — pruning ratios, padded like cids.
        counts: [K] int     — real selected count per round.
        sample_weights : [K, C, B] 0/1 weights or None (ragged clients
            padded to B carry 0 on their repeat samples).
        noises : [K, R, L] per-round packed aggregation noise or None —
            one stack per block dispatch (never a per-round upload), each
            round consuming its own slice inside the scan.
        upload_weights : [K, C] 0/1 floats or None — host-drawn fault
            masks (0 = the upload never arrived); they join the stacked
            schedule operands exactly like cids/ks — ONE upload per block,
            the zero-per-round-H2D property is preserved — and multiply
            into the counts-derived client weights on device.
        corrupt : [K, C] per-client gradient factors or None (1.0 =
            clean). Any fault operand routes the block through the
            lazily built fault entry, which always consumes BOTH [K, C]
            stacks (ones-filled defaults are exact no-ops), so a fault run
            uses one entry per (shape bucket) regardless of which kinds
            fired.
        poisons : [K, C, R, L] additive upload poison or None (zeros =
            clean) — the byzantine GaussianPoison axis. The one block
            operand whose size scales with the model; still ONE upload per
            block, never per round, so the zero-per-round-H2D property
            holds.

        Returns (w', v', losses [K, C_b], thresholds [K] or [K, C_b]) —
        all device arrays, nothing synced; `losses[k, counts[k]:]` belongs
        to padding clients (callers slice). Batch DATA never crosses
        host->device here — only O(K*C*B) int32 index/schedule arrays do.

        The client axis buckets exactly like `round_step` (all rounds in a
        block must share one bucket — the trainer groups rounds so this
        holds); K is NOT padded — padding rounds would cost full gradient
        FLOPs — so callers keep K on a pow2 ladder by decomposition, and
        `k_buckets_used` records the ladder for the trace-bound tests.
        """
        lams = np.asarray(lams, np.float64)
        if np.any((lams < 0.0) | (lams >= 1.0)):
            raise ValueError(f"lambda must be in [0,1), got {lams}")
        # multi-step blocks draw [K, C, E, B] index arrays; the step axis
        # pads to the static pow2 bucket exactly like round_step's batches
        # (replicate the last real step — no RNG consumed, gated no-ops)
        ls = self.local_scheme
        idxs = np.asarray(idxs, np.int32)
        if ls is not None:
            if idxs.ndim != 4 or int(idxs.shape[2]) != ls.steps:
                raise ValueError(
                    f"expected [K, C, {ls.steps}, B] local-step indices, "
                    f"got shape {idxs.shape}")
            epad = ls.steps_bucket - ls.steps
            if epad:
                idxs = np.concatenate(
                    [idxs, np.repeat(idxs[:, :, -1:], epad, axis=2)],
                    axis=2)
                if sample_weights is not None:
                    sws = np.asarray(sample_weights, np.float32)
                    sample_weights = np.concatenate(
                        [sws, np.repeat(sws[:, :, -1:], epad, axis=2)],
                        axis=2)
            n_rounds, c_max = idxs.shape[:2]
            batch = int(idxs.shape[3])
        else:
            if idxs.ndim != 3:
                raise ValueError(
                    f"expected [K, C, B] indices, got shape {idxs.shape}")
            n_rounds, c_max, batch = idxs.shape
        counts = np.asarray(counts, np.int64)
        if counts.shape != (n_rounds,) or cids.shape != (n_rounds, c_max) \
                or lams.shape != (n_rounds, c_max):
            raise ValueError("inconsistent block array shapes")
        if int(counts.max()) > c_max or int(counts.min()) < 1:
            raise ValueError(f"counts {counts} outside [1, {c_max}]")
        ks = np.floor(lams * self.pack.n_prunable).astype(np.int32)

        c_b = self.bucket_size(int(counts.max()))
        if self.bucket_size(int(counts.min())) != c_b:
            raise ValueError(
                "rounds in one block must share a client-axis bucket "
                f"(got counts {counts} -> buckets "
                f"{sorted({self.bucket_size(int(c)) for c in counts})})")
        self.buckets_used.add(c_b)
        self.k_buckets_used.add(n_rounds)
        pad = c_b - c_max

        def pad_cols(a):
            return np.concatenate(
                [a, np.repeat(a[:, -1:], pad, axis=1)], axis=1) if pad else a

        cids = pad_cols(np.asarray(cids, np.int32))
        idxs = pad_cols(idxs)
        ks = pad_cols(ks)
        if sample_weights is None:
            key = (("blk", n_rounds, c_b, batch) if ls is None else
                   ("blk", n_rounds, c_b, ls.steps_bucket, batch))
            sw = self._sw_cache.get(key)
            if sw is None:
                sw = self._sw_cache[key] = jnp.ones(key[1:], jnp.float32)
        else:
            sw = jnp.asarray(pad_cols(
                np.asarray(sample_weights, np.float32)))
        po = None
        if poisons is not None:
            po = np.asarray(poisons, np.float32)
            if po.shape[:2] != (n_rounds, c_max):
                raise ValueError(
                    f"poisons leading dims {po.shape[:2]} != "
                    f"({n_rounds}, {c_max})")
            if pad:
                # padding clients stay clean: additive identity is 0
                po = np.concatenate(
                    [po, np.zeros((n_rounds, pad) + po.shape[2:],
                                  np.float32)], axis=1)
        faulted = (upload_weights is not None or corrupt is not None
                   or po is not None)
        if faulted:
            # per-round survivor counts drive the host mean scalars; the
            # float64 1/n -> float32 cast gives the identical value to the
            # reference server_step's np.float32(1.0 / n) (double rounding
            # is safe: p=53 >= 2*24+2)
            uw = (np.ones((n_rounds, c_max), np.float32)
                  if upload_weights is None
                  else np.asarray(upload_weights, np.float32))
            cfa = (np.ones((n_rounds, c_max), np.float32)
                   if corrupt is None else np.asarray(corrupt, np.float32))
            if uw.shape != (n_rounds, c_max) or cfa.shape != (n_rounds, c_max):
                raise ValueError("fault operand shapes must be [K, C]")
            col = np.arange(c_max)[None, :]
            surv = (uw.astype(np.float64) * (col < counts[:, None])).sum(1)
            inv_host = np.where(surv > 0, 1.0 / np.maximum(surv, 1.0), 0.0)
        else:
            # per-round 1/C on host, like the reference server_step's
            # 1/len(grads); the 0/1 client weights are derived from
            # `counts` on device inside the block impl (no per-block
            # [K, C_b] upload)
            inv_host = 1.0 / counts
        inv = jnp.asarray(inv_host.astype(np.float32))
        counts_dev = jnp.asarray(counts.astype(np.int32))

        def pad_ones(a):
            # padding clients carry weight 0 either way; keep their fault
            # operands clean (1.0) so a poisoned last real client can't
            # replicate NaNs into padding lanes
            return np.concatenate(
                [a, np.ones((n_rounds, pad), np.float32)],
                axis=1) if pad else a

        shared = bool((ks == ks[:, :1]).all())
        nz = () if noises is None else (jnp.asarray(noises),)
        ks_dev = jnp.asarray(ks[:, 0]) if shared else jnp.asarray(ks)
        # a data-sharded cohort store (streamed fleet path) swaps the
        # replicated-store gather for the shard-local one; the round bodies
        # and operand layout are otherwise identical
        streamed = self.mesh is not None and bool(
            getattr(store, "sharded", False))
        dyn = ls is not None and ls.stateful
        if dyn:
            if h is None:
                raise ValueError(
                    "feddyn block_step requires the correction state h")
            if streamed:
                raise ValueError(
                    "feddyn over a data-sharded cohort store is not "
                    "supported: run with shards=1 (streamed cohorts stay "
                    "available) or client_store='replicated'")
            fn = self._dyn_entry("blk_shared" if shared else "blk_multi",
                                 noises is not None, faulted,
                                 po is not None)
            out = fn(w, v, h, store.x, store.y, jnp.asarray(cids),
                     jnp.asarray(idxs), sw, counts_dev, inv, ks_dev,
                     *((jnp.asarray(pad_ones(uw)),
                        jnp.asarray(pad_ones(cfa))) if faulted else ()),
                     *(() if po is None else (jnp.asarray(po),)), *nz)
            w2, v2, h2, losses, thrs, n_oks, asts = out
            self.last_h = h2
            self.last_n_ok = n_oks
            self.last_agg_stat = asts
            return w2, v2, losses, thrs
        if faulted:
            fn = (self._stream_entry(shared, noises is not None, True,
                                     po is not None) if streamed
                  else self._fault_entry(
                      "blk_shared" if shared else "blk_multi",
                      noises is not None, po is not None))
            out = fn(w, v, store.x, store.y, jnp.asarray(cids),
                     jnp.asarray(idxs), sw, counts_dev, inv, ks_dev,
                     jnp.asarray(pad_ones(uw)), jnp.asarray(pad_ones(cfa)),
                     *(() if po is None else (jnp.asarray(po),)), *nz)
        elif streamed:
            fn = self._stream_entry(shared, noises is not None)
            out = fn(w, v, store.x, store.y, jnp.asarray(cids),
                     jnp.asarray(idxs), sw, counts_dev, inv, ks_dev, *nz)
        elif shared:
            fn = self._blk_shared if noises is None else self._blk_shared_nz
            out = fn(w, v, store.x, store.y, jnp.asarray(cids),
                     jnp.asarray(idxs), sw, counts_dev, inv, ks_dev, *nz)
        else:
            fn = self._blk_multi if noises is None else self._blk_multi_nz
            out = fn(w, v, store.x, store.y, jnp.asarray(cids),
                     jnp.asarray(idxs), sw, counts_dev, inv, ks_dev, *nz)
        w2, v2, losses, thrs, n_oks, asts = out
        self.last_n_ok = n_oks
        self.last_agg_stat = asts
        return w2, v2, losses, thrs
