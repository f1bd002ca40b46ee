"""Parameter-efficient FedSGD engine (paper Sec. II-A, eqs. 2-7).

Per round s:
  1. server broadcasts the previous global gradient v^(s-1) (downlink, eq. 9);
  2. each selected client computes first-order importance Q = (v * rho)^2
     (eq. 4), prunes the lambda_n fraction of lowest-importance weights
     (eq. 2), yielding the pruned model w~_n;
  3. the client computes a mini-batch gradient on the pruned model (eq. 5)
     and uploads it masked (uplink, eq. 8 / delay eq. 11);
  4. the server averages the selected gradients (eq. 6) and takes the FedSGD
     step w <- w - eta * G (eq. 7).

The engine is model-agnostic: it needs only `loss_fn(params, x, y) -> scalar`.
Time/energy bookkeeping uses the wireless substrate with the schedule's
per-round (a, lambda, p, f).

Two execution backends (DESIGN.md §5):

  * ``backend="packed"`` (default) — the device-resident round engine
    (core/round_engine.py): parameters and the global gradient live in one
    packed [R, 128] buffer across rounds; threshold, masks, per-client
    gradients, aggregation, and the FedSGD step run in a single jitted
    dispatch per round with fused Pallas kernels. No host-side threshold
    computation (`np.partition`/`np.concatenate` over parameters) and no
    device->host parameter transfers inside the round loop.
  * ``backend="reference"`` — the original per-client Python loop (kept as
    the numerical oracle). With the XLA kernel path — what
    ``kernel_impl="auto"`` resolves to everywhere except TPU — the packed
    path reproduces it bit-for-bit on fp32 models (tests/test_packing.py);
    the TPU Pallas path may differ by 1 ulp per update (FMA contraction in
    the fused aggregate kernel, see kernels/ops.packed_fedsgd_update).

Noisy aggregation (beyond the paper, Wu et al.): with ``channel_noise``
set, the server observes ``mean(g) + noise`` instead of the clean
aggregate — the noisy value becomes both the FedSGD update and the next
round's broadcast v. Noise is drawn on host per ROUND INDEX in the packed
buffer layout and consumed identically by both backends and both dispatch
modes (see wireless/channel.GaussianAggregateNoise and DESIGN.md §9), so
the bit-for-bit contract below extends to noisy runs.

Ragged clients (fewer samples than the batch size): when the loss provides
a weighted form (`models.make_loss_fn` attaches one as ``loss.weighted``),
*both* backends evaluate that client via the weighted mean
``sum(sw*ce)/sum(sw)`` on a batch padded with zero-weight repeats — the
plain mean over the real samples in exact arithmetic, but evaluated at the
padded shape. This deliberately redefines the ragged-client oracle (the
pre-PR-2 reference took a plain mean over the short ``[B']`` batch, which
rounds differently because XLA reassociates reductions per shape): it is
the unique form the eager loop and the fused engine can agree on
bit-for-bit, so stragglers stay on the packed path (DESIGN.md §6). Without
a weighted loss, ragged rounds keep the pre-PR-2 short-batch behavior via
the reference fallback (`n_fallback_rounds` counts them).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import pruning
from repro.core.client_store import (ClientStore, StoreBudgetError,
                                     estimated_store_nbytes)
from repro.core.cohort_store import CohortStore, fleet_counters_zero
from repro.core.local import local_spec_key
from repro.core.optimizer_ao import Schedule
from repro.core.packing import LANES, ParamPack
from repro.core.round_engine import RoundEngine, bucket_capacity
from repro.wireless.comm import SystemParams, per_client_delay, round_energy

PyTree = Any

# Block length the packed backend targets per dispatch when
# rounds_per_dispatch="auto" resolves to block execution (accelerators).
DEFAULT_ROUNDS_PER_DISPATCH = 32


def _resolve_rounds_per_dispatch(rpd) -> int:
    """"auto" -> 1 on CPU (rounds there are gradient-FLOP-bound and the
    per-round dispatch is the bit-for-bit-audited default for parity /
    reference work), DEFAULT_ROUNDS_PER_DISPATCH on accelerator backends
    (where the per-round dispatch + H2D upload dominates). Ints pass
    through; both block (>1) and per-round (1) modes are exact."""
    if rpd == "auto":
        return (1 if jax.default_backend() == "cpu"
                else DEFAULT_ROUNDS_PER_DISPATCH)
    r = int(rpd)
    if r < 1:
        raise ValueError(f"rounds_per_dispatch must be >= 1, got {rpd!r}")
    return r


def _default_device_budget() -> int:
    """Device-memory budget the "auto" client-store policy keys on:
    REPRO_DEVICE_MEM_BUDGET (bytes) when set, else a conservative 1 GiB —
    small enough that fleet-scale rosters stream, large enough that every
    edge-scale config in the repo keeps today's replicated store."""
    env = os.environ.get("REPRO_DEVICE_MEM_BUDGET")
    return int(env) if env else 1 << 30


def _all_finite(tree) -> bool:
    """Whether every leaf of `tree` is finite: one device->host read per
    leaf, up to the first that is not."""
    for leaf in jax.tree_util.tree_leaves(tree):
        obs.count("d2h")
        if not bool(jnp.all(jnp.isfinite(leaf))):
            return False
    return True


@dataclasses.dataclass
class ClientData:
    x: np.ndarray
    y: np.ndarray

    def __len__(self) -> int:
        return len(self.y)

    def label_histogram(self, num_classes: int) -> np.ndarray:
        return np.bincount(self.y.astype(int), minlength=num_classes).astype(float)


@dataclasses.dataclass
class RoundMetrics:
    round: int
    train_loss: float
    selected: list[int]
    mean_lambda: float
    delay: float
    energy: float
    cumulative_delay: float
    cumulative_energy: float
    test_loss: float | None = None
    test_accuracy: float | None = None
    # graceful-degradation accounting (core/faults.py): uploads that never
    # arrived (dropout/straggler draw) and arrived-but-non-finite uploads
    # the engine's isfinite guard quarantined
    n_faulted: int = 0
    n_quarantined: int = 0
    # robust-aggregation accounting (core/aggregators.py): clients the
    # active robust reducer trimmed / clipped / excluded this round (the
    # aggregator's `stat_field` names which); always 0 on the mean path
    n_agg_adjusted: int = 0


class FederatedTrainer:
    """FedSGD with client selection + importance pruning + masked aggregation."""

    def __init__(
        self,
        loss_fn: Callable[[PyTree, jnp.ndarray, jnp.ndarray], jnp.ndarray],
        params: PyTree,
        clients: Sequence[ClientData],
        *,
        eta: float,
        batch_size: int,
        seed: int = 0,
        prune_spec: pruning.PruneSpec = pruning.PruneSpec(),
        backend: str = "packed",
        client_axis: str = "auto",
        kernel_impl: str = "auto",
        weighted_loss_fn: Callable | None = None,
        shards: int | None = None,
        rounds_per_dispatch: int | str = "auto",
        channel_noise=None,
        fault_model=None,
        aggregator=None,
        client_store: str = "auto",
        device_mem_budget: int | None = None,
        local_scheme=None,
    ):
        if backend not in ("packed", "reference"):
            raise ValueError(f"unknown backend {backend!r}")
        if client_store not in ("auto", "replicated", "streamed"):
            raise ValueError(f"unknown client_store {client_store!r}")
        self.loss_fn = loss_fn
        # sequences that publish per-client `counts` (FleetRoster) stay
        # lazy — list()-ing a 1e5-client roster would materialize the fleet
        self.clients = (clients if getattr(clients, "counts", None)
                        is not None else list(clients))
        self.eta = float(eta)
        self.batch_size = int(batch_size)
        self.rng = np.random.default_rng(seed)
        self.prune_spec = prune_spec
        self.backend = backend
        self._grad_fn = jax.jit(jax.value_and_grad(loss_fn))
        # Per-sample-weighted loss: lets ragged client batches (fewer
        # samples than the batch size) be padded with zero-weight samples
        # so they stay on the packed path. models.make_loss_fn attaches one
        # as loss_fn.weighted; custom losses can pass weighted_loss_fn
        # explicitly, otherwise ragged rounds fall back to the per-client
        # reference loop exactly as before (n_fallback_rounds counts them).
        self._weighted_loss = (weighted_loss_fn
                               or getattr(loss_fn, "weighted", None))
        self._wgrad_fn = (jax.jit(jax.value_and_grad(self._weighted_loss))
                          if self._weighted_loss is not None else None)
        self.n_fallback_rounds = 0
        # Block execution (rounds_per_dispatch > 1, packed backend only):
        # K consecutive schedule rounds run as ONE jitted lax.scan dispatch
        # with batches gathered on device from a ClientStore — no per-round
        # host sync, no per-round batch upload, K-1 of every K dispatches
        # gone. n_batch_uploads counts per-round host->device stacked-batch
        # transfers (the block path performs none — bench-asserted).
        self.rounds_per_dispatch = (
            _resolve_rounds_per_dispatch(rounds_per_dispatch)
            if backend == "packed" else 1)
        self._store: ClientStore | None = None
        self.n_batch_uploads = 0
        self.n_block_dispatches = 0
        # Fleet-scale client-store policy (core/cohort_store.py):
        # "replicated" keeps the PR-3 full ClientStore, "streamed" moves
        # per-block cohorts with double-buffered prefetch, "auto" picks by
        # the estimated replicated footprint vs the device-memory budget.
        # Streaming only moves data — the RNG/index protocol is untouched —
        # so streamed trajectories are bitwise the replicated ones.
        self.client_store = client_store
        self.device_mem_budget = (int(device_mem_budget)
                                  if device_mem_budget
                                  else _default_device_budget())
        self._store_nbytes: int | None = None
        self._cohorts: CohortStore | None = None
        self.streaming = False
        self.fleet_counters = fleet_counters_zero()
        # Noisy aggregation channel (wireless/channel.GaussianAggregateNoise
        # protocol: sample_packed(round, shape, valid)). Noise is drawn on
        # host keyed by the ROUND INDEX only, in the packed [R, 128] layout
        # (the reference backend unpacks the same buffer through a layout-
        # only ParamPack, built lazily), so both backends, both dispatch
        # modes, and resumed runs all consume identical draws.
        self.channel_noise = channel_noise
        self._noise_ref_pack: ParamPack | None = None
        self._noise_valid: np.ndarray | None = None
        # Client fault injection (core/faults.FaultModel protocol): draws
        # are host-side, keyed (seed, round, kind), attached to the round's
        # schedule info, and consumed identically by both backends — fault
        # runs stay bitwise packed-vs-reference. Counters accumulate at
        # materialization points (and checkpoint/restore with the batch
        # RNG, so resumed totals match an uninterrupted run).
        self.fault_model = fault_model
        self.fault_counters = {"n_dropped": 0, "n_quarantined": 0,
                               "n_skipped_rounds": 0, "n_corrupt_finite": 0}
        # Byzantine-robust aggregation (core/aggregators.py): an engine
        # construction constant, like eta — it changes every round graph,
        # so swapping reducers means a new trainer (Experiment.build /
        # the sweep pool key both fold `aggregator_key` in). None keeps
        # the builtin weighted-mean path byte-identical.
        self.aggregator = aggregator
        self.aggregator_key = (aggregator.spec_key
                               if aggregator is not None else "mean")
        self.agg_counters = ({aggregator.stat_field: 0}
                             if aggregator is not None else {})
        # Local-update scheme (core/local.py, DESIGN.md §14): None is the
        # single-step FedSGD body (today's paths, byte-identical). Like the
        # aggregator it is an engine construction constant — swapping
        # schemes means a new trainer, and `local_key` is the fragment the
        # sweep pool / Experiment.build reuse keys fold in so pooled
        # trainers can never mix per-client state across schemes.
        self.local_scheme = local_scheme
        self.local_key = local_spec_key(local_scheme)
        # FedDyn per-client correction state: one packed [R, 128] row per
        # client in the population, lazily allocated at first use (zeros)
        # on BOTH backends — the reference updates it with the same eager
        # jnp ops the engine fuses, so the state trajectories are bitwise
        # comparable. Rides checkpoints (repro.api.callbacks) for
        # bit-for-bit resume.
        self._h = None
        # lifecycle hooks for the current run() (repro.api.Callback
        # protocol); held on the instance so _exec_block can fire
        # on_block_end without threading them through every call
        self._callbacks: tuple = ()
        if backend == "packed":
            self.pack = ParamPack.build(params, prune_spec)
            # the trainer owns the packed buffers and reassigns them every
            # round, so donation is safe here
            self.engine = RoundEngine(loss_fn, self.pack, eta=self.eta,
                                      client_axis=client_axis,
                                      kernel_impl=kernel_impl, donate=True,
                                      weighted_loss_fn=self._weighted_loss,
                                      shards=shards,
                                      max_clients=len(self.clients),
                                      aggregator=aggregator,
                                      local_scheme=local_scheme)
            self._w, self._v = self.engine.init_buffers(params)
            # pytree views of the packed buffers, memoized on buffer
            # identity so repeated property reads (eval_fn, the ragged
            # fallback's client_update loop) don't rebuild the unpack graph
            self._w_view = self._v_view = None
        else:
            self.pack = self.engine = None
            self._params = params
            self._global_grad: PyTree = jax.tree.map(jnp.zeros_like, params)

    # Params / global gradient are stored packed on the packed backend; the
    # properties give both backends (and external callers) the same pytree
    # view. Writes pack straight back into the device-resident buffers.

    @property
    def params(self) -> PyTree:
        if self.backend == "packed":
            if self._w_view is None or self._w_view[0] is not self._w:
                with obs.span("trainer.unpack"):
                    self._w_view = (self._w, self.pack.unpack(self._w))
            return self._w_view[1]
        return self._params

    @params.setter
    def params(self, tree: PyTree) -> None:
        if self.backend == "packed":
            self._w = self.pack.pack(tree)
            self._w_view = None
        else:
            self._params = tree

    @property
    def global_grad(self) -> PyTree:
        if self.backend == "packed":
            if self._v_view is None or self._v_view[0] is not self._v:
                with obs.span("trainer.unpack"):
                    self._v_view = (self._v, self.pack.unpack(self._v))
            return self._v_view[1]
        return self._global_grad

    @global_grad.setter
    def global_grad(self, tree: PyTree) -> None:
        if self.backend == "packed":
            self._v = self.pack.pack(tree)
            self._v_view = None
        else:
            self._global_grad = tree

    # -- run-state lifecycle ------------------------------------------------

    def reset(self, params: PyTree, seed: int, *, channel_noise=None,
              fault_model=None) -> None:
        """Reinitialize all run state for a FRESH run over the same
        (clients, loss, eta, batch, backend, shards) wiring — the sweep
        engine's trainer-reuse hook (repro.api.sweep). Compiled engine
        traces and the device-resident ClientStore survive, which is what
        makes an S-seed sweep cost far less than S cold trainers; params,
        the global gradient, the batch RNG, and every counter are reset
        exactly as the constructor would, so a reused trainer's trajectory
        is bit-for-bit a cold one's."""
        with obs.span("trainer.reset"):
            self.rng = np.random.default_rng(seed)
            self.channel_noise = channel_noise
            self.fault_model = fault_model
            self.fault_counters = {"n_dropped": 0, "n_quarantined": 0,
                                   "n_skipped_rounds": 0,
                                   "n_corrupt_finite": 0}
            self.agg_counters = ({self.aggregator.stat_field: 0}
                                 if self.aggregator is not None else {})
            self.n_fallback_rounds = 0
            self.n_batch_uploads = 0
            self.n_block_dispatches = 0
            self._callbacks = ()
            # zero the fleet counters IN PLACE: a run's CohortStore accumulates
            # into this dict by reference
            self.fleet_counters.update(fleet_counters_zero())
            self.streaming = False
            self._cohorts = None
            # per-client optimizer state MUST NOT survive pooling: a reused
            # trainer carrying the previous cell's FedDyn correction buffer
            # would silently bias the next run (the regression test in
            # tests/test_local_schemes.py pins pooled == cold byte-identical).
            # Dropping the buffer (rather than zeroing in place) also frees
            # the device memory until the next stateful run touches it.
            self._h = None
            if self.engine is not None:
                self.engine.last_h = None
            if self.backend == "packed":
                self._w, self._v = self.engine.init_buffers(params)
                self._w_view = self._v_view = None
            else:
                self._params = params
                self._global_grad = jax.tree.map(jnp.zeros_like, params)

    # -- noisy aggregation channel ------------------------------------------

    def _noise_layout(self) -> ParamPack:
        """The packed layout noise is drawn in: the engine's pack on the
        packed backend; a lazily built layout-only pack on the reference
        backend (ParamPack.build is pure metadata — no buffers)."""
        if self.pack is not None:
            return self.pack
        if self._noise_ref_pack is None:
            self._noise_ref_pack = ParamPack.build(self._params,
                                                   self.prune_spec)
        return self._noise_ref_pack

    def _noise_packed(self, s: int) -> np.ndarray:
        """Round-s aggregation noise as a packed [R, 128] host array with
        padding lanes zeroed (they hold no real coordinates and must stay
        zero in the buffers)."""
        pack = self._noise_layout()
        if self._noise_valid is None:
            self._noise_valid = pack.valid_mask()
        return self.channel_noise.sample_packed(
            s, (pack.rows, LANES), self._noise_valid)

    def _noise_tree(self, s: int) -> PyTree:
        """The same round-s draw as a pytree (reference backend): unpack is
        a pure gather of the packed draw, so per-coordinate values are
        identical to what the packed engine adds."""
        return self._noise_layout().unpack(jnp.asarray(self._noise_packed(s)))

    def _poison_stack(self, fault) -> np.ndarray | None:
        """Materialize a fault draw's lazy additive poison in the packed
        [C_sel, R, 128] layout (padding lanes masked to 0.0), shared by
        both backends — the reference unpacks the identical rows, so
        per-coordinate poison values match the packed engine's exactly
        (the GaussianPoison analog of `_noise_packed`)."""
        if fault is None or getattr(fault, "poison", None) is None:
            return None
        pack = self._noise_layout()
        if self._noise_valid is None:
            self._noise_valid = pack.valid_mask()
        return fault.poison((pack.rows, LANES), self._noise_valid)

    # -- per-client optimizer state (FedDyn) --------------------------------

    def _ensure_h(self) -> jnp.ndarray:
        """The FedDyn correction state [C_all, R, 128], zeros at first use.
        Device-resident for both backends (the reference updates it with
        eager jnp scatters). NOTE: the buffer covers the full population —
        fleet-scale rosters should not run stateful schemes yet (the
        streamed path moves data cohorts, not optimizer state slabs beyond
        the per-block gather below)."""
        if self._h is None:
            pack = self._noise_layout()
            self._h = jnp.zeros((len(self.clients), pack.rows, LANES),
                                jnp.float32)
        return self._h

    # -- round primitives ---------------------------------------------------

    def _draw_indices(self, count: int) -> np.ndarray:
        """THE batch-index draw — one `choice` call per (round, selected
        client), shared by the per-round path (which gathers on host) and
        the block path (which ships the indices to the on-device gather).
        Keeping the call in one place is what pins both paths to the same
        RNG stream, which the bit-for-bit contract depends on. Takes the
        client's sample COUNT, not the client: the block path over a fleet
        roster draws indices without ever materializing the client's data
        (the cohort prefetcher does that, off-thread)."""
        count = int(count)
        return self.rng.choice(
            count, size=min(self.batch_size, count),
            replace=count < self.batch_size)

    def _client_len(self, n: int) -> int:
        """Sample count of client n without materializing it: rosters
        publish a host-resident `counts` array; plain client lists fall
        back to len()."""
        counts = getattr(self.clients, "counts", None)
        return int(counts[n]) if counts is not None else len(self.clients[n])

    def _sample_batch(
        self, client: ClientData,
    ) -> tuple[jnp.ndarray, jnp.ndarray, np.ndarray]:
        """Draw one mini-batch: (x, y, sample_weights).

        A client smaller than the batch size yields a short batch; when a
        weighted loss is available the batch is padded back to batch_size
        with repeated samples carrying weight 0, so every client's batch is
        stackable and the round stays on the packed path. The RNG stream is
        identical to the unpadded draw (one `choice` call either way)."""
        idx = self._draw_indices(len(client))
        x, y = client.x[idx], client.y[idx]
        n = len(idx)
        if n < self.batch_size and self._weighted_loss is not None:
            pad = self.batch_size - n
            x = np.concatenate([x, np.repeat(x[-1:], pad, axis=0)])
            y = np.concatenate([y, np.repeat(y[-1:], pad, axis=0)])
            sw = np.zeros(self.batch_size, np.float32)
            sw[:n] = 1.0
        else:
            sw = np.ones(n, np.float32)
        return jnp.asarray(x), jnp.asarray(y), sw

    def client_update(
        self, n: int, lam: float,
        batch: tuple | None = None,
    ) -> tuple[PyTree, PyTree, float]:
        """Steps 2-3 for client n: returns (masked gradient, mask, loss)."""
        if lam > 0.0:
            imp = pruning.taylor_importance(self.params, self.global_grad)
            masks = pruning.build_masks(imp, lam, self.prune_spec)
        else:
            masks = jax.tree.map(
                lambda w: jnp.ones_like(w, dtype=jnp.float32), self.params)
        pruned = pruning.apply_masks(self.params, masks)
        if batch is None:
            batch = self._sample_batch(self.clients[n])
        x, y, sw = batch if len(batch) == 3 else (*batch, None)
        if sw is None or sw.all():
            # full batch: the plain mean loss, byte-identical to the seed
            loss, grads = self._grad_fn(pruned, x, y)
        else:
            # ragged client: the same weighted mean the packed engine
            # computes, so the two backends stay bit-for-bit comparable
            loss, grads = self._wgrad_fn(pruned, x, y, jnp.asarray(sw))
        grads = pruning.apply_masks(grads, masks)  # pruned coords not uploaded
        obs.count("d2h")
        return grads, masks, float(loss)

    def _client_update_local(self, n: int, lam: float, batches: list,
                             h_row=None):
        """Eager reference body for the local-update scheme zoo (DESIGN.md
        §14), mirroring the packed step scan op for op: E local steps from
        the pruned start u0 = w*mask, each taking a masked gradient at the
        CURRENT iterate, folding in the scheme's regularizer, accumulating
        the direction into the upload (from a zeros accumulator, so the
        first add normalizes -0.0 exactly like the engine's), and stepping
        `u <- u - eta*d`. Every jnp op here is its own eager dispatch, so
        each product rounds to fp32 exactly where the engine fences it.

        `batches`: the client's E drawn batches in step order. `h_row`:
        the client's packed FedDyn state row (or None); its pytree view is
        an exact gather through the layout pack. Returns (upload tree,
        loss at step 0, packed FedDyn state delta or None)."""
        ls = self.local_scheme
        if lam > 0.0:
            imp = pruning.taylor_importance(self.params, self.global_grad)
            masks = pruning.build_masks(imp, lam, self.prune_spec)
        else:
            masks = jax.tree.map(
                lambda w: jnp.ones_like(w, dtype=jnp.float32), self.params)
        u0 = pruning.apply_masks(self.params, masks)
        u = u0
        acc = jax.tree.map(jnp.zeros_like, u0)
        hm = None
        if h_row is not None:
            hm = pruning.apply_masks(
                self._noise_layout().unpack(jnp.asarray(h_row)), masks)
        coeff = jnp.float32(ls.coeff)
        loss0 = None
        for t, batch in enumerate(batches):
            x, y, sw = batch if len(batch) == 3 else (*batch, None)
            if sw is None or sw.all():
                loss, g = self._grad_fn(u, x, y)
            else:
                loss, g = self._wgrad_fn(u, x, y, jnp.asarray(sw))
            if t == 0:
                obs.count("d2h")
                loss0 = float(loss)
            g = pruning.apply_masks(g, masks)
            if ls.name == "fedavg":
                d = g
            else:
                # coeff*(u - u0): two eager dispatches (sub, then the
                # product) — the rounding sequence the engine's FMA fence
                # reproduces inside its fused graph
                prox = jax.tree.map(lambda a, b: coeff * (a - b), u, u0)
                d = jax.tree.map(lambda gt, p: gt + p, g, prox)
                if ls.stateful:
                    d = jax.tree.map(lambda dt, m: dt - m, d, hm)
            acc = jax.tree.map(lambda a, dt: a + dt, acc, d)
            u = jax.tree.map(lambda ut, dt: ut - self.eta * dt, u, d)
        hd = None
        if ls.stateful:
            alpha = jnp.float32(ls.alpha)
            hd = self._noise_layout().pack(
                jax.tree.map(lambda a, b: alpha * (a - b), u, u0))
        return acc, loss0, hd

    def server_step(self, grads: list[PyTree], noise: PyTree | None = None) -> None:
        """Eqs. (6)-(7): average selected gradients, FedSGD update.
        `noise` (a pytree, `_noise_tree`) models the noisy aggregation
        channel: the server observes mean(g) + noise and both broadcasts
        and updates with it.

        Deliberately eager: each op runs as its own dispatch, so eta*g is
        rounded to fp32 before the subtraction. The packed engine blocks
        FMA contraction of the same pair inside its fused graph, which is
        what makes the two backends bit-identical (see round_engine)."""
        if not grads:
            return
        inv = 1.0 / len(grads)
        g = grads[0]
        for extra in grads[1:]:
            g = jax.tree.map(lambda acc, e: acc + e, g, extra)
        g = jax.tree.map(lambda t: t * inv, g)
        if noise is not None:
            g = jax.tree.map(lambda t, nz: t + nz, g, noise)
        self.global_grad = g
        self.params = jax.tree.map(
            lambda w, gg: w - self.eta * gg.astype(w.dtype), self.params, g)

    def _reference_round(self, selected: list[int], lam_s: np.ndarray,
                         batches: list, s: int = 0, fault=None):
        """Original per-client loop: steps 2-4 with host-side thresholds.

        The fault draw is applied EAGERLY, mirroring the packed engine op
        for op: every selected client still computes its update (identical
        RNG stream), corruption factors scale the upload, uploads that
        never arrived are dropped before aggregation, and — the eager form
        of the engine's always-on isfinite guard — a non-finite upload is
        quarantined host-side. `server_step` over the survivors then
        renormalizes by their count (and early-returns when none survive),
        which is the semantics the packed guard reproduces on device.
        With a robust ``aggregator`` the round instead routes through
        `_reference_robust_round` — the eager mirror of the engine's
        robust reduce over the same bucket-padded packed stack.
        Returns (per-client losses, surviving upload count, agg stat —
        None on the mean path)."""
        if self.aggregator is not None:
            return self._reference_robust_round(selected, lam_s, batches,
                                                s=s, fault=fault)
        grads, losses = [], []
        ok = (np.asarray(fault.upload_ok, bool) if fault is not None
              else np.ones(len(selected), bool))
        cf = fault.corrupt if fault is not None else None
        po = self._poison_stack(fault)
        ls = self.local_scheme
        dyn = ls is not None and ls.stateful
        hbuf = self._ensure_h() if dyn else None
        surv_ids, surv_hds = [], []
        for j, (n, batch) in enumerate(zip(selected, batches)):
            if ls is None:
                g, _, loss = self.client_update(n, float(lam_s[n]),
                                                batch=batch)
                hd = None
            else:
                g, loss, hd = self._client_update_local(
                    n, float(lam_s[n]), batch,
                    h_row=hbuf[n] if dyn else None)
            losses.append(loss)
            if not ok[j]:
                continue                     # the upload never arrived
            if cf is not None:
                g = jax.tree.map(
                    lambda t, c=np.float32(cf[j]): t * c, g)
            if po is not None:
                # applied to EVERY arriving upload (zeros for clean
                # clients), mirroring the engine's stack-wide add — the
                # `g + 0.0` normalization of -0.0 then matches bitwise
                pz = self._noise_layout().unpack(jnp.asarray(po[j]))
                g = jax.tree.map(lambda t, z: t + z, g, pz)
            if _all_finite(g):
                grads.append(g)
                if dyn:
                    # the state only moves for arrived-AND-finite uploads
                    # (post-fault — exactly the engine's cw_eff gate)
                    surv_ids.append(n)
                    surv_hds.append(hd)
        self.server_step(
            grads,
            noise=self._noise_tree(s) if self.channel_noise else None)
        if surv_ids:
            # one scatter-add contribution per surviving row — bitwise the
            # engine's h.at[cid].add (padding rows there contribute exact
            # +0.0, a no-op)
            self._h = hbuf.at[jnp.asarray(np.asarray(surv_ids, np.int32))
                              ].add(-jnp.stack(surv_hds))
        return losses, len(grads), None

    def _reference_robust_round(self, selected: list[int], lam_s: np.ndarray,
                                batches: list, s: int = 0, fault=None):
        """Eager robust round — the reference oracle for a non-mean
        aggregator, mirroring the packed engine op for op over the SAME
        bucket-padded [C_b, R, 128] stack (DESIGN.md §11):

        every selected client's masked gradient is packed at its
        selected-order position (packing is a pure scatter, so the rows are
        bitwise the engine's), faults apply as ``cf * g + poison``, the
        effective weight is ``arrived & finite`` (the eager isfinite
        quarantine), padding rows are zero with weight 0 — the reducers
        are weight-aware and bucket-capacity invariant, so zero padding
        and the engine's replicated-batch padding give identical bits.
        The SAME `Aggregator.reduce` then runs eagerly, and the update is
        the eager form of the engine's fenced inv=1.0 tail: ``ghat (+
        noise)`` becomes the broadcast v and ``w - eta*v`` the step (the
        separate eager multiply rounds exactly like the fence). A round
        with no survivors skips the update (server_step's empty-grads
        early return)."""
        pack = self._noise_layout()
        ok = (np.asarray(fault.upload_ok, bool) if fault is not None
              else np.ones(len(selected), bool))
        cf = fault.corrupt if fault is not None else None
        po = self._poison_stack(fault)
        ls = self.local_scheme
        dyn = ls is not None and ls.stateful
        hbuf = self._ensure_h() if dyn else None
        losses, gps, cws, hds = [], [], [], []
        for j, (n, batch) in enumerate(zip(selected, batches)):
            if ls is None:
                g, _, loss = self.client_update(n, float(lam_s[n]),
                                                batch=batch)
                hd = None
            else:
                g, loss, hd = self._client_update_local(
                    n, float(lam_s[n]), batch,
                    h_row=hbuf[n] if dyn else None)
            losses.append(loss)
            hds.append(hd)
            gp = pack.pack(g)
            if cf is not None:
                gp = gp * jnp.float32(cf[j])
            if po is not None:
                gp = gp + jnp.asarray(po[j])
            fin = _all_finite(gp)
            gps.append(gp)
            cws.append(1.0 if (ok[j] and fin) else 0.0)
        c_b = bucket_capacity(len(selected),
                              max_clients=len(self.clients))
        zero = jnp.zeros((pack.rows, LANES), jnp.float32)
        gps += [zero] * (c_b - len(selected))
        cws += [0.0] * (c_b - len(selected))
        stack = jnp.stack(gps)
        cw = jnp.asarray(np.asarray(cws, np.float32))
        ghat, ast = self.aggregator.reduce(stack, cw)
        n_ok = int(np.asarray(cws).sum())
        if dyn:
            ids = [n for n, c in zip(selected, cws) if c > 0]
            if ids:
                self._h = hbuf.at[jnp.asarray(np.asarray(ids, np.int32))
                                  ].add(-jnp.stack(
                                      [h for h, c in zip(hds, cws)
                                       if c > 0]))
        if n_ok > 0:
            g = pack.unpack(ghat)
            if self.channel_noise:
                g = jax.tree.map(lambda t, nz: t + nz, g,
                                 self._noise_tree(s))
            self.global_grad = g
            self.params = jax.tree.map(
                lambda w, gg: w - self.eta * gg.astype(w.dtype),
                self.params, g)
        return losses, n_ok, ast

    def _round(self, selected: list[int], lam_s: np.ndarray, s: int = 0,
               fault=None):
        """Steps 2-4 for one round; batches are drawn once, in selected
        order, so both backends consume the identical RNG sequence.

        Returns the per-client losses *without* synchronizing: a device
        array on the packed path (materialized lazily by `run`, so rounds
        pipeline on accelerators), a list of floats on the reference path.
        With a weighted loss every batch is padded to batch_size, so ragged
        clients and round-to-round varying selection sizes all stay on the
        packed path (the engine buckets the client axis); the reference
        fallback only fires for custom losses without a weighted form.

        Returns (losses, n_ok, ast): n_ok is the surviving weighted-upload
        count — a lazy device scalar on the packed path (the engine's
        `last_n_ok`), an int on the reference path — materialized with the
        losses to drive the fault counters; ast is the robust aggregator's
        per-round diagnostic count (None on the mean path)."""
        ls = self.local_scheme
        if ls is None:
            batches = [self._sample_batch(self.clients[n]) for n in selected]
            stackable = len({b[0].shape for b in batches}) <= 1
        else:
            # E draws per (round, client), client-major — THE step-batch
            # RNG order, identical on the packed, block, and reference
            # paths (the bit-for-bit contract's multi-step extension)
            batches = [[self._sample_batch(self.clients[n])
                        for _ in range(ls.steps)] for n in selected]
            stackable = len({b[0].shape
                             for bs in batches for b in bs}) <= 1
        if self.backend != "packed" or not stackable:
            if self.backend == "packed":
                self.n_fallback_rounds += 1
            return self._reference_round(selected, lam_s, batches, s=s,
                                         fault=fault)
        lam_sel = np.asarray([lam_s[n] for n in selected], np.float64)
        if ls is None:
            xs = jnp.stack([b[0] for b in batches])
            ys = jnp.stack([b[1] for b in batches])
            sws = np.stack([b[2] for b in batches])
        else:
            xs = jnp.stack([jnp.stack([b[0] for b in bs])
                            for bs in batches])
            ys = jnp.stack([jnp.stack([b[1] for b in bs])
                            for bs in batches])
            sws = np.stack([np.stack([b[2] for b in bs])
                            for bs in batches])
        extra = {}
        if ls is not None and ls.stateful:
            extra = dict(h=self._ensure_h(),
                         client_ids=np.asarray(selected, np.int32))
        self.n_batch_uploads += 1
        self._w, self._v, losses, _, _ = self.engine.round_step(
            self._w, self._v, xs, ys, lam_sel,
            # all-ones weights carry no information: skip the transfer and
            # let the engine materialize them on device
            sample_weights=None if sws.all() else sws,
            noise=self._noise_packed(s) if self.channel_noise else None,
            upload_weights=(fault.upload_ok.astype(np.float32)
                            if fault is not None else None),
            corrupt=fault.corrupt if fault is not None else None,
            poison=self._poison_stack(fault), **extra)
        if extra:
            self._h = self.engine.last_h
        ast = (self.engine.last_agg_stat if self.aggregator is not None
               else None)
        return losses, self.engine.last_n_ok, ast

    # -- block execution ----------------------------------------------------

    def store_nbytes(self) -> int:
        """Estimated device footprint of a REPLICATED ClientStore for this
        trainer's clients (cached; never materializes a roster)."""
        if self._store_nbytes is None:
            self._store_nbytes = estimated_store_nbytes(self.clients)
        return self._store_nbytes

    def store_mode(self) -> str:
        """The resolved client-store policy: "replicated" or "streamed"
        ("auto" keys on the estimated footprint vs device_mem_budget)."""
        if self.client_store != "auto":
            return self.client_store
        return ("replicated" if self.store_nbytes() <= self.device_mem_budget
                else "streamed")

    def check_store_budget(self) -> None:
        """OOM guard: raise the actionable StoreBudgetError when block
        execution would build a replicated store over the device-memory
        budget (an explicit client_store="replicated" on a fleet-scale
        roster — "auto" streams instead). Called by Experiment.build at
        spec time and by _ensure_store right before the H2D transfer."""
        if (self.backend == "packed" and self.rounds_per_dispatch > 1
                and self.store_mode() == "replicated"
                and self.store_nbytes() > self.device_mem_budget):
            raise StoreBudgetError(len(self.clients), self.store_nbytes(),
                                   self.device_mem_budget)

    def _ensure_store(self) -> ClientStore:
        """Build (once) the device-resident dataset store the block path
        gathers batches from; replicated over the engine's mesh when the
        client axis is sharded, so shards never re-transfer the data."""
        if self._store is None:
            self.check_store_budget()
            store = ClientStore.build(self.clients)
            if self.engine is not None and self.engine.mesh is not None:
                store = store.replicated(self.engine.mesh)
            self._store = store
        return self._store

    def _block_key(self, selected: list[int], lam_s: np.ndarray):
        """Homogeneity key for grouping consecutive rounds into one block
        (client-axis bucket, lambda family, drawn batch length) — or None
        when the round cannot take the block path (empty selection, or
        mixed batch lengths without a weighted loss: those rounds fall to
        the per-round path, which handles them exactly as before)."""
        if not selected:
            return None
        lens = [min(self.batch_size, self._client_len(n)) for n in selected]
        if self._weighted_loss is not None:
            blen = self.batch_size       # ragged clients pad to batch_size
        elif len(set(lens)) == 1:
            blen = lens[0]               # uniformly short: packed, no pad
        else:
            return None                  # per-round path -> reference fallback
        ks = np.floor(np.asarray([lam_s[n] for n in selected], np.float64)
                      * self.pack.n_prunable).astype(np.int32)
        shared = bool((ks == ks[0]).all())
        return (self.engine.bucket_size(len(selected)), shared, blen)

    def _plan_blocks(self, infos, boundaries: set, rpd: int,
                     first_round: int = 0) -> dict:
        """Partition the (truncated) schedule into blocks: {start: K}.

        Rounds group while their _block_key matches; a run always ends at
        a boundary round — an eval round or a checkpoint round (both read
        coherent state AFTER that round, so a block may not span one).
        Each homogeneous run is then decomposed into power-of-two chunks
        of at most `rpd` rounds — decomposition rather than padding,
        because a padded round would cost a full round of gradient FLOPs —
        which keeps compiled block lengths on a pow2 ladder
        (<= log2(rpd)+1 distinct K per (bucket, family) pair).
        `first_round` skips already-executed rounds when resuming from a
        checkpoint."""
        blocks: dict[int, int] = {}
        n = len(infos)
        i = first_round
        while i < n:
            key = self._block_key(infos[i][0], infos[i][1])
            if key is None:
                i += 1
                continue
            j = i
            while j < n and self._block_key(infos[j][0], infos[j][1]) == key:
                j += 1
                if (j - 1) in boundaries:
                    break
            start, left = i, j - i
            while left:
                k = 1 << (min(left, rpd).bit_length() - 1)
                blocks[start] = k
                start += k
                left -= k
            i = j
        return blocks

    def _block_cids(self, start: int, n_rounds: int,
                    infos) -> tuple[np.ndarray, np.ndarray]:
        """The block's stacked GLOBAL client ids [K, c_max] (trainer
        padding included — rows pad by replicating the round's last real
        client, exactly what _exec_block executes) plus per-round real
        counts [K]. Selection-pure — consumes NO RNG — so the cohort store
        can plan every block's cohort before execution starts, which is
        what makes prefetch schedules (and resume) deterministic."""
        sels = [infos[start + k][0] for k in range(n_rounds)]
        counts = np.asarray([len(s) for s in sels], np.int64)
        c_max = int(counts.max())
        cids = np.empty((n_rounds, c_max), np.int32)
        for k, sel in enumerate(sels):
            cids[k, :len(sel)] = sel
            cids[k, len(sel):] = sel[-1]
        return cids, counts

    def _exec_block(self, start: int, n_rounds: int, infos,
                    out: dict) -> None:
        """Run rounds [start, start+n_rounds) as one engine.block_step
        dispatch; per-round loss slices (still device arrays) land in
        `out`. Indices are drawn from self.rng with the identical
        `choice` calls — same order, same arguments — that the per-round
        path's _sample_batch would make, so the batch sequence is
        bit-for-bit the reference one."""
        with obs.span("trainer.draw"):
            sels = [infos[start + k][0] for k in range(n_rounds)]
            cids, counts = self._block_cids(start, n_rounds, infos)
            c_max = int(counts.max())
            blen = self._block_key(sels[0], infos[start][1])[2]
            # multi-step schemes draw an E-deep index stack per (round,
            # client) — same RNG calls, same round -> client -> step order
            # as the per-round path, so the batch stream stays bit-for-bit
            # shared
            ls = self.local_scheme
            if ls is None:
                idxs = np.empty((n_rounds, c_max, blen), np.int32)
                sw = np.ones((n_rounds, c_max, blen), np.float32)
            else:
                idxs = np.empty((n_rounds, c_max, ls.steps, blen),
                                np.int32)
                sw = np.ones((n_rounds, c_max, ls.steps, blen),
                             np.float32)
            lams = np.empty((n_rounds, c_max), np.float64)
            # host-drawn fault masks join the stacked [K, C] schedule
            # operands (ones = clean defaults, exact no-ops on device)
            # whenever a fault model is active — one upload per block, zero
            # per-round H2D
            fault_on = self.fault_model is not None
            pos = None
            if fault_on:
                fw = np.ones((n_rounds, c_max), np.float32)
                cfa = np.ones((n_rounds, c_max), np.float32)
                # the additive-poison stack is built lazily: zero until
                # some round in the block actually flagged a byzantine
                # client, so clean blocks never allocate the [K, C, R, L]
                # operand
                if any(infos[start + k][6] is not None
                       and infos[start + k][6].poison is not None
                       for k in range(n_rounds)):
                    pack = self._noise_layout()
                    pos = np.zeros((n_rounds, c_max, pack.rows, LANES),
                                   np.float32)
            any_ragged = False
            for k, sel in enumerate(sels):
                lam_s = infos[start + k][1]
                if fault_on:
                    fault = infos[start + k][6]
                    if fault is not None:
                        fw[k, :len(sel)] = np.asarray(fault.upload_ok,
                                                      np.float32)
                        if fault.corrupt is not None:
                            cfa[k, :len(sel)] = fault.corrupt
                        if pos is not None and fault.poison is not None:
                            pos[k, :len(sel)] = self._poison_stack(fault)
                for j, n in enumerate(sel):
                    lams[k, j] = lam_s[n]
                    for t in range(1 if ls is None else ls.steps):
                        row = idxs[k, j] if ls is None else idxs[k, j, t]
                        swr = sw[k, j] if ls is None else sw[k, j, t]
                        draw = self._draw_indices(self._client_len(n))
                        m = len(draw)
                        if m < blen:     # ragged: repeat last drawn sample
                            row[:m] = draw          # with weight 0, exactly
                            row[m:] = draw[-1]      # like _sample_batch
                            swr[m:] = 0.0
                            any_ragged = True
                        else:
                            row[:] = draw
                c_k = len(sel)           # pad rows to c_max by replicating
                idxs[k, c_k:] = idxs[k, c_k - 1]  # the round's last client
                sw[k, c_k:] = sw[k, c_k - 1]      # (cids padded identically
                lams[k, c_k:] = lam_s[sel[-1]]    # by _block_cids)
        dyn = ls is not None and ls.stateful
        slab_ids = None
        h_arg = None
        if self._cohorts is not None:
            # streamed path: this block's prefetched cohort stands in for
            # the full store; global ids remap to cohort-local rows (the
            # index DRAWS above are layout-independent, so the RNG stream
            # — and the bitwise contract — is untouched)
            store = self._cohorts.acquire(start)
            cids = store.remap(cids)
            if dyn:
                # FedDyn state slab, cohort-swap protocol: slice the rows
                # of this cohort's clients in cohort-row order (remapped
                # cids index the slab exactly like the data buffers);
                # padded slab rows replicate the last client — remapped
                # ids never reference them, and only the unique prefix is
                # scattered back, so the slab round-trip is an exact copy
                ids = np.asarray(store.ids_by_shard[0], np.int64)
                rows = len(store.counts)
                gidx = np.concatenate(
                    [ids, np.full(rows - len(ids), ids[-1], np.int64)])
                slab_ids = ids
                h_arg = self._ensure_h()[jnp.asarray(gidx)]
        else:
            store = self._ensure_store()
            if dyn:
                h_arg = self._ensure_h()
        noises = (np.stack([self._noise_packed(start + k)
                            for k in range(n_rounds)])
                  if self.channel_noise else None)
        self._w, self._v, losses, _ = self.engine.block_step(
            self._w, self._v, store, cids, idxs, lams, counts,
            sample_weights=sw if any_ragged else None, noises=noises,
            upload_weights=fw if fault_on else None,
            corrupt=cfa if fault_on else None, poisons=pos, h=h_arg)
        if dyn:
            if slab_ids is None:
                self._h = self.engine.last_h
            else:
                self._h = self._h.at[jnp.asarray(slab_ids)].set(
                    self.engine.last_h[:len(slab_ids)])
        n_oks = self.engine.last_n_ok        # [K] lazy survivor counts
        asts = (self.engine.last_agg_stat    # [K] lazy reducer diagnostics
                if self.aggregator is not None else None)
        self.n_block_dispatches += 1
        # slicing a value the device has not finished waits for it: this
        # span holds most of the host's wait for a block
        with obs.span("trainer.slice"):
            for k in range(n_rounds):
                out[start + k] = (losses[k, : int(counts[k])], n_oks[k],
                                  asts[k] if asts is not None else None)
        # fires right after the dispatch returns: the block's losses are
        # still lazy device arrays, so hooks here never force a sync
        for cb in self._callbacks:
            cb.on_block_end(start, n_rounds, self)

    # -- full run -----------------------------------------------------------

    def run(
        self,
        schedule: Schedule,
        sp: SystemParams,
        h_up: np.ndarray,
        h_down: np.ndarray,
        *,
        eval_fn: Callable[[PyTree], tuple[float, float]] | None = None,
        eval_every: int = 10,
        stop_delay: float | None = None,
        stop_energy: float | None = None,
        callbacks: Sequence = (),
        start_round: int = 0,
    ) -> list[RoundMetrics]:
        """Execute the schedule. eval_fn(params) -> (test_loss, test_acc).

        ``eval_fn``/``eval_every`` are the LEGACY direct-use evaluation
        path, kept for hand-wired callers; new code should drive runs
        through the experiment API (repro.api), whose RunSpec configures
        them and layers the callback protocol on top.

        ``callbacks`` take objects following the repro.api.Callback
        protocol. Hooks fire at MATERIALIZATION points only — they never
        force a per-round device sync (see repro.api.callbacks):

          * ``on_round_end(m, self)`` — once per round, in order, batched
            at the next materialization point (m.train_loss is set);
          * ``on_eval(m, self)`` — at eval rounds, after eval_fn;
          * ``on_block_end(start, k, self)`` — after each block dispatch;
          * ``on_checkpoint(m, self)`` — at rounds where ``m.round %
            cb.checkpoint_every == 0``. Those rounds become block
            boundaries and materialization points, so trainer state there
            is exactly the state after round m.round (what bit-for-bit
            checkpoint/resume requires).

        ``start_round`` skips execution of rounds before it (their
        wireless bookkeeping is still computed, keeping cumulative
        counters, stop truncation, and eval cadence bitwise identical to
        an uninterrupted run): with params/global-grad/batch-RNG restored
        from a checkpoint taken after round ``start_round - 1``, the
        remaining trajectory replays bit-for-bit on fp32 — the resume
        contract the experiment API builds on. The returned history covers
        only the executed rounds.

        Per-round train losses are kept as device arrays and materialized
        lazily (at eval/checkpoint points and at the end of the run): the
        packed round then never blocks on a device->host sync, so
        consecutive rounds pipeline on accelerators instead of
        serializing on `float(loss)`.

        With ``rounds_per_dispatch > 1`` (packed backend) the schedule is
        consumed in multi-round BLOCKS: the wireless bookkeeping and stop
        conditions are schedule-pure, so they are precomputed, the
        surviving rounds are partitioned into homogeneous blocks ending at
        eval/checkpoint points (`_plan_blocks`), and each block runs as a
        single `RoundEngine.block_step` dispatch with batches sampled on
        device — no per-round dispatch, host sync, or batch upload.
        Per-round metrics, eval cadence, stop behavior, and the training
        trajectory (bit-for-bit on fp32 single-device) are unchanged.
        """
        callbacks = tuple(callbacks)
        self._callbacks = callbacks
        history: list[RoundMetrics] = []
        # rounds whose train_loss / survivor count are still unmaterialized
        # device values: (metrics, losses, n_ok, fault draw, agg stat)
        pending: list[tuple[RoundMetrics, Any, Any, Any, Any]] = []

        def materialize():
            if not pending:
                return
            with obs.span("trainer.materialize") as sp:
                # wait for the device before the first read (which would block
                # on it anyway), so the wait and the reads are timed apart
                with obs.span("trainer.wait"):
                    jax.block_until_ready([
                        x for p in pending for x in p[1:]
                        if isinstance(x, jax.Array)])
                for m, losses, n_ok, fault, ast in pending:
                    mask = (np.asarray(fault.upload_ok, bool)
                            if fault is not None else None)
                    if losses is not None:
                        # float64 mean over the synced fp32 values —
                        # identical to the old eager np.mean over a list of
                        # floats; restricted to the uploads that arrived
                        # (the server never observes a dropped client's
                        # loss)
                        if isinstance(losses, jax.Array):
                            sp.count("d2h")
                        arr = np.asarray(losses, np.float64)
                        if mask is not None:
                            arr = arr[mask]
                        m.train_loss = (float(arr.mean()) if arr.size
                                        else float("nan"))
                    n_sel = len(m.selected)
                    n_up = int(mask.sum()) if mask is not None else n_sel
                    m.n_faulted = n_sel - n_up
                    if n_ok is not None:
                        if isinstance(n_ok, jax.Array):
                            sp.count("d2h")
                        ok = int(n_ok)
                        # on the robust path the quarantine count folds the
                        # reducer's survivor arithmetic the same way: n_ok is
                        # still "weighted clients whose upload stayed finite"
                        m.n_quarantined = max(0, n_up - ok)
                        if n_sel and ok == 0:
                            self.fault_counters["n_skipped_rounds"] += 1
                    self.fault_counters["n_dropped"] += m.n_faulted
                    self.fault_counters["n_quarantined"] += m.n_quarantined
                    # corrupt-but-FINITE arrivals: damage the isfinite
                    # guard cannot see (satellite of the quarantine's
                    # documented blind spot) — counted host-side from the
                    # draw so reports stop under-counting corruption. `.get`
                    # keeps restores of pre-PR-7 checkpoints (no such key)
                    # working.
                    if fault is not None:
                        ncf = 0
                        arrived = (mask if mask is not None
                                   else np.ones(n_sel, bool))
                        if fault.corrupt is not None:
                            cfv = np.asarray(fault.corrupt, np.float64)
                            ncf += int((arrived & np.isfinite(cfv)
                                        & (cfv != 1.0)).sum())
                        flags = getattr(fault.poison, "flags", None)
                        if flags is not None:
                            ncf += int((arrived
                                        & np.asarray(flags, bool)).sum())
                        self.fault_counters["n_corrupt_finite"] = (
                            self.fault_counters.get("n_corrupt_finite", 0)
                            + ncf)
                    if ast is not None and self.aggregator is not None:
                        if isinstance(ast, jax.Array):
                            sp.count("d2h")
                        m.n_agg_adjusted = int(ast)
                        sf = self.aggregator.stat_field
                        self.agg_counters[sf] = (self.agg_counters.get(sf, 0)
                                                 + m.n_agg_adjusted)
                    for cb in callbacks:
                        cb.on_round_end(m, self)
            pending.clear()

        with obs.span("trainer.plan"):
            n_rounds = schedule.a.shape[0]
            # Per-round host bookkeeping is schedule-pure (independent of
            # training state), so compute it — and the stop-condition
            # truncation — up front; the block partition then only has to
            # respect eval boundaries.
            infos = []
            cum_t = cum_e = 0.0
            for s in range(n_rounds):
                a_s, lam_s = schedule.a[s], schedule.lam[s]
                p_s, f_s = schedule.power[s], schedule.freq[s]
                selected = [int(i) for i in np.flatnonzero(a_s > 0)]
                # per-client tau_n + tau^_n feed both the round deadline
                # (the gated max is round_delay's expression verbatim —
                # bitwise identical bookkeeping) and the straggler fault
                # model's judgment against that deadline
                per = per_client_delay(lam_s, p_s, f_s, h_up, h_down, sp)
                gated = np.asarray(a_s, np.float64) * per
                d = float(gated.max()) if gated.size else 0.0
                e = round_energy(a_s, lam_s, p_s, f_s, h_up, h_down, sp)
                cum_t += d
                cum_e += e
                fault = None
                if self.fault_model is not None and selected:
                    sel_arr = np.asarray(selected, int)
                    fault = self.fault_model.draw(
                        s, len(self.clients), sel_arr,
                        delays=per[sel_arr], deadline=d)
                infos.append((selected, lam_s, d, e, cum_t, cum_e, fault))
                if stop_delay is not None and cum_t >= stop_delay:
                    break
                if stop_energy is not None and cum_e >= stop_energy:
                    break

            # Checkpoint rounds (repro.api.Callback.checkpoint_every):
            # these become materialization points and block boundaries so
            # the hook observes state coherent at exactly that round.
            def _ckpt_cbs(s: int) -> list:
                return [cb for cb in callbacks
                        if getattr(cb, "checkpoint_every", None)
                        and s % cb.checkpoint_every == 0]

            ckpt_rounds = {s for s in range(start_round, len(infos))
                           if _ckpt_cbs(s)}

            blocks: dict[int, int] = {}
            if self.rounds_per_dispatch > 1 and self.backend == "packed":
                boundaries = set(ckpt_rounds)
                if eval_fn is not None:
                    boundaries |= {s for s in range(len(infos))
                                   if s % eval_every == 0}
                    boundaries.add(n_rounds - 1)
                blocks = self._plan_blocks(infos, boundaries,
                                           self.rounds_per_dispatch,
                                           first_round=start_round)

            self.streaming = False
            self._cohorts = None
            if blocks and self.store_mode() == "streamed":
                # cohort plans are a pure function of the block partition
                # (selection-only, no RNG), so a resumed run — same infos,
                # same first_round — replays the identical cohort schedule
                # bit for bit; prefetch of the first two cohorts starts
                # here, before any round executes
                self._cohorts = CohortStore(
                    self.clients, mesh=self.engine.mesh,
                    shards=self.engine.shards,
                    bucket_size=self.engine.bucket_size,
                    max_clients=len(self.clients),
                    counters=self.fleet_counters)
                self._cohorts.schedule(
                    [(st, *self._block_cids(st, blocks[st], infos))
                     for st in sorted(blocks)])
                self.streaming = True

        block_losses: dict[int, Any] = {}
        try:
            for s, (selected, lam_s, d, e, cum_t, cum_e,
                    fault) in enumerate(infos):
                if s < start_round:
                    continue   # already executed before the checkpoint
                if s in blocks:
                    self._exec_block(s, blocks[s], infos, block_losses)
                if s in block_losses:
                    losses, n_ok, ast = block_losses.pop(s)
                elif selected:
                    with obs.span("trainer.round"):
                        losses, n_ok, ast = self._round(selected, lam_s,
                                                        s=s, fault=fault)
                else:
                    losses = n_ok = ast = None
                m = RoundMetrics(
                    round=s,
                    train_loss=float("nan"),
                    selected=selected,
                    mean_lambda=(float(lam_s[selected].mean())
                                 if selected else 0.0),
                    delay=d, energy=e,
                    cumulative_delay=cum_t, cumulative_energy=cum_e,
                )
                pending.append((m, losses, n_ok, fault, ast))
                is_eval = (eval_fn is not None
                           and (s % eval_every == 0 or s == n_rounds - 1))
                if is_eval or s in ckpt_rounds:
                    materialize()  # eval/ckpt sync anyway; drain the backlog
                    if is_eval:
                        m.test_loss, m.test_accuracy = eval_fn(self.params)
                        for cb in callbacks:
                            cb.on_eval(m, self)
                    for cb in _ckpt_cbs(s):
                        cb.on_checkpoint(m, self)
                history.append(m)
            materialize()
        finally:
            # a raising hook (e.g. a simulated kill after a checkpoint)
            # must not leave stale callback refs on the long-lived trainer;
            # the cohort store's prefetch threads and device buffers go
            # with it (self.streaming stays set for result surfacing)
            self._callbacks = ()
            if self._cohorts is not None:
                self._cohorts.close()
                self._cohorts = None
        return history
