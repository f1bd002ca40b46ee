"""Streamed cohort store: fleet-scale populations through the packed engine.

`ClientStore` (client_store.py) materializes EVERY client's padded rows on
every device — right for edge-scale federations, impossible for the
100k-1M-client fleets the paper's selection machinery is motivated by. The
cohort store keeps the full population host-side (a lazy `FleetRoster` or a
plain client list) and moves only each block's *cohort* — the union of
clients the schedule actually selects in that block — to device:

  * the trainer registers the whole run's block plans up front (the block
    partition is schedule-pure, so cohort k+1 is known while block k
    trains);
  * a background thread packs cohort k+1's padded ``[C_cohort, N_max, ...]``
    buffers and commits them with `jax.device_put` (+ `block_until_ready`)
    while the main thread's block-k dispatch runs — double-buffered
    prefetch, the PR-3 zero-per-round-sync discipline one level up. At most
    two cohorts are ever device-resident (current + prefetching), so peak
    device bytes track the COHORT size, not the population;
  * `acquire(start)` joins the prefetch (recording stall seconds), drops the
    previous cohort's buffers, kicks off the next prefetch, and returns a
    `Cohort` whose ``remap`` translates global client ids to cohort-local
    rows.

Bitwise contract: cohort rows are byte-copies of the rows a replicated
`ClientStore` would hold, local-id gathers read the identical elements, and
the host-drawn index protocol (core/federated._draw_indices) is untouched —
streaming moves data, never randomness — so a streamed run's trajectory is
bit-for-bit the replicated run's (tests/test_fleet.py asserts it on 1 device
and the forced-4-device leg).

Shard placement: on a mesh the cohort store composes with the engine's
client-axis shard_map instead of replicating. Client-axis position j of a
bucketed block belongs to mesh shard ``j // (c_bucket / shards)``; each
shard's sub-cohort (clients appearing at its positions, trainer padding
included) packs into its slice of one ``[shards * rows_per_shard, ...]``
buffer committed with ``PartitionSpec("data")`` — each device holds ONLY its
clients' rows. Row counts sit on the same pow2 bucket ladder as the client
axis (capped at the population), so trace counts keep the PR-2/PR-3 bounds.
Engine-side, a purely-local shard_map gather (no collective) replaces the
replicated-store gather (round_engine._make_block_impl(sharded_store=True)).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Sequence

import jax
import numpy as np

from repro import obs
from repro.core.round_engine import bucket_capacity


def fleet_counters_zero() -> dict:
    """The streaming observability counters, in one place so the trainer,
    checkpoints, and RunResult.summary['fleet'] agree on the keys."""
    return {"n_cohort_swaps": 0, "h2d_bytes": 0,
            "prefetch_stall_s": 0.0, "peak_cohort_bytes": 0}


@dataclasses.dataclass
class Cohort:
    """One block's device-resident client rows (ClientStore-shaped).

    ``x``/``y`` are the padded device buffers `RoundEngine.block_step`
    gathers from; ``counts`` the per-row real sample counts (zero on
    padding rows, which are never gathered). ``sharded`` routes the engine
    to the shard-local gather; ``ids_by_shard`` holds each shard's sorted
    global client ids (one entry when unsharded) for ``remap``."""

    x: Any
    y: Any
    counts: np.ndarray
    sharded: bool
    ids_by_shard: list
    per: int                  # client-axis positions per shard (sharded only)
    start: int                # first schedule round of the owning block
    nbytes: int               # device bytes (== H2D bytes of the commit)

    def remap(self, cids: np.ndarray) -> np.ndarray:
        """Global client ids [K, C] -> cohort-local row ids, position-wise.

        Unsharded: one sorted id table. Sharded: position j maps through
        shard ``j // per``'s table into SHARD-LOCAL row space (the engine's
        gather runs inside shard_map, so each shard indexes its own
        ``rows_per_shard`` rows)."""
        if not self.sharded:
            return np.searchsorted(self.ids_by_shard[0],
                                   cids).astype(np.int32)
        k, c_max = cids.shape
        out = np.empty((k, c_max), np.int32)
        for s, ids in enumerate(self.ids_by_shard):
            lo, hi = s * self.per, min((s + 1) * self.per, c_max)
            if lo >= c_max:
                break
            out[:, lo:hi] = np.searchsorted(ids, cids[:, lo:hi])
        return out

    def gather(self, cids, idx):
        """ClientStore.gather over cohort-LOCAL ids (unsharded layout)."""
        return self.x[cids[:, None], idx], self.y[cids[:, None], idx]


class CohortStore:
    """Plans, prefetches, and hands out per-block cohorts (see module doc).

    One instance serves one `FederatedTrainer.run` (plans are a property of
    that run's schedule); the trainer rebuilds it per run and `close`s it
    in the run's finally block.
    """

    def __init__(self, clients: Sequence, *, mesh=None, shards: int = 1,
                 bucket_size: Callable[[int], int] | None = None,
                 max_clients: int | None = None,
                 counters: dict | None = None):
        self.clients = clients
        self.mesh = mesh
        self.shards = int(shards) if mesh is not None else 1
        self._bucket_size = bucket_size or (lambda n: int(n))
        self.max_clients = int(max_clients or len(clients))
        counts = getattr(clients, "counts", None)
        if counts is None:
            counts = [len(c) for c in clients]
        self.counts = np.asarray(counts, np.int64)
        self.n_max = int(self.counts.max())
        x0 = np.asarray(clients[0].x)
        self._xshape, self._xdtype = x0.shape[1:], x0.dtype
        self._ydtype = np.asarray(clients[0].y).dtype
        self.counters = counters if counters is not None \
            else fleet_counters_zero()
        self._lock = threading.Lock()
        self._resident = 0                 # bytes of built, un-dropped cohorts
        self._plans: list[tuple] = []      # (start, cids [K, C], counts [K])
        self._order: dict[int, int] = {}
        self._pending: dict[int, tuple] = {}   # plan idx -> (thread, box)
        self._live: dict[int, Cohort] = {}

    # -- planning / prefetch lifecycle --------------------------------------

    def schedule(self, plans: Sequence[tuple]) -> None:
        """Register the run's blocks in execution order and start
        prefetching the first two cohorts. Each plan is ``(start_round,
        cids [K, c_max] global ids incl. trainer padding, counts [K])`` —
        exactly the arrays `_exec_block` will pass to the engine, which is
        what makes the cohort schedule a pure function of the block plan
        (and therefore bit-for-bit reproducible across resumes)."""
        self._plans = list(plans)
        self._order = {int(p[0]): i for i, p in enumerate(self._plans)}
        self._launch(0)
        self._launch(1)

    def _launch(self, i: int) -> None:
        if i >= len(self._plans) or i in self._pending or i in self._live:
            return
        box: dict = {}
        th = threading.Thread(target=self._worker, args=(i, box), daemon=True)
        self._pending[i] = (th, box)
        th.start()

    def _worker(self, i: int, box: dict) -> None:
        try:
            cohort = self._build(*self._plans[i])
            with self._lock:
                self._resident += cohort.nbytes
                self.counters["peak_cohort_bytes"] = max(
                    self.counters["peak_cohort_bytes"], self._resident)
            box["cohort"] = cohort
        except BaseException as e:          # surfaced at acquire()
            box["error"] = e

    def acquire(self, start: int) -> Cohort:
        """Block on cohort `start` (stall time is the prefetch miss cost),
        retire earlier cohorts, and prefetch the next plan."""
        i = self._order[int(start)]
        for j in [j for j in self._live if j != i]:
            dropped = self._live.pop(j)
            with self._lock:
                self._resident -= dropped.nbytes
        if i not in self._live:
            self._launch(i)                 # miss: first block, or no prefetch
            th, box = self._pending.pop(i)
            with obs.span("cohort.wait") as wait:
                th.join()
            self.counters["prefetch_stall_s"] += wait.t1 - wait.t0
            err = box.get("error")
            if err is not None:
                raise err
            self._live[i] = box["cohort"]
        cohort = self._live[i]
        self.counters["n_cohort_swaps"] += 1
        self.counters["h2d_bytes"] += cohort.nbytes
        self._launch(i + 1)
        return cohort

    def close(self) -> None:
        """Join outstanding prefetches and drop every device buffer."""
        for th, _ in self._pending.values():
            th.join()
        self._pending.clear()
        self._live.clear()
        self._plans = []
        self._order = {}
        with self._lock:
            self._resident = 0

    # -- cohort construction ------------------------------------------------

    def _pack_into(self, x: np.ndarray, y: np.ndarray, rcounts: np.ndarray,
                   ids: np.ndarray, row0: int) -> None:
        """Copy clients `ids` into rows [row0, row0+len(ids)) of the padded
        host buffers — byte-copies of the rows a replicated ClientStore
        holds for the same clients (the bitwise anchor)."""
        for k, cid in enumerate(np.asarray(ids, np.int64)):
            c = self.clients[int(cid)]
            n = int(self.counts[cid])
            x[row0 + k, :n] = c.x
            y[row0 + k, :n] = c.y
            rcounts[row0 + k] = n

    def _alloc(self, rows: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        x = np.zeros((rows, self.n_max) + self._xshape, self._xdtype)
        y = np.zeros((rows, self.n_max), self._ydtype)
        return x, y, np.zeros(rows, np.int64)

    def _build(self, start: int, cids: np.ndarray,
               counts: np.ndarray) -> Cohort:
        cids = np.asarray(cids)
        if self.mesh is None or self.shards <= 1:
            return self._build_flat(int(start), cids)
        return self._build_sharded(int(start), cids, np.asarray(counts))

    def _build_flat(self, start: int, cids: np.ndarray) -> Cohort:
        ids = np.unique(cids).astype(np.int64)
        # pow2 row bucket capped at the population: distinct cohort sizes
        # reuse block traces on the same ladder the client axis does
        rows = max(len(ids), bucket_capacity(
            len(ids), shards=1, max_clients=self.max_clients))
        x, y, rcounts = self._alloc(rows)
        self._pack_into(x, y, rcounts, ids, 0)
        dx, dy = jax.device_put(x), jax.device_put(y)
        dx.block_until_ready()
        dy.block_until_ready()
        return Cohort(x=dx, y=dy, counts=rcounts, sharded=False,
                      ids_by_shard=[ids], per=int(cids.shape[1]),
                      start=start, nbytes=int(dx.nbytes + dy.nbytes))

    def _build_sharded(self, start: int, cids: np.ndarray,
                       counts: np.ndarray) -> Cohort:
        from jax.sharding import NamedSharding, PartitionSpec
        k, c_max = cids.shape
        c_b = self._bucket_size(int(counts.max()))
        per = max(1, c_b // self.shards)
        ids_by_shard = []
        for s in range(self.shards):
            lo, hi = s * per, min((s + 1) * per, c_max)
            cols = (cids[:, lo:hi] if hi > lo
                    else np.empty((k, 0), cids.dtype))
            ids_by_shard.append(np.unique(cols).astype(np.int64))
        cap = -(-self.max_clients // self.shards)
        rps = max(1, max(len(i) for i in ids_by_shard))
        rps = max(rps, bucket_capacity(rps, shards=1, max_clients=cap))
        x, y, rcounts = self._alloc(self.shards * rps)
        for s, ids in enumerate(ids_by_shard):
            self._pack_into(x, y, rcounts, ids, s * rps)
        sharding = NamedSharding(self.mesh, PartitionSpec("data"))
        dx = jax.device_put(x, sharding)
        dy = jax.device_put(y, sharding)
        dx.block_until_ready()
        dy.block_until_ready()
        return Cohort(x=dx, y=dy, counts=rcounts, sharded=True,
                      ids_by_shard=ids_by_shard, per=per, start=start,
                      nbytes=int(dx.nbytes + dy.nbytes))
