"""Jitted public wrappers around the Pallas kernels.

These adapt model-layout tensors to kernel layouts, handle padding to tile
multiples, and fall back to interpret mode off-TPU automatically.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as _fa
from repro.kernels import pruning_mask as _pm
from repro.kernels import ssd_chunk as _sc

PyTree = Any
LANES = _pm.LANES


# ---------------------------------------------------------------------------
# Flash attention: model layout [B, S, H, D] <-> kernel layout [B, H, S, D]
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("causal", "window", "cap",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=0, cap=0.0,
                    block_q=128, block_k=128):
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = _fa.flash_attention(qt, kt, vt, causal=causal, window=window, cap=cap,
                            block_q=block_q, block_k=block_k)
    return jnp.swapaxes(o, 1, 2)


# ---------------------------------------------------------------------------
# Pruning: arbitrary pytree leaves -> padded [R, LANES] tiles
# ---------------------------------------------------------------------------

def _to_tiles(x: jnp.ndarray) -> tuple[jnp.ndarray, int]:
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % LANES
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANES), n


def _from_tiles(t: jnp.ndarray, n: int, shape, dtype) -> jnp.ndarray:
    return t.reshape(-1)[:n].reshape(shape).astype(dtype)


@jax.jit
def importance_and_mask(w: jnp.ndarray, v: jnp.ndarray, threshold):
    """Fused eq.-(4) importance + keep-mask for one tensor (any shape)."""
    wt, n = _to_tiles(w)
    vt, _ = _to_tiles(v)
    q, m = _pm.importance_mask_2d(wt, vt, threshold,
                                  block_rows=_packed_block_rows(wt.shape[0]))
    return (_from_tiles(q, n, w.shape, jnp.float32),
            _from_tiles(m, n, w.shape, jnp.float32))


@jax.jit
def masked_update(w: jnp.ndarray, g: jnp.ndarray, mask: jnp.ndarray, eta):
    """Fused pruned-SGD step for one tensor."""
    wt, n = _to_tiles(w)
    gt, _ = _to_tiles(g)
    mt, _ = _to_tiles(mask)
    out = _pm.masked_update_2d(wt, gt, mt, eta,
                               block_rows=_packed_block_rows(wt.shape[0]))
    return _from_tiles(out, n, w.shape, w.dtype)


# ---------------------------------------------------------------------------
# Packed-buffer entry points (core/packing.py layout: [R, 128], R % block == 0)
#
# The packed round engine hands whole-model buffers straight to the kernels —
# no per-leaf flatten/pad, one launch per model per operation. Each entry
# point takes `impl`:
#
#   * "pallas" — the fused Pallas kernels (interpret mode off-TPU);
#   * "xla"    — an op-for-op jnp mirror with the same reduction order
#                (bit-identical results); faster on CPU, where interpret-mode
#                Pallas adds per-launch emulation overhead;
#   * "auto"   — pallas on TPU, xla elsewhere.
# ---------------------------------------------------------------------------

# Bytes one [clients, block, 128] fp32 block of a client-stacked operand may
# take. Pallas double-buffers every blocked operand inside the 16 MiB scoped
# VMEM of a TPU v5e core, so a fixed 256-row block overflows it once
# clients * 256 rows pass 8k (C=64 for the masks and aggregates). The rank
# sort also keeps 2C live [block, 128] arrays (keys and values) through its
# network, hence its smaller slab.
_STACK_BYTES = 4 << 20
_SORT_STACK_BYTES = 1 << 20


def _packed_block_rows(rows: int, clients: int = 1,
                       stack_bytes: int = _STACK_BYTES) -> int:
    """Row block for a packed kernel: the largest multiple of 8 up to 256
    that divides `rows` and keeps a [clients, block, 128] fp32 slab within
    `stack_bytes` (never below 8 rows); the full row count when no multiple
    of 8 divides it (a full-extent block is always a legal tile)."""
    fits = [b for b in (256, 128, 64, 32, 16, 8) if rows % b == 0]
    if not fits:
        return rows
    small = [b for b in fits if clients * b * LANES * 4 <= stack_bytes]
    return small[0] if small else fits[-1]


def _resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown impl {impl!r}")
    return impl


@functools.partial(jax.jit, static_argnames=("impl",))
def packed_importance_mask(w, v, prunable, threshold, *, impl="auto"):
    """Shared-threshold path: one fused importance+mask pass for the whole
    packed model (the single-tensor kernel, previously orphaned, applied to
    the [R, 128] packed buffer). Protected/padding coordinates (prunable == 0)
    are always kept. Returns (importance fp32, mask fp32), both [R, 128]."""
    if _resolve_impl(impl) == "pallas":
        q, keep = _pm.importance_mask_2d(
            w, v, threshold, block_rows=_packed_block_rows(w.shape[0]))
    else:
        q = jnp.square(w.astype(jnp.float32) * v.astype(jnp.float32))
        keep = (q >= threshold).astype(jnp.float32)
    return q, jnp.where(prunable > 0, keep, 1.0)


@functools.partial(jax.jit, static_argnames=("impl",))
def packed_importance_masks(w, v, prunable, thresholds, *, impl="auto"):
    """Per-client-threshold path: (importance [R,128], masks [C,R,128])."""
    if _resolve_impl(impl) == "pallas":
        return _pm.importance_mask_batched(
            w, v, prunable, thresholds,
            block_rows=_packed_block_rows(w.shape[0], thresholds.shape[0]))
    q = jnp.square(w.astype(jnp.float32) * v.astype(jnp.float32))
    keep = (q[None] >= thresholds[:, None, None]).astype(jnp.float32)
    return q, jnp.where(prunable[None] > 0, keep, 1.0)


@functools.partial(jax.jit, static_argnames=("impl",))
def packed_exponent_histogram(q, prunable, *, impl="auto"):
    """256-bin histogram of fp32 exponent bytes over valid coordinates.

    The coarse first pass of ``kth_smallest_threshold(coarse="histogram")``
    (core/round_engine.py): bin b counts coordinates with
    ``bits(q) >> 23 == b`` and prunable > 0. ``impl="pallas"`` runs the
    tiled kernel (per-lane bin counts, compare-reduce instead of scatter)
    and needs the packed [R, 128*k] layout — any other shape raises rather
    than silently leaving the kernel; "xla" is the scatter-add mirror,
    exact everywhere but ~130 ns/element on CPU (why coarse="auto" keeps
    plain bisection there, see ROADMAP)."""
    if _resolve_impl(impl) == "pallas":
        if q.ndim != 2 or q.shape[1] % LANES:
            raise ValueError(
                f"exponent histogram kernel needs a packed [R, {LANES}*k] "
                f"buffer, got shape {q.shape}")
        return _pm.exponent_histogram(
            q, prunable, block_rows=_packed_block_rows(q.shape[0]))
    bits = jax.lax.bitcast_convert_type(q.reshape(-1), jnp.int32)
    valid = prunable.reshape(-1) > 0
    return jnp.zeros((256,), jnp.int32).at[bits >> 23].add(
        valid.astype(jnp.int32))


def _rounded_product(eta, g):
    """eta * g rounded to fp32 *before* any consumer sees it.

    A plain `w - eta * g` inside a jitted graph is contracted by XLA:CPU
    into an FMA, skipping the product's intermediate rounding and breaking
    bit-parity with the eager reference update (two separate dispatches).
    Neither `optimization_barrier` nor multi-use outputs survive fusion
    duplication, but a while loop whose trip count the compiler cannot
    prove to be 1 does: the product is materialized in the loop carry, so
    the subtraction can only consume the rounded value. The bound is
    derived from runtime data (1, or 2 on a NaN input — the body is
    idempotent) precisely so it is not constant-foldable."""
    n = jnp.int32(1) + jnp.isnan(g[0, 0]).astype(jnp.int32)

    def body(carry):
        i, _ = carry
        return i + 1, eta * g

    _, step = jax.lax.while_loop(lambda c: c[0] < n, body,
                                 (jnp.int32(0), jnp.zeros_like(g)))
    return step


# public name: callers outside the fused aggregate (e.g. tests) sometimes
# need the bare fence
rounded_step = _rounded_product


def packed_local_delta(g, u, u0, coeff, hm=None):
    """Per-local-step update direction for the scheme zoo (DESIGN.md §14).

    d = g + coeff*(u - u0) [- hm], with the regularizer product FMA-fenced:
    the eager reference computes ``coeff * (u - u0)`` as its own dispatch
    (rounded to fp32) before adding g, so the fused graph must materialize
    the rounded product too or the `g + coeff*(u-u0)` add contracts into an
    FMA and drifts by an ulp.  The subtraction ``u - u0`` and the optional
    ``- hm`` (FedDyn's masked correction state) are single ops on both
    backends — exact, no fence needed.

    coeff == 0.0 would fence a zero product; callers skip the call for the
    plain-FedAvg direction instead of passing 0.
    """
    d = g + _rounded_product(jnp.float32(coeff), u - u0)
    if hm is not None:
        d = d - hm
    return d


def packed_apply_mean_update(w, gsum, inv, eta, noise=None):
    """g = gsum * inv (+ noise), then the FMA-fenced FedSGD step:
    (w', g, step).

    The single tail shared by the weighted aggregate's XLA mirror and the
    sharded round engine (which applies it after the cross-shard psum) —
    one copy of the fence-sensitive sequence, not three.

    `noise` models a noisy aggregation channel (the server only observes
    mean(g) + noise): it is added BEFORE the update and becomes part of the
    broadcast g. The mean product is fenced on that path so the add cannot
    be FMA-contracted with it — the eager reference sequence (scale, then
    add, two dispatches) rounds each op, and bit-parity requires the fused
    graph to do the same."""
    if noise is None:
        g = gsum * inv
    else:
        g = _rounded_product(inv, gsum) + noise
    step = _rounded_product(eta, g)
    return (w.astype(jnp.float32) - step).astype(w.dtype), g, step


def packed_client_quarantine(grads, cweights, inv):
    """Always-on non-finite upload guard (DESIGN.md §10): per-client
    isfinite flags over the stacked masked gradients [C, R, 128], returning
    ``(cw_eff, inv_eff, n_ok, alive)`` for the weighted aggregate.

    * cw_eff  — cweights with non-finite clients zeroed. With every upload
      finite (the default path) this is ``cweights * 1.0`` — the exact same
      0/1 values, so the downstream weighted sum is bitwise unchanged.
    * inv_eff — the mean's 1/n. When nobody is quarantined it passes the
      HOST-computed `inv` through untouched (the bit-for-bit contract's
      value); with survivors missing it renormalizes to 1/n_ok on device —
      which equals the host convention ``float32(1/n)`` exactly, because
      binary64->binary32 double rounding is safe for division (p=53 >=
      2*24+2); all clients quarantined yields 0 (the caller skips the
      update entirely via `alive`).
    * n_ok    — int32 count of surviving (weighted AND finite) clients,
      surfaced per round as RoundEngine.last_n_ok -> the n_quarantined /
      n_skipped_rounds counters.
    * alive   — scalar bool, False when no client survives: the caller
      carries (w, v) unchanged through the round (params untouched).

    Zero-weight clients (client-axis padding, host-dropped faults) are
    excluded from both counts by construction (their cw is already 0).

    Contract — the guard detects NON-FINITE uploads only. A *finite*
    corrupted or adversarial upload (`CorruptUpload(mode="scale")`,
    `SignFlip`, `ScaledMalicious` — core/faults.py) passes unflagged BY
    DESIGN: finiteness is the only property checkable without a model of
    honest gradients, so the quarantine is a crash barrier, not a defense.
    Bounding finite adversaries is the robust aggregators' job
    (core/aggregators.py / packed_robust_aggregate); reporting keeps the
    two failure classes distinct (`summary["faults"]["n_quarantined"]` vs
    `n_corrupt_finite` — core/federated.py)."""
    cw = cweights.astype(jnp.float32)
    fin = jnp.isfinite(grads).all(axis=(1, 2))
    cw_eff = cw * fin.astype(jnp.float32)
    n_w = cw.sum()
    n_ok = cw_eff.sum()
    inv_eff = jnp.where(
        n_ok == n_w, jnp.asarray(inv, jnp.float32),
        jnp.where(n_ok > 0.0, 1.0 / jnp.maximum(n_ok, 1.0), 0.0))
    return cw_eff, inv_eff, n_ok.astype(jnp.int32), n_ok > 0.0


def packed_weighted_grad_sum(grads, cweights):
    """sum_c cweights[c] * grads[c] in client-stack order, [C,R,128]->[R,128].

    Zero-weight (padding) clients are *skipped* via `where` rather than
    multiplied in, so garbage gradients from replicated padding batches can
    never reach the update (not even as NaN), and weight-1 clients
    accumulate as `acc + 1.0*g` — bit-identical to the unweighted
    reference sum. Used per shard by the sharded round engine (the psum
    over shards is the round's single collective) and by the XLA mirror of
    the weighted aggregate."""
    acc = jnp.zeros(grads.shape[1:], jnp.float32)
    cw = cweights.astype(jnp.float32)
    for c in range(grads.shape[0]):          # static unroll: same summation
        acc = jnp.where(cw[c] > 0.0,          # order as the reference
                        acc + cw[c] * grads[c].astype(jnp.float32), acc)
    return acc


_INT32_MAX = 2**31 - 1


def _order_keys(x):
    """Monotone int32 total-order keys for fp32 values: ``b ^ ((b >> 31) &
    0x7fffffff)`` on the bit pattern (an involution) maps IEEE-754 floats
    to integers that compare like the values, negatives included — the
    same bit-pattern machinery the PR-1 k-th-smallest threshold search
    uses, here driving client-axis rank selection. -0.0 orders strictly
    below +0.0 (distinct keys), so ties always carry identical bits and
    any sort — stable, unstable, or a sort network — produces the same
    per-rank values."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))


def packed_client_rank_sort(grads, cweights, *, impl="auto"):
    """Per-coordinate rank sort along the client axis of a [C, R, 128]
    gradient stack; zero-weight (padding / quarantined) clients are keyed
    to INT32_MAX so every rank < n_valid holds a real value and ranks >=
    n_valid hold don't-cares the weight-aware reducers never read. "pallas"
    runs the odd-even transposition-network kernel
    (pruning_mask.client_rank_sort); "xla" a stable `lax.sort` on the same
    keys — both emit bitwise-identical per-rank values (ties share bit
    patterns). Valid lanes cannot collide with the sentinel: a key of
    INT32_MAX is a NaN bit pattern, and non-finite clients are quarantined
    to weight 0 before rank selection."""
    if _resolve_impl(impl) == "pallas":
        return _pm.client_rank_sort(
            grads, cweights, block_rows=_packed_block_rows(
                grads.shape[1], grads.shape[0], _SORT_STACK_BYTES))
    g = grads.astype(jnp.float32)
    key = _order_keys(g)
    invalid = ~(cweights.astype(jnp.float32) > 0.0)
    key = jnp.where(invalid[:, None, None], jnp.int32(_INT32_MAX), key)
    _, sv = jax.lax.sort((key, g), dimension=0, num_keys=1, is_stable=True)
    return sv


def _sorted_median(sorted_vals, nn):
    """Midpoint of ranks (nn-1)//2 and nn//2 of a rank-sorted stack — the
    median over the nn valid lanes ((a+a)*0.5 is exact for odd counts, so
    odd-count medians are the rank value bit-for-bit)."""
    lo = jax.lax.dynamic_index_in_dim(sorted_vals, (nn - 1) // 2, axis=0,
                                      keepdims=False)
    hi = jax.lax.dynamic_index_in_dim(sorted_vals, nn // 2, axis=0,
                                      keepdims=False)
    return (lo + hi) * 0.5


def packed_robust_aggregate(grads, cweights, *, kind, impl="auto",
                            beta=0.1, tau=None, f=1, m=None):
    """Weight-aware Byzantine-robust reduction of a packed gradient stack.

    grads: [C, R, 128] stacked per-client masked gradients; cweights: [C]
    effective validity weights — 0 marks client-axis padding, host-dropped
    uploads, AND quarantined (non-finite) clients, exactly the `cw_eff`
    ops.packed_client_quarantine emits. Returns ``(ghat, stat)``: the
    robust aggregate [R, 128] fp32 (already survivor-normalized — the
    caller applies it with inv=1.0 through the FMA-fenced update tail) and
    an int32 diagnostic count (clients trimmed / clipped / excluded this
    round, 0 for an all-faulted round).

    Weight-aware contract: zero-weight lanes are excluded from ranks,
    norms, and distance scores — their (garbage) values cannot influence
    any output bit — and every mean renormalizes over the lanes that
    actually contributed. All client-axis reductions are ordered
    where-accumulates (or monolithic dots) over the valid prefix, so the
    result is invariant to the bucket capacity C and bitwise identical
    between the packed graph, the eager reference backend, and the
    all-gather sharded path (DESIGN.md §11).

    Kinds (core/aggregators.py wraps these as registry entries):
      * "coord_median"     — coordinate-wise median over valid lanes via
        rank sort (Pallas sort network on TPU, stable lax.sort mirror
        elsewhere — `packed_client_rank_sort`).
      * "trimmed_mean"     — drop the floor(beta*n) smallest and largest
        values per coordinate, mean the middle; beta in [0, 0.5).
      * "norm_clip"        — scale client c by min(1, tau/||g_c||); tau
        None/0 = adaptive median-of-norms over valid clients.
      * "multi_krum"       — Blanchard-style selection: per-client score =
        sum of its n-f-2 smallest squared distances to other valid
        clients (one Gram matmul, invalid pairs +inf), keep the m
        lowest-scoring clients (default n-f), mean them.
    """
    g = grads.astype(jnp.float32)
    cw = cweights.astype(jnp.float32)
    valid = cw > 0.0
    n = valid.astype(jnp.int32).sum()
    nn = jnp.maximum(n, 1)
    c_b = g.shape[0]
    if kind == "coord_median":
        sv = packed_client_rank_sort(g, cw, impl=impl)
        ghat = _sorted_median(sv, nn)
        # clients outside the (one- or two-element) median window
        stat = jnp.maximum(n - 2 + (n & 1), 0)
    elif kind == "trimmed_mean":
        if not 0.0 <= beta < 0.5:
            raise ValueError(f"trimmed_mean beta must be in [0, 0.5), "
                             f"got {beta}")
        sv = packed_client_rank_sort(g, cw, impl=impl)
        t = jnp.floor(jnp.float32(beta)
                      * nn.astype(jnp.float32)).astype(jnp.int32)
        keep = jnp.maximum(nn - 2 * t, 1)
        acc = jnp.zeros(g.shape[1:], jnp.float32)
        for c in range(c_b):                 # static unroll: rank order
            acc = jnp.where((c >= t) & (c < nn - t), acc + sv[c], acc)
        ghat = acc * (1.0 / keep.astype(jnp.float32))
        stat = jnp.minimum(2 * t, n)
    elif kind == "norm_clip":
        # per-client L2 norms as monolithic dots (deterministic reduction
        # order for a given [C, R, L] shape on every backend)
        sq = jnp.einsum("crl,crl->c", g, g)
        norms = jnp.sqrt(sq)
        if tau is None or float(tau) <= 0.0:
            key = jnp.where(valid, _order_keys(norms),
                            jnp.int32(_INT32_MAX))
            _, sn = jax.lax.sort((key, norms), dimension=0, num_keys=1,
                                 is_stable=True)
            lo = jax.lax.dynamic_index_in_dim(sn, (nn - 1) // 2, axis=0,
                                              keepdims=False)
            hi = jax.lax.dynamic_index_in_dim(sn, nn // 2, axis=0,
                                              keepdims=False)
            tau_t = (lo + hi) * 0.5
        else:
            tau_t = jnp.float32(tau)
        # a quarantined client's NaN norm fails both compares: factor 1.0,
        # and its weight is already 0 in the sum
        clipped = valid & (norms > tau_t)
        factor = jnp.where(norms > tau_t, tau_t / norms, jnp.float32(1.0))
        gsum = packed_weighted_grad_sum(g * factor[:, None, None], cw)
        ghat = gsum * (1.0 / nn.astype(jnp.float32))
        stat = clipped.astype(jnp.int32).sum()
    elif kind == "multi_krum":
        if int(f) < 0:
            raise ValueError(f"multi_krum f must be >= 0, got {f}")
        if m is not None and int(m) < 1:
            raise ValueError(f"multi_krum m must be >= 1, got {m}")
        gm = g.reshape(c_b, -1)
        gram = gm @ gm.T                     # one dot: all pairwise inners
        sq = jnp.diagonal(gram)
        # 2*gram is exact (x2 never rounds), so the expression cannot be
        # perturbed by FMA contraction of the subtract
        d2 = sq[:, None] + sq[None, :] - 2.0 * gram
        inf = jnp.float32(jnp.inf)
        pair_ok = valid[:, None] & valid[None, :] \
            & ~jnp.eye(c_b, dtype=bool)
        sd = jnp.sort(jnp.where(pair_ok, d2, inf), axis=1)
        # each valid row has n-1 finite entries, and k_nb <= n-2, so no
        # +inf sentinel can reach a valid client's score
        k_nb = jnp.clip(n - jnp.int32(int(f)) - 2, 1, max(c_b - 1, 1))
        score = jnp.zeros((c_b,), jnp.float32)
        for j in range(c_b):                 # static unroll: rank order
            score = jnp.where(j < k_nb, score + sd[:, j], score)
        score = jnp.where(valid, score, inf)
        # valid clients first even on tied +inf scores (the sentinel is
        # strictly above the +inf key), stable on remaining ties
        skey = jnp.where(valid, _order_keys(score), jnp.int32(_INT32_MAX))
        m_sel = jnp.clip(
            n - jnp.int32(int(f)) if m is None else jnp.int32(int(m)),
            1, nn)
        _, order = jax.lax.sort(
            (skey, jnp.arange(c_b, dtype=jnp.int32)), dimension=0,
            num_keys=1, is_stable=True)
        sel = jnp.zeros((c_b,), jnp.float32).at[order].set(
            (jnp.arange(c_b) < m_sel).astype(jnp.float32))
        gsum = packed_weighted_grad_sum(g, sel * cw)
        ghat = gsum * (1.0 / m_sel.astype(jnp.float32))
        stat = jnp.maximum(n - m_sel, 0)
    else:
        raise ValueError(f"unknown robust aggregate kind {kind!r}")
    return ghat, stat.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("impl",))
def packed_fedsgd_update(w, grads, eta, *, impl="auto"):
    """Fused eqs. (6)-(7): average stacked masked gradients [C,R,128] and
    apply the FedSGD step, returning (w', mean_grad, step).

    The "xla" path reproduces the eager reference loop bit-for-bit (same
    summation order, FMA-fenced update — see `_rounded_product`). The
    "pallas" kernel keeps the update fully fused in one pass; on real TPU
    hardware the contraction there may differ from the reference by 1 ulp."""
    if _resolve_impl(impl) == "pallas":
        return _pm.fedsgd_aggregate(
            w, grads, eta,
            block_rows=_packed_block_rows(w.shape[0], grads.shape[0]))
    g = grads[0].astype(jnp.float32)
    for c in range(1, grads.shape[0]):       # same summation order as the
        g = g + grads[c].astype(jnp.float32)  # kernel / reference trainer
    g = g * (1.0 / grads.shape[0])
    step = _rounded_product(eta, g)
    return (w.astype(jnp.float32) - step).astype(w.dtype), g, step


@functools.partial(jax.jit, static_argnames=("impl",))
def packed_fedsgd_update_weighted(w, grads, cweights, inv, eta, *,
                                  impl="auto"):
    """Weighted eqs. (6)-(7): g = (sum_c cw[c]*grads[c]) * inv, w' = w -
    eta*g, returning (w', g, step). The bucketed round engine's aggregate:
    cweights marks real clients (1) vs client-axis padding (0) and inv =
    1/#real is host-computed, so one compiled graph serves every selected
    count in a bucket. With 0/1 weights this reproduces
    `packed_fedsgd_update` — and hence the eager reference loop — bit for
    bit on the real-client prefix (same summation order, `1.0*g` exact,
    same FMA-fenced update; see `packed_weighted_grad_sum`)."""
    if _resolve_impl(impl) == "pallas":
        return _pm.fedsgd_aggregate_weighted(
            w, grads, cweights, inv, eta,
            block_rows=_packed_block_rows(w.shape[0], grads.shape[0]))
    return packed_apply_mean_update(
        w, packed_weighted_grad_sum(grads, cweights), inv, eta)


@functools.partial(jax.jit, static_argnames=("impl",))
def packed_masked_update(w, g, mask, eta, *, impl="auto"):
    """Fused (w - eta*g)*mask on a packed buffer (masked_update_2d, one
    launch for the whole model). Not used by the round engine — the
    FedSGD server update never masks w (see packed_fedsgd_update); this is
    the packed form of the per-leaf `masked_update` for pruned-checkpoint
    workflows (launch/train.py style)."""
    if _resolve_impl(impl) == "pallas":
        return _pm.masked_update_2d(
            w, g, mask, eta, block_rows=_packed_block_rows(w.shape[0]))
    return ((w.astype(jnp.float32) - eta * g.astype(jnp.float32))
            * mask).astype(w.dtype)


# ---------------------------------------------------------------------------
# SSD: full sequence via kernel-per-chunk + host scan for the recurrence
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_chunked_pallas(x, b, c, dt, a_log, *, chunk=128):
    """Drop-in for models.ssm.ssd_chunked's core (no D-skip, zero init state).

    x [B,S,H,P], b/c [B,S,N], dt [B,S,H] -> (y [B,S,H,P], final [B,H,P,N])."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    if s % q:
        raise ValueError(f"seq {s} must divide chunk {q}")
    nc = s // q
    xr = jnp.moveaxis(x.reshape(bsz, nc, q, h, p), 1, 0)
    br = jnp.moveaxis(b.reshape(bsz, nc, q, n), 1, 0)
    cr = jnp.moveaxis(c.reshape(bsz, nc, q, n), 1, 0)
    dtr = jnp.moveaxis(dt.reshape(bsz, nc, q, h), 1, 0)

    def body(state, xs):
        xc, bc, cc, dtc = xs
        y_intra, st_contrib, dec = _sc.ssd_chunk(xc, bc, cc, dtc, a_log)
        # inter-chunk term: y_inter[s] = C_s . state * exp(acum_s)
        a = -jnp.exp(a_log.astype(jnp.float32))
        acum = jnp.cumsum(dtc.astype(jnp.float32) * a, axis=1)  # [B,q,H]
        y_inter = jnp.einsum("bqn,bhpn,bqh->bqhp",
                             cc.astype(jnp.float32), state, jnp.exp(acum))
        state_new = state * dec[..., None, None] \
            + jnp.swapaxes(st_contrib, -1, -2)       # [B,H,P,N]
        return state_new, y_intra.astype(jnp.float32) + y_inter

    state0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    final, ys = jax.lax.scan(body, state0, (xr, br, cr, dtr))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, p).astype(x.dtype)
    return y, final


@functools.partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k, v, pos, *, block_k=512):
    """Flash-decoding kernel: q [B,1,Hq,D] (model layout), k/v [B,S,Hkv,D],
    pos = valid cache length. Returns [B,1,Hq,D]."""
    from repro.kernels import decode_attention as _da
    qt = jnp.swapaxes(q, 1, 2)            # [B,Hq,1,D]
    o = _da.decode_attention(qt, k, v, pos, block_k=block_k)
    return jnp.swapaxes(o, 1, 2)
