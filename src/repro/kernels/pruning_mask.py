"""Pallas kernels for the paper's pruning hot spot (eq. 4 over O(10^9) weights).

Fused kernels, all tiled [BLOCK_R, 128] (lane-width aligned for the VPU):

  * importance_mask: Q = (w * v)^2 and keep-mask (Q >= threshold) in one pass
    — one read of (w, v), two writes; the unfused jnp version materializes Q
    twice (once for the threshold compare, once for the mask multiply).
  * masked_update:  w' = (w - eta * g) * mask — the pruned-FedSGD server
    update (eq. 7) fused with mask application, saving one full parameter
    read+write per round.

  * importance_mask_batched: the packed-engine generalization of
    importance_mask — one threshold per client plus a prunable-coordinate
    mask, emitting every per-client keep-mask from a single read of (w, v).
  * fedsgd_aggregate: eqs. (6)-(7) fused — sum the stacked per-client
    gradients, average, and take the FedSGD step in one launch, replacing
    the O(clients) `jax.tree.map` accumulation.
  * fedsgd_aggregate_weighted: the bucketed/sharded generalization — each
    stacked gradient carries a per-client validity weight (0 for padding
    clients on the bucketed client axis, 1 for real ones; fractional
    weights supported for weighted FedAvg), and the mean divisor 1/C is an
    operand instead of a shape-derived constant, so one compiled launch
    serves every selected-client count in the bucket.

Per-leaf inputs of arbitrary shape are flattened and padded to tiles by
ops.py; the packed round engine (core/packing.py + core/round_engine.py)
hands whole-model [R, 128] buffers to the batched/aggregate kernels directly
— one launch per model instead of one per leaf (DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128

# Scalar operands (thresholds, eta, 1/C, client weights) live in SMEM and
# are read whole by every grid step: a kernel may only load from VMEM or
# SMEM refs, never from an ANY-space (HBM) ref.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _row_blocks(r: int, c: int, block_rows: int,
                interpret: bool | None) -> tuple[int, bool]:
    """Validate an [r, c] packed operand against its row block; returns
    (block rows, interpret) — interpret mode everywhere but on a TPU."""
    if c % LANES:
        raise ValueError(f"last dim must be a multiple of {LANES}")
    br = min(block_rows, r)
    if r % br:
        raise ValueError(f"rows {r} must divide block {br}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return br, interpret


def _importance_mask_kernel(w_ref, v_ref, thr_ref, q_ref, m_ref):
    w = w_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    q = jnp.square(w * v)
    q_ref[...] = q
    m_ref[...] = (q >= thr_ref[0]).astype(jnp.float32)


def importance_mask_2d(w, v, threshold, *, block_rows: int = 256,
                       interpret: bool | None = None):
    """w, v: [R, 128*k]; threshold scalar -> (importance fp32, mask fp32)."""
    r, c = w.shape
    br, interpret = _row_blocks(r, c, block_rows, interpret)
    thr = jnp.asarray([threshold], jnp.float32)
    grid = (r // br,)
    spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    return pl.pallas_call(
        _importance_mask_kernel,
        name="importance_mask",
        grid=grid,
        in_specs=[spec, spec, _SMEM],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((r, c), jnp.float32),
                   jax.ShapeDtypeStruct((r, c), jnp.float32)],
        interpret=interpret,
    )(w, v, thr)


def _importance_mask_batched_kernel(w_ref, v_ref, pr_ref, thr_ref,
                                    q_ref, m_ref):
    w = w_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    pr = pr_ref[...] > 0
    q = jnp.square(w * v)
    q_ref[...] = q
    for c in range(m_ref.shape[0]):          # static unroll over clients
        keep = (q >= thr_ref[c]).astype(jnp.float32)
        m_ref[c] = jnp.where(pr, keep, 1.0)


def importance_mask_batched(w, v, prunable, thresholds, *,
                            block_rows: int = 256,
                            interpret: bool | None = None):
    """Per-client masks from one read of the packed buffers.

    w, v, prunable: [R, 128*k]; thresholds: [C] fp32 (one per client).
    Returns (importance fp32 [R, 128*k], masks fp32 [C, R, 128*k]); mask is 1
    wherever `prunable` is 0 (protected / padding coordinates are kept)."""
    r, c = w.shape
    n_clients = thresholds.shape[0]
    br, interpret = _row_blocks(r, c, block_rows, interpret)
    thr = thresholds.astype(jnp.float32)
    spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    mspec = pl.BlockSpec((n_clients, br, c), lambda i: (0, i, 0))
    return pl.pallas_call(
        _importance_mask_batched_kernel,
        name="importance_mask_batched",
        grid=(r // br,),
        in_specs=[spec, spec, spec, _SMEM],
        out_specs=[spec, mspec],
        out_shape=[jax.ShapeDtypeStruct((r, c), jnp.float32),
                   jax.ShapeDtypeStruct((n_clients, r, c), jnp.float32)],
        interpret=interpret,
    )(w, v, prunable, thr)


def _fedsgd_aggregate_kernel(w_ref, g_ref, eta_ref, o_ref, gm_ref, st_ref):
    acc = g_ref[0].astype(jnp.float32)
    for c in range(1, g_ref.shape[0]):       # static unroll: same summation
        acc = acc + g_ref[c].astype(jnp.float32)   # order as the reference
    g = acc * (1.0 / g_ref.shape[0])
    gm_ref[...] = g
    # The step eta*g is written to its own output: giving the multiply a
    # second consumer stops the compiler from contracting it with the
    # subtraction into an FMA, so the update rounds exactly like the eager
    # reference loop (bit-for-bit reproducibility contract).
    step = eta_ref[0] * g
    st_ref[...] = step
    o_ref[...] = (w_ref[...].astype(jnp.float32) - step).astype(o_ref.dtype)


def fedsgd_aggregate(w, grads, eta, *, block_rows: int = 256,
                     interpret: bool | None = None):
    """Eqs. (6)-(7) fused on packed buffers.

    w: [R, 128*k]; grads: [C, R, 128*k] stacked per-client (already masked)
    gradients. Returns (updated w, mean gradient fp32, applied step
    eta*mean fp32), all [R, 128*k], in one launch — the mean doubles as the
    next round's broadcast v."""
    r, c = w.shape
    n_clients = grads.shape[0]
    br, interpret = _row_blocks(r, c, block_rows, interpret)
    eta_arr = jnp.asarray([eta], jnp.float32)
    spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    gspec = pl.BlockSpec((n_clients, br, c), lambda i: (0, i, 0))
    return pl.pallas_call(
        _fedsgd_aggregate_kernel,
        name="fedsgd_aggregate",
        grid=(r // br,),
        in_specs=[spec, gspec, _SMEM],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((r, c), w.dtype),
                   jax.ShapeDtypeStruct((r, c), jnp.float32),
                   jax.ShapeDtypeStruct((r, c), jnp.float32)],
        interpret=interpret,
    )(w, grads, eta_arr)


def _fedsgd_aggregate_weighted_kernel(w_ref, g_ref, cw_ref, sc_ref,
                                      o_ref, gm_ref, st_ref):
    acc = jnp.zeros(w_ref.shape, jnp.float32)
    for c in range(g_ref.shape[0]):          # static unroll: same summation
        wc = cw_ref[c]                       # order as the reference; the
        # `where` (not acc + 0*g) skips zero-weight clients entirely, so a
        # padding client's gradient can never leak in — not even as a NaN —
        # and `acc + 1.0*g` keeps the 0/1 case bit-identical to the
        # unweighted kernel on the real-client prefix.
        acc = jnp.where(wc > 0.0,
                        acc + wc * g_ref[c].astype(jnp.float32), acc)
    g = acc * sc_ref[0]
    gm_ref[...] = g
    # The step eta*g is written to its own output: giving the multiply a
    # second consumer stops the compiler from contracting it with the
    # subtraction into an FMA, so the update rounds exactly like the eager
    # reference loop (bit-for-bit reproducibility contract).
    step = sc_ref[1] * g
    st_ref[...] = step
    o_ref[...] = (w_ref[...].astype(jnp.float32) - step).astype(o_ref.dtype)


def fedsgd_aggregate_weighted(w, grads, cweights, inv, eta, *,
                              block_rows: int = 256,
                              interpret: bool | None = None):
    """Weighted eqs. (6)-(7) fused on packed buffers.

    w: [R, 128*k]; grads: [C, R, 128*k] stacked per-client (already masked)
    gradients; cweights: [C] per-client weights (0 = padding client);
    inv: scalar 1/sum(cweights) (host-computed so the mean matches the
    reference's 1/len(grads) exactly). Returns (updated w, weighted mean
    gradient fp32, applied step eta*mean fp32) in one launch — the mean
    doubles as the next round's broadcast v."""
    r, c = w.shape
    n_clients = grads.shape[0]
    br, interpret = _row_blocks(r, c, block_rows, interpret)
    cw = jnp.asarray(cweights, jnp.float32)
    scal = jnp.stack([jnp.asarray(inv, jnp.float32),
                      jnp.asarray(eta, jnp.float32)])
    spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    gspec = pl.BlockSpec((n_clients, br, c), lambda i: (0, i, 0))
    return pl.pallas_call(
        _fedsgd_aggregate_weighted_kernel,
        name="fedsgd_aggregate_weighted",
        grid=(r // br,),
        in_specs=[spec, gspec, _SMEM, _SMEM],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((r, c), w.dtype),
                   jax.ShapeDtypeStruct((r, c), jnp.float32),
                   jax.ShapeDtypeStruct((r, c), jnp.float32)],
        interpret=interpret,
    )(w, grads, cw, scal)


def _client_rank_sort_kernel(g_ref, cw_ref, o_ref):
    """Per-coordinate client-axis sort for the robust reducers.

    Loads the [C, br, c] gradient block, maps each lane to the monotone
    int32 total-order key of its fp32 bits (the PR-1 bit-pattern trick:
    ``b ^ ((b >> 31) & 0x7fffffff)`` orders like the float value), replaces
    every zero-weight client's keys with INT32_MAX so padding / quarantined
    lanes sort strictly after all real values (valid lanes can never reach
    the sentinel — non-finite uploads are quarantined to weight 0 first),
    and runs an odd-even transposition network over the STATIC client axis
    — C compare-exchange passes of lane-parallel selects, no data-dependent
    control flow. Rank r of the output holds the r-th smallest valid value
    per coordinate; ranks >= n_valid hold don't-care values the weight-
    aware reducers never read. Ties carry identical bit patterns, so the
    network's output is bitwise equal to a stable sort's."""
    n_clients = g_ref.shape[0]
    sentinel = jnp.int32(2**31 - 1)
    vals, keys = [], []
    for i in range(n_clients):
        v = g_ref[i].astype(jnp.float32)
        b = jax.lax.bitcast_convert_type(v, jnp.int32)
        k = b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
        vals.append(v)
        keys.append(jnp.where(cw_ref[i] > 0.0, k, sentinel))
    for p in range(n_clients):
        for i in range(p % 2, n_clients - 1, 2):
            ki, kj, vi, vj = keys[i], keys[i + 1], vals[i], vals[i + 1]
            swap = ki > kj
            keys[i] = jnp.where(swap, kj, ki)
            keys[i + 1] = jnp.where(swap, ki, kj)
            vals[i] = jnp.where(swap, vj, vi)
            vals[i + 1] = jnp.where(swap, vi, vj)
    for i in range(n_clients):
        o_ref[i] = vals[i]


def client_rank_sort(grads, cweights, *, block_rows: int = 256,
                     interpret: bool | None = None):
    """Client-axis rank sort on packed gradient stacks.

    grads: [C, R, 128*k] stacked per-client gradients; cweights: [C]
    validity weights (0 = padding / quarantined). Returns the [C, R, 128*k]
    fp32 stack sorted per coordinate along the client axis, zero-weight
    clients last — the shared first stage of `coord_median` and
    `trimmed_mean` (kernels/ops.packed_robust_aggregate)."""
    c_clients, r, c = grads.shape
    br, interpret = _row_blocks(r, c, block_rows, interpret)
    cw = jnp.asarray(cweights, jnp.float32)
    gspec = pl.BlockSpec((c_clients, br, c), lambda i: (0, i, 0))
    return pl.pallas_call(
        _client_rank_sort_kernel,
        name="client_rank_sort",
        grid=(r // br,),
        in_specs=[gspec, _SMEM],
        out_specs=gspec,
        out_shape=jax.ShapeDtypeStruct((c_clients, r, c), jnp.float32),
        interpret=interpret,
    )(grads, cw)


def _exponent_histogram_kernel(q_ref, pr_ref, hist_ref):
    """256-bin histogram over the exponent byte of non-negative fp32 q.

    Counts are kept per (bin, lane): row r of the block adds the one-hot
    compare ``bins == byte[r]`` — the (1, L) row broadcast down the 256
    bin sublanes — so the kernel needs no scatter-add (which XLA:CPU
    serializes at ~130 ns/element and TPU lowers poorly for int32), no
    reshape and no cross-lane reduction. Invalid coordinates carry byte
    -1, which matches no bin; everything stays int32, because Mosaic
    cannot relayout the bool masks a flattening reshape would need. Grid
    steps run in order ("arbitrary"), so the running total in `hist_ref`
    (same output block every step) is race-free; the caller sums the
    lanes."""
    rows, width = q_ref.shape
    chunk = min(rows, 8)
    while rows % chunk:
        chunk -= 1
    bins = jax.lax.broadcasted_iota(jnp.int32, (256, width), 0)

    def body(c, acc):
        rs = pl.ds(c * chunk, chunk)
        bits = jax.lax.bitcast_convert_type(
            q_ref[rs, :].astype(jnp.float32), jnp.int32)
        byte = jnp.where(pr_ref[rs, :] > 0, bits >> 23, -1)
        for r in range(chunk):
            acc = acc + jnp.where(bins == byte[r:r + 1, :], 1, 0)
        return acc

    acc = jax.lax.fori_loop(0, rows // chunk, body,
                            jnp.zeros((256, width), jnp.int32))

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = acc

    @pl.when(pl.program_id(0) > 0)
    def _accum():
        hist_ref[...] += acc


def exponent_histogram(q, prunable, *, block_rows: int = 256,
                       interpret: bool | None = None):
    """Counts of valid coordinates per fp32 exponent byte.

    q (non-negative fp32), prunable: [R, 128*k] -> [256] int32, where bin
    b counts coordinates with ``bits(q) >> 23 == b`` and prunable > 0 —
    the coarse first pass of `kth_smallest_threshold(coarse="histogram")`
    (core/round_engine.py), whose cumulative sum pins the top 8 bits of
    the k-th smallest importance in one data scan."""
    r, c = q.shape
    br, interpret = _row_blocks(r, c, block_rows, interpret)
    spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    hist = pl.pallas_call(
        _exponent_histogram_kernel,
        name="exponent_histogram",
        grid=(r // br,),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((256, c), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((256, c), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(q, prunable)
    return hist.sum(axis=1)


def _masked_update_kernel(w_ref, g_ref, m_ref, eta_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    o_ref[...] = ((w - eta_ref[0] * g) * m).astype(o_ref.dtype)


def masked_update_2d(w, g, mask, eta, *, block_rows: int = 256,
                     interpret: bool | None = None):
    """Fused (w - eta g) * mask on [R, 128*k] tiles."""
    r, c = w.shape
    br, interpret = _row_blocks(r, c, block_rows, interpret)
    eta_arr = jnp.asarray([eta], jnp.float32)
    spec = pl.BlockSpec((br, c), lambda i: (i, 0))
    return pl.pallas_call(
        _masked_update_kernel,
        name="masked_update",
        grid=(r // br,),
        in_specs=[spec, spec, spec, _SMEM],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, c), w.dtype),
        interpret=interpret,
    )(w, g, mask, eta_arr)
