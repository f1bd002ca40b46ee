"""Device-resident round engine: API, kernel impl parity, client-axis
strategies, bucketed/ragged/sharded rounds, and the perf harness itself.

The sharded tests need a multi-device host; scripts/test.sh reruns this
file under XLA_FLAGS=--xla_force_host_platform_device_count=4 (the sharded
smoke leg), which un-skips them and also exercises every other test here on
the mesh-parallel round path."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _trainer_pair import (assert_trainers_bitwise, make_schedule,
                           run_pair)
from repro.core import ClientData, FederatedTrainer, ParamPack, RoundEngine
from repro.data import make_dataset, partition_by_dirichlet
from repro.kernels import ops
from repro.models import lenet_init, lenet_apply, make_loss_fn
from repro.wireless import ChannelModel, SystemParams

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs a multi-device host "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=N)")


@pytest.fixture(scope="module")
def env():
    ds = make_dataset("synthetic-mnist", n_train=300, n_test=100, seed=1)
    parts = partition_by_dirichlet(ds.y_train, 3, sigma=1.0,
                                   rng=np.random.default_rng(1))
    clients = [ClientData(ds.x_train[i], ds.y_train[i]) for i in parts]
    params = lenet_init(jax.random.key(1))
    loss_fn = make_loss_fn(lenet_apply)
    return clients, params, loss_fn


def _batches(clients, batch, seed=0):
    rng = np.random.default_rng(seed)
    xs, ys = [], []
    for c in clients:
        idx = rng.choice(len(c), size=batch, replace=len(c) < batch)
        xs.append(c.x[idx])
        ys.append(c.y[idx])
    return jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(ys))


def test_round_step_shapes_and_state(env):
    clients, params, loss_fn = env
    pack = ParamPack.build(params)
    eng = RoundEngine(loss_fn, pack, eta=0.1)
    w, v = eng.init_buffers(params)
    xs, ys = _batches(clients, 8)
    w2, v2, losses, thr, step = eng.round_step(w, v, xs, ys, np.full(3, 0.2))
    assert w2.shape == w.shape and v2.shape == w.shape
    assert losses.shape == (3,)
    assert np.isfinite(np.asarray(losses)).all()
    assert bool(jnp.any(w2 != w))          # the step moved the params
    # v starts at zero -> importance all zero -> update = plain FedSGD mean
    assert float(jnp.max(jnp.abs(v2))) > 0.0


def test_round_step_rejects_bad_lambda(env):
    clients, params, loss_fn = env
    pack = ParamPack.build(params)
    eng = RoundEngine(loss_fn, pack, eta=0.1)
    w, v = eng.init_buffers(params)
    xs, ys = _batches(clients, 4)
    with pytest.raises(ValueError):
        eng.round_step(w, v, xs, ys, np.full(3, 1.0))
    with pytest.raises(ValueError):
        eng.round_step(w, v, xs, ys, np.full(3, -0.1))


def test_kernel_impls_bitwise_equal(env):
    """interpret-mode Pallas kernels and the XLA mirror agree exactly."""
    _, params, loss_fn = env
    pack = ParamPack.build(params)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(pack.rows, 128)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(pack.rows, 128)), jnp.float32)
    pr = jnp.asarray(pack.prunable_mask())
    thr = jnp.float32(0.2)
    q_p, m_p = ops.packed_importance_mask(w, v, pr, thr, impl="pallas")
    q_x, m_x = ops.packed_importance_mask(w, v, pr, thr, impl="xla")
    assert bool(jnp.all(q_p == q_x)) and bool(jnp.all(m_p == m_x))

    thrs = jnp.asarray([0.0, 0.2, 1.5], jnp.float32)
    qb_p, mb_p = ops.packed_importance_masks(w, v, pr, thrs, impl="pallas")
    qb_x, mb_x = ops.packed_importance_masks(w, v, pr, thrs, impl="xla")
    assert bool(jnp.all(qb_p == qb_x)) and bool(jnp.all(mb_p == mb_x))
    # batched kernel row c == single-threshold kernel at thresholds[c]
    for c, t in enumerate(np.asarray(thrs)):
        _, m_one = ops.packed_importance_mask(w, v, pr, jnp.float32(t),
                                              impl="pallas")
        assert bool(jnp.all(mb_p[c] == m_one))

    grads = jnp.asarray(rng.normal(size=(4, pack.rows, 128)), jnp.float32)
    w2_p, g_p, s_p = ops.packed_fedsgd_update(w, grads, 0.05, impl="pallas")
    w2_x, g_x, s_x = ops.packed_fedsgd_update(w, grads, 0.05, impl="xla")
    assert bool(jnp.all(g_p == g_x))
    assert bool(jnp.all(s_p == s_x))
    # the fused kernel may FMA-contract the final w - eta*g (skipping the
    # product rounding the fenced xla path performs): 1-ulp tolerance
    np.testing.assert_allclose(np.asarray(w2_p), np.asarray(w2_x),
                               rtol=1e-6, atol=1e-8)

    mask = (jnp.asarray(rng.random((pack.rows, 128))) > 0.5).astype(jnp.float32)
    u_p = ops.packed_masked_update(w, g_p, mask, 0.05, impl="pallas")
    u_x = ops.packed_masked_update(w, g_p, mask, 0.05, impl="xla")
    assert bool(jnp.all(u_p == u_x))


@pytest.mark.parametrize("axis", ["unroll", "scan", "vmap"])
def test_client_axis_strategies_agree(env, axis):
    clients, params, loss_fn = env
    pack = ParamPack.build(params)
    ref_eng = RoundEngine(loss_fn, pack, eta=0.1, client_axis="unroll")
    eng = RoundEngine(loss_fn, pack, eta=0.1, client_axis=axis)
    w, v = ref_eng.init_buffers(params)
    xs, ys = _batches(clients, 8)
    # warm v so pruning is active
    w1, v1, _, _, _ = ref_eng.round_step(w, v, xs, ys, np.full(3, 0.0))
    ref = ref_eng.round_step(w1, v1, xs, ys, np.full(3, 0.3))
    got = eng.round_step(w1, v1, xs, ys, np.full(3, 0.3))
    if axis == "vmap":
        # vmap batches the backward pass; ulp-level reassociation allowed
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]),
                                   rtol=0, atol=1e-6)
    else:
        assert bool(jnp.all(got[0] == ref[0]))
        assert bool(jnp.all(got[1] == ref[1]))


def test_trainer_packed_state_roundtrip(env):
    """params / global_grad setters write through to the packed buffers."""
    clients, params, loss_fn = env
    tr = FederatedTrainer(loss_fn, params, clients, eta=0.1, batch_size=8,
                          seed=0, backend="packed")
    p0 = tr.params
    doubled = jax.tree.map(lambda x: 2.0 * x, p0)
    tr.params = doubled
    for a, b in zip(jax.tree_util.tree_leaves(tr.params),
                    jax.tree_util.tree_leaves(doubled)):
        assert bool(jnp.all(a == b))


def test_weighted_aggregate_matches_unweighted_and_skips_padding(env):
    """The weighted kernel with 0/1 weights == unweighted kernel on the real
    prefix, for both impls — and zero-weight clients are skipped so even a
    NaN padding gradient cannot leak into the update."""
    _, params, _ = env
    pack = ParamPack.build(params)
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.normal(size=(pack.rows, 128)), jnp.float32)
    grads = jnp.asarray(rng.normal(size=(3, pack.rows, 128)), jnp.float32)
    ref = ops.packed_fedsgd_update(w, grads, 0.05, impl="xla")

    padded = jnp.concatenate(
        [grads, jnp.full((2, pack.rows, 128), jnp.nan, jnp.float32)])
    cw = jnp.asarray([1.0, 1.0, 1.0, 0.0, 0.0], jnp.float32)
    inv = np.float32(1.0 / 3)
    # the oracle step: eta times the *materialized* mean, exactly what the
    # eager reference trainer computes (the fence exists to preserve this
    # inside fused graphs; the legacy op's w2/step may differ by 1 ulp at
    # the op level because its trace-time-constant 1/C licenses a constant
    # reassociation the runtime inv blocks)
    eager_step = jnp.float32(0.05) * ref[1]
    for impl in ("xla", "pallas"):
        w2, g, step = ops.packed_fedsgd_update_weighted(
            w, padded, cw, inv, 0.05, impl=impl)
        assert bool(jnp.all(g == ref[1])), impl
        assert bool(jnp.all(step == eager_step)), impl
        np.testing.assert_allclose(np.asarray(w2), np.asarray(ref[0]),
                                   rtol=1e-6, atol=1e-8)
    # pallas and xla mirrors agree exactly on mean and step; w2 may differ
    # by 1 ulp (the fused kernel can FMA-contract the final w - step, same
    # caveat as the unweighted aggregate)
    outs = [ops.packed_fedsgd_update_weighted(w, padded, cw, inv, 0.05,
                                              impl=i) for i in ("xla", "pallas")]
    assert bool(jnp.all(outs[0][1] == outs[1][1]))
    assert bool(jnp.all(outs[0][2] == outs[1][2]))
    np.testing.assert_allclose(np.asarray(outs[0][0]), np.asarray(outs[1][0]),
                               rtol=1e-6, atol=1e-8)


def test_exponent_histogram_kernel_matches_xla(env):
    """The Pallas exponent-histogram kernel (per-block bin counts in VMEM
    scratch, no scatter-add) is bin-for-bin equal to the scatter-add
    mirror, and kth_smallest_threshold(coarse="histogram") gives the same
    threshold through either hist impl as the pure bisection."""
    _, params, _ = env
    pack = ParamPack.build(params)
    rng = np.random.default_rng(3)
    q = jnp.asarray(np.square(rng.normal(size=(pack.rows, 128))), jnp.float32)
    pr = jnp.asarray(pack.prunable_mask())
    h_x = ops.packed_exponent_histogram(q, pr, impl="xla")
    h_p = ops.packed_exponent_histogram(q, pr, impl="pallas")
    assert bool(jnp.all(h_x == h_p))
    assert int(h_x.sum()) == int(pr.sum())
    # zeros / tiny / huge importances land in the right bins
    q2 = q.at[0, 0].set(0.0).at[0, 1].set(1e-38).at[0, 2].set(3e38)
    assert bool(jnp.all(ops.packed_exponent_histogram(q2, pr, impl="xla")
                        == ops.packed_exponent_histogram(q2, pr,
                                                         impl="pallas")))
    from repro.core.round_engine import kth_smallest_threshold
    n_valid = int(pr.sum())
    for k in (0, 1, n_valid // 3, n_valid):
        kk = jnp.int32(k)
        t_ref = kth_smallest_threshold(q, pr, kk, coarse="bisect")
        for impl in ("xla", "pallas"):
            t = kth_smallest_threshold(q, pr, kk, coarse="histogram",
                                       hist_impl=impl)
            assert bool(t == t_ref), (k, impl)
    # vector k (per-client thresholds) through the kernel path
    ks = jnp.asarray([0, 5, n_valid // 2, n_valid], jnp.int32)
    t_ref = kth_smallest_threshold(q, pr, ks, coarse="bisect")
    t_pal = kth_smallest_threshold(q, pr, ks, coarse="histogram",
                                   hist_impl="pallas")
    assert bool(jnp.all(t_ref == t_pal))


def test_exponent_histogram_kernel_rejects_unpacked_shape():
    """The Pallas histogram raises on a shape it cannot tile instead of
    quietly switching to the scatter mirror."""
    q = jnp.ones((4, 100), jnp.float32)
    with pytest.raises(ValueError, match="packed"):
        ops.packed_exponent_histogram(q, q, impl="pallas")
    assert int(ops.packed_exponent_histogram(q, q, impl="xla").sum()) == 400


@pytest.mark.parametrize("clients", [1, 8, 32, 64, 128, 1024])
def test_packed_block_rows_bound_the_client_slab(clients):
    """Row blocks shrink as the client stack grows, so a double-buffered
    [C, block, 128] fp32 slab stays within its VMEM share; blocks are
    multiples of 8 that divide the packed rows, never above 256."""
    rows = 2304
    for budget in (ops._STACK_BYTES, ops._SORT_STACK_BYTES):
        br = ops._packed_block_rows(rows, clients, budget)
        assert br % 8 == 0 and rows % br == 0 and 8 <= br <= 256
        assert clients * br * 128 * 4 <= budget or br == 8
        if br < 256:   # the next larger block would not fit
            assert clients * 2 * br * 128 * 4 > budget or rows % (2 * br)
    assert ops._packed_block_rows(rows, 64) == 128
    assert ops._packed_block_rows(rows, 64, ops._SORT_STACK_BYTES) == 32
    assert ops._packed_block_rows(12) == 12     # full-extent block


# -- bucketed client axis: ragged batches + varying selection ----------------


def _hetero_env(sizes, seed=0):
    """Clients with the given sample counts (deliberately heterogeneous)."""
    ds = make_dataset("synthetic-mnist", n_train=sum(sizes),
                      n_test=60, seed=seed)
    off = np.cumsum([0] + list(sizes))
    clients = [ClientData(ds.x_train[a:b], ds.y_train[a:b])
               for a, b in zip(off, off[1:])]
    return clients, lenet_init(jax.random.key(seed)), make_loss_fn(lenet_apply)


def test_bucket_sizes_power_of_two_per_shard():
    clients, params, loss_fn = _hetero_env([40, 20])
    pack = ParamPack.build(params)
    eng = RoundEngine(loss_fn, pack, eta=0.1, shards=1)
    assert [eng.bucket_size(c) for c in (1, 2, 3, 5, 8, 9, 17)] == \
        [1, 2, 4, 8, 8, 16, 32]
    flat = RoundEngine(loss_fn, pack, eta=0.1, shards=1, bucket=False)
    assert [flat.bucket_size(c) for c in (1, 3, 7)] == [1, 3, 7]
    # shard-count multiples: per-shard counts are power-of-two padded
    eng.shards = 4          # formula check only (no 4-device mesh needed)
    assert [eng.bucket_size(c) for c in (1, 4, 5, 9, 17)] == \
        [4, 4, 8, 16, 32]
    # population cap: full participation never pads past the roster
    capped = RoundEngine(loss_fn, pack, eta=0.1, shards=1, max_clients=20)
    assert [capped.bucket_size(c) for c in (3, 10, 17, 20)] == [4, 16, 20, 20]
    capped.shards = 4
    assert capped.bucket_size(20) == 20 and capped.bucket_size(17) == 20


def test_ragged_clients_stay_packed_and_bitwise():
    """Clients smaller than the batch size run packed (no reference
    fallback) and match the reference trainer bit for bit."""
    clients, params, loss_fn = _hetero_env([60, 10, 7, 3])
    a = np.ones((6, 4))
    out = run_pair(clients, params, loss_fn, make_schedule(a, 0.3), shards=1)
    (tr_ref, h_ref), (tr_pk, h_pk) = out["reference"], out["packed"]
    assert tr_pk.n_fallback_rounds == 0
    for mr, mp in zip(h_ref, h_pk):
        assert mr.train_loss == mp.train_loss
    assert_trainers_bitwise(tr_ref, tr_pk)


def test_varying_selection_bounded_traces_and_bitwise():
    """solve_p1-style schedules select a different client count every round;
    the bucketed engine must compile at most one trace per bucket size and
    stay bit-for-bit equal to the reference loop — including ragged
    stragglers in the mix."""
    sizes = [60, 40, 30, 25, 20, 18, 10, 7, 3]   # last three ragged at B=16
    clients, params, loss_fn = _hetero_env(sizes)
    rng = np.random.default_rng(5)
    n, rounds = len(sizes), 50
    a = np.zeros((rounds, n))
    for s in range(rounds):
        sel = rng.choice(n, size=rng.integers(1, n + 1), replace=False)
        a[s, sel] = 1.0
    out = run_pair(clients, params, loss_fn, make_schedule(a, 0.3), shards=1)
    (tr_ref, h_ref), (tr_pk, h_pk) = out["reference"], out["packed"]
    assert tr_pk.n_fallback_rounds == 0
    eng = tr_pk.engine
    counts = {int(r.sum()) for r in a}
    assert eng.buckets_used == {eng.bucket_size(c) for c in counts}
    assert eng.n_traces <= len(eng.buckets_used)      # zero retrace storms
    for mr, mp in zip(h_ref, h_pk):
        assert mr.train_loss == mp.train_loss
    assert_trainers_bitwise(tr_ref, tr_pk)


def test_varying_selection_per_client_lambda_bounded_traces():
    """Same bound for the per-client-lambda (batched threshold) family."""
    sizes = [60, 40, 30, 20, 10]
    clients, params, loss_fn = _hetero_env(sizes)
    rng = np.random.default_rng(9)
    n, rounds = len(sizes), 12
    a = np.zeros((rounds, n))
    for s in range(rounds):
        sel = rng.choice(n, size=rng.integers(2, n + 1), replace=False)
        a[s, sel] = 1.0
    lam = np.broadcast_to(np.linspace(0.1, 0.5, n), a.shape)
    out = run_pair(clients, params, loss_fn, make_schedule(a, lam), shards=1)
    (tr_ref, _), (tr_pk, _) = out["reference"], out["packed"]
    assert tr_pk.n_fallback_rounds == 0
    assert tr_pk.engine.n_traces <= len(tr_pk.engine.buckets_used)
    assert_trainers_bitwise(tr_ref, tr_pk)


def test_packed_losses_stay_on_device(env):
    """S1: _round returns the per-client losses as a device array (no host
    sync inside the round loop); run() materializes them lazily."""
    clients, params, loss_fn = env
    tr = FederatedTrainer(loss_fn, params, clients, eta=0.1, batch_size=8,
                          seed=0, backend="packed", shards=1)
    losses, n_ok, ast = tr._round([0, 1, 2], np.full(3, 0.2))
    assert isinstance(losses, jax.Array)
    assert losses.shape == (3,)
    assert isinstance(n_ok, jax.Array)    # survivor count stays lazy too
    assert ast is None                    # no robust aggregator active
    sp = SystemParams.table1(3)
    ch = ChannelModel(3)
    hist = tr.run(make_schedule(np.ones((3, 3)), 0.2), sp, ch.uplink, ch.downlink)
    assert all(np.isfinite(m.train_loss) for m in hist)


# -- sharded client axis (multi-device host) ---------------------------------


@multidevice
def test_sharded_engine_first_round_matches_single_device(env):
    clients, params, loss_fn = env
    pack = ParamPack.build(params)
    eng1 = RoundEngine(loss_fn, pack, eta=0.1, shards=1)
    engn = RoundEngine(loss_fn, pack, eta=0.1)        # all local devices
    assert engn.mesh is not None and engn.shards == len(jax.devices())
    w, v = eng1.init_buffers(params)
    xs, ys = _batches(clients, 8)
    o1 = eng1.round_step(w, v, xs, ys, np.full(3, 0.2))
    on = engn.round_step(w, v, xs, ys, np.full(3, 0.2))
    # per-client forward/backward is identical math; only the cross-shard
    # reduction reassociates, so losses are exact and w within ~1 ulp
    assert bool(jnp.all(o1[2] == on[2]))
    assert float(jnp.max(jnp.abs(o1[3] - on[3]))) == 0.0   # same threshold
    np.testing.assert_allclose(np.asarray(o1[0]), np.asarray(on[0]),
                               rtol=1e-6, atol=1e-7)
    # per-client-lambda family on the sharded path
    m1 = eng1.round_step(o1[0], o1[1], xs, ys, np.asarray([0.0, 0.2, 0.5]))
    mn = engn.round_step(on[0], on[1], xs, ys, np.asarray([0.0, 0.2, 0.5]))
    np.testing.assert_allclose(np.asarray(m1[0]), np.asarray(mn[0]),
                               rtol=1e-6, atol=1e-7)


@multidevice
def test_sharded_trainer_trajectory_equivalent():
    """Auto-sharded trainer stays within ulp-level drift of the
    single-device packed trainer over a short run, ragged clients and
    varying selection included."""
    sizes = [60, 30, 20, 10, 7, 3]
    clients, params, loss_fn = _hetero_env(sizes)
    rng = np.random.default_rng(3)
    n, rounds = len(sizes), 6
    a = np.zeros((rounds, n))
    for s in range(rounds):
        sel = rng.choice(n, size=rng.integers(2, n + 1), replace=False)
        a[s, sel] = 1.0
    hists = {}
    trs = {}
    for shards in (1, None):                 # None = auto (all devices)
        tr = FederatedTrainer(loss_fn, params, clients, eta=0.1,
                              batch_size=16, seed=0, backend="packed",
                              shards=shards)
        sp = SystemParams.table1(n)
        ch = ChannelModel(n)
        hists[shards] = tr.run(make_schedule(a, 0.3), sp, ch.uplink, ch.downlink)
        trs[shards] = tr
    assert trs[None].engine.mesh is not None
    assert trs[None].n_fallback_rounds == 0
    for m1, mn in zip(hists[1], hists[None]):
        assert abs(m1.train_loss - mn.train_loss) < 1e-5
    for p1, pn in zip(jax.tree_util.tree_leaves(trs[1].params),
                      jax.tree_util.tree_leaves(trs[None].params)):
        np.testing.assert_allclose(np.asarray(p1), np.asarray(pn),
                                   rtol=1e-5, atol=1e-6)


# -- the perf harness itself -------------------------------------------------

def test_benchmark_compare_reports():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import round_engine as bench

    def rep(s_fast, s_slow):
        return {"meta": {"git_rev": "abc"}, "results": [
            {"model": "lenet", "n_clients": 4, "batch": 8,
             "packed_s_per_round": 0.1, "speedup": s_fast},
            {"model": "lenet", "n_clients": 8, "batch": 8,
             "packed_s_per_round": 0.2, "speedup": s_slow},
            {"model": "only-prev", "n_clients": 1, "batch": 1,
             "packed_s_per_round": 1.0, "speedup": 1.0}]}

    prev = rep(2.0, 2.0)
    cur = rep(2.2, 1.5)                       # one improved, one regressed
    cur["results"] = cur["results"][:2]       # dropped config is skipped
    rows = bench.compare_reports(prev, cur)
    assert len(rows) == 2
    assert not rows[0]["regressed"] and rows[0]["speedup_delta_pct"] > 0
    assert rows[1]["regressed"] and rows[1]["speedup_delta_pct"] < -10
    bench.print_compare(rows, prev["meta"])   # smoke the printer


def test_benchmark_harness_smoke(tmp_path):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import round_engine as bench

    out = tmp_path / "BENCH_round_engine.json"
    report = bench.run_benchmark(configs=[("lenet", 2, 8)],
                                 equiv_cfg=("lenet", 2, 8, 3),
                                 rounds=2, warmup=1, n_train=240,
                                 out_path=str(out))
    assert out.exists()
    (r,) = report["results"]
    assert r["reference_s_per_round"] > 0
    assert r["packed_s_per_round"] > 0
    assert r["speedup"] > 0
    assert report["equivalence"]["abs_diff"] <= 1e-5
