"""(P2)-(P5) solvers + Algorithm 1 (AO)."""
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.api.registry import SCHEMES
from repro.api.spec import SchemeSpec
from repro.core import optimizer_ao
from repro.core.convergence import BoundConstants, theta
from repro.core.optimizer_ao import AOConfig, solve_p1
from repro.core.ratio import solve_pruning_ratios
from repro.core.resource import (
    allocate_client, solve_round_resources, solve_schedule_resources,
    sca_round_resources, min_client_delay)
from repro.core.selection import solve_selection, round_objective
from repro.wireless import ChannelModel, SystemParams
from repro.wireless.comm import total_delay, total_energy

N = 6


@pytest.fixture
def env():
    sp = SystemParams.table1(N, dataset="mnist")
    ch = ChannelModel(N, seed=0)
    c = BoundConstants(rounds_S=3, batch_Z=32)
    rng = np.random.default_rng(0)
    phi = rng.uniform(0.2, 3.0, N)
    return sp, ch, c, phi


# ---------------- resource allocation (P2) ----------------

def test_allocate_client_respects_budget_and_boxes(env):
    sp, ch, _, _ = env
    t_min = min_client_delay(0, 0.3, ch.uplink, ch.downlink, sp)
    al = allocate_client(0, 0.3, 2.0 * t_min, ch.uplink, ch.downlink, sp)
    assert al.feasible
    assert al.delay <= 2.0 * t_min * (1 + 1e-6)
    assert 0 <= al.power <= sp.p_max[0] + 1e-12
    assert 0 <= al.freq <= sp.f_max[0] + 1e3


def test_allocate_client_infeasible_when_budget_below_min(env):
    sp, ch, _, _ = env
    t_min = min_client_delay(0, 0.0, ch.uplink, ch.downlink, sp)
    al = allocate_client(0, 0.0, 0.5 * t_min, ch.uplink, ch.downlink, sp)
    assert not al.feasible


def test_more_time_less_energy(env):
    """The energy-vs-delay tradeoff is monotone (convexity sanity)."""
    sp, ch, _, _ = env
    t_min = min_client_delay(0, 0.0, ch.uplink, ch.downlink, sp)
    e = [allocate_client(0, 0.0, k * t_min, ch.uplink, ch.downlink, sp).energy
         for k in (1.2, 2.0, 4.0)]
    assert e[0] >= e[1] >= e[2]


def test_analytic_matches_sca(env):
    """The production decomposition and the paper-faithful SCA (eq. 28)
    land on comparable round energies (within 10%)."""
    sp, ch, _, _ = env
    a = np.ones(N)
    lam = 0.2 * np.ones(N)
    t_round = 2.5 * max(min_client_delay(i, 0.2, ch.uplink, ch.downlink, sp)
                        for i in range(N))
    ana = solve_round_resources(a, lam, t_round, ch.uplink, ch.downlink, sp)
    sca = sca_round_resources(a, lam, 1e9, t_round, ch.uplink, ch.downlink, sp)
    assert ana.feasible
    assert ana.energy <= sca.energy * 1.10  # decomposition is exact per client


def per_round_schedule_resources(a, lam, e0, t0, h_up, h_down, sp):
    """The schedule solve as one `solve_round_resources` per round at the
    uniform budget t0/(S+1): every selected (round, client) solved anew."""
    a, lam = np.atleast_2d(a), np.atleast_2d(lam)
    t_round = t0 / max(a.shape[0], 1)
    rounds = [solve_round_resources(a[s], lam[s], t_round, h_up, h_down, sp)
              for s in range(a.shape[0])]
    e_tot, t_tot, feas = 0.0, 0.0, True
    for ra in rounds:
        e_tot += ra.energy
        t_tot += ra.delay
        feas &= ra.feasible
    feas = False if t_tot > t0 else feas and e_tot <= e0
    return (np.array([ra.power for ra in rounds]),
            np.array([ra.freq for ra in rounds]),
            {"energy": e_tot, "delay": t_tot, "feasible": feas})


def _t0(ch, sp, rounds, lam=0.0, slack=3.0):
    return rounds * slack * max(
        min_client_delay(i, lam, ch.uplink, ch.downlink, sp) for i in range(N))


def _case(name, rounds):
    """(a, lam, t0 scale, distinct (client, lambda) among the selected)."""
    rng = np.random.default_rng(7)
    a = np.ones((rounds, N))
    lam = np.full((rounds, N), 0.2)
    if name == "all_rounds_equal":
        return a, lam, 1.0, N
    if name == "one_client_per_round":
        a = np.zeros((rounds, N))
        a[np.arange(rounds), np.arange(rounds) % N] = 1.0
        lam = rng.choice([0.0, 0.3, 0.5], size=(rounds, N))
        return a, lam, 1.0, len({(s % N, lam[s, s % N])
                                 for s in range(rounds)})
    if name == "rows_with_a_zero":
        a = (rng.uniform(size=(rounds, N)) < 0.5).astype(float)
        a[0, :3] = 1.0
        a[1] = 0.0
        a[-1] = 0.0
        lam = rng.choice([0.1, 0.4], size=(rounds, N))
        sel = np.argwhere(a > 0)
        return a, lam, 1.0, len({(n, lam[s, n]) for s, n in sel})
    if name == "lambda_one_ulp_apart":
        lam[1, 0] = np.nextafter(lam[0, 0], 1.0)
        return a, lam, 1.0, N + 1
    if name == "infeasible_budget":
        return a, lam, 0.05, N
    raise ValueError(name)


CASES = ["all_rounds_equal", "one_client_per_round", "rows_with_a_zero",
         "lambda_one_ulp_apart", "infeasible_budget"]


@pytest.mark.parametrize("case", CASES)
def test_schedule_resources_match_a_solve_per_round_bitwise(env, case):
    """Solving each distinct (client, lambda) once gives the bits of a
    solve per (round, client), and counts what it solved."""
    sp, ch, c, _ = env
    rounds = c.rounds_S + 1
    a, lam, scale, distinct = _case(case, rounds)
    t0 = scale * _t0(ch, sp, rounds)
    args = (a, lam, 40.0, t0, ch.uplink, ch.downlink, sp)
    t_start = time.perf_counter()
    with obs.span("p2.test"):
        p, f, info = solve_schedule_resources(*args)
    (rec,) = [r for r in obs.between(t_start, time.perf_counter())
              if r.name == "p2.test"]
    p_ref, f_ref, info_ref = per_round_schedule_resources(*args)
    for got, want in ((p, p_ref), (f, f_ref)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
    assert info == info_ref
    assert [type(v) for v in info.values()] == [
        type(v) for v in info_ref.values()]
    assert info["feasible"] == (case != "infeasible_budget")
    assert rec.counts == {"p2.pairs": int((a != 0).sum()),
                          "p2.solved": distinct}


@pytest.mark.parametrize("e0", [50.0, 0.3])
def test_solve_p1_schedule_matches_a_solve_per_round(env, monkeypatch, e0):
    """`proposed` through Algorithm 1: the same Schedule, bit for bit, as
    with every (round, client) allocation solved anew."""
    sp, ch, c, phi = env
    cfg = SCHEMES.get("proposed")(SchemeSpec())
    args = (phi, e0, _t0(ch, sp, c.rounds_S + 1), ch.uplink, ch.downlink,
            sp, c, cfg)
    got = solve_p1(*args)
    monkeypatch.setattr(optimizer_ao, "solve_schedule_resources",
                        per_round_schedule_resources)
    want = solve_p1(*args)
    for field in ("a", "lam", "power", "freq"):
        x, y = getattr(got, field), getattr(want, field)
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), field
    for field in ("theta", "energy", "delay", "feasible", "history"):
        assert getattr(got, field) == getattr(want, field), field


def test_ao_resources_span_counts_pairs_and_solves(env):
    """Fixed selection and lambda: one P2 call on every (round, client),
    one distinct lambda per client."""
    sp, ch, c, phi = env
    rounds = c.rounds_S + 1
    t_start = time.perf_counter()
    solve_p1(phi, 50.0, _t0(ch, sp, rounds), ch.uplink, ch.downlink, sp, c,
             AOConfig(outer_iters=1, fix_lambda=0.2, fix_selection=True))
    recs = [r for r in obs.between(t_start, time.perf_counter())
            if r.name == "ao.resources"]
    assert [r.counts for r in recs] == [{"p2.pairs": rounds * N,
                                         "p2.solved": N}]


# ---------------- pruning-ratio LP (P3) ----------------

def test_lp_zero_when_unconstrained(env):
    sp, ch, c, _ = env
    s = c.rounds_S + 1
    a = np.ones((s, N))
    p = 0.3 * np.ones((s, N))
    f = 300e6 * np.ones((s, N))
    lam, info = solve_pruning_ratios(a, p, f, 1e9, 1e9, ch.uplink,
                                     ch.downlink, sp, c)
    assert info["status"] == "optimal"
    np.testing.assert_allclose(lam, 0.0, atol=1e-8)


def test_lp_prunes_exactly_to_feasibility(env):
    sp, ch, c, _ = env
    s = c.rounds_S + 1
    a = np.ones((s, N))
    p = 0.3 * np.ones((s, N))
    f = 300e6 * np.ones((s, N))
    e_free = total_energy(a, np.zeros((s, N)), p, f, ch.uplink, ch.downlink, sp)
    e0 = 0.8 * e_free
    lam, info = solve_pruning_ratios(a, p, f, e0, 1e9, ch.uplink,
                                     ch.downlink, sp, c)
    assert info["status"] == "optimal"
    assert (lam <= sp.lambda_max + 1e-9).all() and (lam >= -1e-9).all()
    e_after = total_energy(a, lam, p, f, ch.uplink, ch.downlink, sp)
    assert e_after <= e0 * (1 + 1e-6)
    assert lam.sum() > 0  # had to prune something


# ---------------- client selection (P5) ----------------

def test_exact_selection_beats_or_matches_paper_heuristic(env):
    sp, ch, c, phi = env
    s = c.rounds_S + 1
    lam = 0.2 * np.ones((s, N))
    t0 = s * 3.0 * max(min_client_delay(i, 0.2, ch.uplink, ch.downlink, sp)
                       for i in range(N))
    a_ex, info_ex = solve_selection(lam, phi, c, 1e9, t0, ch.uplink,
                                    ch.downlink, sp, method="exact")
    a_pp, info_pp = solve_selection(lam, phi, c, 1e9, t0, ch.uplink,
                                    ch.downlink, sp, method="paper")
    assert info_ex["objective"] <= info_pp["objective"] + 1e-9
    assert a_ex.shape == (s, N)
    assert set(np.unique(a_ex)).issubset({0.0, 1.0})


def test_selection_prefers_low_phi(env):
    sp, ch, c, _ = env
    s = c.rounds_S + 1
    phi = np.array([0.1, 0.1, 8.0, 9.0, 10.0, 11.0])
    lam = np.zeros((s, N))
    t0 = s * 3.0 * max(min_client_delay(i, 0.0, ch.uplink, ch.downlink, sp)
                       for i in range(N))
    a, _ = solve_selection(lam, phi, c, 1e9, t0, ch.uplink, ch.downlink, sp)
    # low-phi clients selected at least as often as high-phi ones
    counts = a.sum(axis=0)
    assert counts[0] >= counts[-1]
    assert a.sum() >= s  # at least one client every round


# ---------------- Algorithm 1 ----------------

def test_ao_produces_feasible_nonincreasing_schedule(env):
    sp, ch, c, phi = env
    t0 = (c.rounds_S + 1) * 3.0 * max(
        min_client_delay(i, 0.0, ch.uplink, ch.downlink, sp) for i in range(N))
    sched = solve_p1(phi, 50.0, t0, ch.uplink, ch.downlink, sp, c,
                     AOConfig(outer_iters=3))
    assert sched.feasible
    assert sched.energy <= 50.0 * (1 + 1e-4)
    assert sched.delay <= t0 * (1 + 1e-4)
    # theta consistency
    assert sched.theta == pytest.approx(theta(sched.a, sched.lam, phi, c))
    # incumbent is the best feasible iterate
    feas = [h["theta"] for h in sched.history if h["feasible"]]
    assert sched.theta == pytest.approx(min(feas))


def test_ao_tight_energy_forces_pruning_or_fewer_clients(env):
    sp, ch, c, phi = env
    t0 = (c.rounds_S + 1) * 3.0 * max(
        min_client_delay(i, 0.0, ch.uplink, ch.downlink, sp) for i in range(N))
    loose = solve_p1(phi, 1e9, t0, ch.uplink, ch.downlink, sp, c,
                     AOConfig(outer_iters=2))
    tight = solve_p1(phi, 0.3, t0, ch.uplink, ch.downlink, sp, c,
                     AOConfig(outer_iters=2))
    assert tight.energy <= 0.3 * (1 + 1e-4)
    # under the tight budget the system uses more pruning or fewer clients
    assert (tight.lam.sum() >= loose.lam.sum() - 1e-9) or \
        (tight.a.sum() <= loose.a.sum())
