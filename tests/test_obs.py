"""The span recorder (repro.obs) and the spans the program records.

Covers: nesting and parents (per thread), the bounded flight recorder,
counts on spans, `between()` and `durations()` on time, span names, a
short packed block run that records every span under its parent with the
device->host read count its schedule implies (and that every read the
run makes is counted) and the same numbers as a run with the recorder
replaced by a no-op, the cohort store's stall span, and the named device
phases in a lowered block program's HLO metadata.
"""
import collections
import dataclasses
import re
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import (DataSpec, Experiment, ExperimentSpec, ModelSpec,
                       RunSpec, SchemeSpec, WirelessSpec)
from repro.api.experiment import build_environment

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


def names_since(t0):
    return [r.name for r in obs.between(t0, float("inf"))]


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

def test_spans_nest_and_record_their_parent_per_thread():
    t0 = time.perf_counter()
    seen = {}

    def other():
        with obs.span("t.outer"):
            with obs.span("t.inner"):
                pass
        seen["done"] = True

    with obs.span("m.outer") as outer:
        with obs.span("m.inner"):
            th = threading.Thread(target=other)
            th.start()
            th.join(timeout=30)
    assert not th.is_alive() and seen["done"]
    recs = {r.name: r for r in obs.between(t0, float("inf"))}
    assert recs["m.outer"].parent is None
    assert recs["m.inner"].parent == "m.outer"
    # the second thread's spans nest under its own, not the caller's
    assert recs["t.outer"].parent is None
    assert recs["t.inner"].parent == "t.outer"
    assert outer.t0 <= recs["m.inner"].t0 <= recs["m.inner"].t1 <= outer.t1
    assert (recs["m.outer"].t0, recs["m.outer"].t1) == (outer.t0, outer.t1)


def test_a_span_closed_by_an_exception_is_recorded_and_unwound():
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with obs.span("x.raises"):
            raise ValueError("boom")
    with obs.span("x.after"):
        pass
    recs = {r.name: r for r in obs.between(t0, float("inf"))}
    assert recs["x.after"].parent is None
    assert "x.raises" in recs


def test_recorder_is_bounded_and_drops_the_oldest(monkeypatch):
    monkeypatch.setattr(obs, "_records", collections.deque(maxlen=4))
    spans = []
    for i in range(6):
        with obs.span(f"r{i}") as s:
            pass
        spans.append(s)
    assert [r.name for r in obs.recent()] == ["r2", "r3", "r4", "r5"]
    assert [r.name for r in obs.recent(2)] == ["r4", "r5"]
    assert obs.recent(0) == []
    # from r3 on nothing was dropped; from r0 on, r0 and r1 were
    assert [r.name for r in obs.between(spans[3].t0, spans[5].t1)] == [
        "r3", "r4", "r5"]
    assert obs.between(spans[0].t0, spans[5].t1) is None


def test_counts_are_kept_on_the_span_open_at_the_work():
    t0 = time.perf_counter()
    obs.count("d2h")                       # no span open: not recorded
    with obs.span("c.outer") as outer:
        outer.count("d2h")
        with obs.span("c.inner") as inner:
            obs.count("d2h", 2)            # the innermost open span
            inner.count("rows", 5)
        obs.count("d2h")
    with obs.span("c.none"):
        pass
    recs = {r.name: r for r in obs.between(t0, float("inf"))}
    assert recs["c.outer"].counts == {"d2h": 2}
    assert recs["c.inner"].counts == {"d2h": 2, "rows": 5}
    assert recs["c.none"].counts is None


def test_between_keeps_the_spans_inside_the_interval():
    with obs.span("b.before") as before:
        pass
    with obs.span("b.a") as a:
        pass
    with obs.span("b.b") as b:
        pass
    with obs.span("b.after"):
        pass
    assert [r.name for r in obs.between(a.t0, b.t1)] == ["b.a", "b.b"]
    assert [r.name for r in obs.between(a.t0, (b.t0 + b.t1) / 2)] == [
        "b.a"]
    assert "b.before" not in [r.name for r in obs.between(
        (before.t0 + before.t1) / 2, b.t1)]


def test_durations_reads_one_name_inside_the_interval(monkeypatch):
    with obs.span("d.x") as x1:
        pass
    with obs.span("d.y"):
        pass
    with obs.span("d.x") as x2:
        pass
    assert obs.durations("d.x", x1.t0, x2.t1) == [x1.t1 - x1.t0,
                                                  x2.t1 - x2.t0]
    assert obs.durations("d.x", x2.t0, x2.t1) == [x2.t1 - x2.t0]
    assert obs.durations("d.z", x1.t0, x2.t1) == []
    monkeypatch.setattr(obs, "_records", collections.deque(
        obs.recent(2), maxlen=2))
    assert obs.durations("d.x", x1.t0, x2.t1) is None


def test_no_span_name_starts_with_the_benchmark_prefix():
    names = set()
    for path in SRC.rglob("*.py"):
        text = path.read_text()
        names |= set(re.findall(r'obs\.span\("([^"]+)"\)', text))
    assert {"trainer.plan", "trainer.draw", "trainer.materialize",
            "trainer.wait", "trainer.unpack", "trainer.reset",
            "trainer.slice", "eval",
            "eval.wait", "experiment.init", "ao.resources", "ao.pruning",
            "ao.selection", "cohort.wait"} <= names
    assert not [n for n in names if n.startswith("bench:")]


# ---------------------------------------------------------------------------
# the spans of a run
# ---------------------------------------------------------------------------

ROUNDS, EVAL_EVERY, N_TEST, EVAL_BATCH = 10, 4, 1100, 500


def run_spec(seed=0):
    return ExperimentSpec(
        data=DataSpec(dataset="synthetic-mnist", n_clients=5, sigma=5.0,
                      n_train=300, n_test=N_TEST, seed=0),
        model=ModelSpec(name="lenet"),
        wireless=WirelessSpec(e0=1e6, t0=1e6, seed=0),
        scheme=SchemeSpec(name="proposed", rounds=ROUNDS, eta=0.1, batch=8,
                          ao={"outer_iters": 1}),
        run=RunSpec(seed=seed, eval_every=EVAL_EVERY, rounds_per_dispatch=4,
                    stop_on_budget=False))


class _NoSpan:
    """A span that records nothing: the recorder taken out."""
    t0 = t1 = 0.0

    def __init__(self, name):
        pass

    def count(self, key, n=1):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _build(env, seed):
    """A run of `run_spec(seed)` on a pooled trainer, as a sweep builds it."""
    spec = run_spec(seed)
    pooled = Experiment(spec).build(env=env)
    return Experiment(spec).build(env=env, trainer=pooled.trainer)


def _run(env, seed):
    run = _build(env, seed)
    res = run.run()
    return res, [np.asarray(a) for a in jax.tree.leaves(run.trainer.params)]


def _device_reads(monkeypatch):
    """Count every read of a device value into host memory, whether or
    not the program counts it: each `float`/`int`/`bool`/`__array__` of a
    `jax.Array` that fetches its value, and each `np.asarray`/`np.array`
    of one (on the CPU these read through the buffer protocol, past
    `__array__`). Returns a list that grows by one per read."""
    from jax._src import array as jarray
    fetch = jarray.ArrayImpl._value
    reads, converting = [], []

    def counted(self):
        if self._npy_value is None and not converting:
            reads.append(1)
        return fetch.fget(self)

    def counting(convert):
        def wrapped(a, *args, **kwargs):
            if isinstance(a, jax.Array):
                reads.append(1)
            converting.append(1)
            try:
                return convert(a, *args, **kwargs)
            finally:
                converting.pop()
        return wrapped

    monkeypatch.setattr(jarray.ArrayImpl, "_value", property(counted))
    monkeypatch.setattr(np, "asarray", counting(np.asarray))
    monkeypatch.setattr(np, "array", counting(np.array))
    return reads


@pytest.fixture(scope="module")
def env():
    return build_environment(run_spec())


def test_run_records_every_span_under_its_parent(env, monkeypatch):
    pooled = Experiment(run_spec(3)).build(env=env)
    pooled.run()               # compiles every program the run dispatches
    built = time.perf_counter()
    run = Experiment(run_spec(3)).build(env=env, trainer=pooled.trainer)
    reads = _device_reads(monkeypatch)
    t0 = time.perf_counter()
    res = run.run()
    recs = obs.between(t0, time.perf_counter())
    monkeypatch.undo()
    recs = obs.between(built, t0) + recs
    by = collections.defaultdict(list)
    for r in recs:
        by[r.name].append(r)
    evals = [s for s in range(ROUNDS)
             if s % EVAL_EVERY == 0 or s == ROUNDS - 1]
    parents = {"trainer.plan": None, "trainer.draw": None,
               "trainer.slice": None,
               "trainer.materialize": None,
               "trainer.wait": "trainer.materialize",
               "trainer.unpack": None, "trainer.reset": None,
               "eval": None, "eval.wait": "eval",
               "experiment.init": None, "ao.resources": None,
               "ao.pruning": None, "ao.selection": None}
    for name, parent in parents.items():
        assert by[name], name
        assert {r.parent for r in by[name]} == {parent}, name
    assert len(by["eval"]) == len(evals)
    batches = -(-N_TEST // EVAL_BATCH)
    assert len(by["eval.wait"]) == len(evals) * batches
    # one materialization per eval round drains every pending round
    assert len(by["trainer.materialize"]) == len(evals)
    assert len(by["trainer.wait"]) == len(evals)
    # every round selected clients and ran in a block dispatch, each
    # with one draw of its batch indices
    assert all(m.selected for m in res.history)
    assert len(by["trainer.draw"]) == run.trainer.n_block_dispatches > 1
    assert not by["trainer.round"]
    assert len(by["trainer.slice"]) == len(by["trainer.draw"])
    # device->host reads: the loss slice and the survivor count of every
    # round, the loss and accuracy of every eval batch
    d2h = sum((r.counts or {}).get("d2h", 0) for r in recs)
    assert d2h == 2 * ROUNDS + 2 * batches * len(evals)
    # and they are all the reads the run made, counted or not
    assert len(reads) == d2h
    assert sum(r.counts["d2h"] for r in by["trainer.materialize"]) == (
        2 * ROUNDS)
    assert sum(r.counts["d2h"] for r in by["eval"]) == (
        2 * batches * len(evals))


def test_reference_backend_counts_its_reads(env):
    """The reference loop reads each client's loss and, per client, one
    isfinite flag per leaf while every leaf is finite; a checkpoint reads
    each leaf it saves."""
    spec = run_spec(seed=4)
    spec = dataclasses.replace(spec, run=dataclasses.replace(
        spec.run, backend="reference", evaluate=False))
    run = Experiment(spec).build(env=env)
    leaves = len(jax.tree.leaves(run.trainer.params))
    t0 = time.perf_counter()
    res = run.run()
    recs = obs.between(t0, time.perf_counter())
    rounds = [r for r in recs if r.name == "trainer.round"]
    clients = [len(m.selected) for m in res.history if m.selected]
    assert len(rounds) == len(clients)
    assert [r.counts["d2h"] for r in rounds] == [
        c * (1 + leaves) for c in clients]
    # pending values on this path are host floats: nothing to read
    assert all(not (r.counts or {}).get("d2h")
               for r in recs if r.name == "trainer.materialize")


def test_a_checkpoint_counts_one_read_per_saved_leaf(env, tmp_path):
    from repro.api.callbacks import save_trainer_state
    from repro.checkpoint import CheckpointManager
    run = Experiment(run_spec(seed=6)).build(env=env)
    m = run.trainer.run(run.schedule, env.sp, env.ch.uplink,
                        env.ch.downlink)[0]
    leaves = 2 * len(jax.tree.leaves(run.trainer.params))   # params and v
    t0 = time.perf_counter()
    save_trainer_state(CheckpointManager(str(tmp_path)), run.trainer, m)
    saves = [r for r in obs.between(t0, time.perf_counter())
             if r.name == "checkpoint.save"]
    assert [r.counts["d2h"] for r in saves] == [leaves]


def test_the_recorder_changes_no_numbers(env, monkeypatch):
    res, params = _run(env, seed=5)
    with monkeypatch.context() as m:
        m.setattr(obs, "span", _NoSpan)
        m.setattr(obs, "count", lambda key, n=1: None)
        t0 = time.perf_counter()
        res2, params2 = _run(env, seed=5)
        assert names_since(t0) == []
    losses = [h.train_loss for h in res.history]
    assert losses == [h.train_loss for h in res2.history]
    assert [h.test_loss for h in res.history] == [
        h.test_loss for h in res2.history]
    assert all(np.array_equal(a, b) for a, b in zip(params, params2))


def test_cohort_stall_is_the_wait_span(env):
    """The streamed store's prefetch stall counter is the sum of its
    `cohort.wait` spans."""
    spec = run_spec(seed=1)
    spec = dataclasses.replace(spec, run=dataclasses.replace(
        spec.run, client_store="streamed"))
    t0 = time.perf_counter()
    run = Experiment(spec).build(env=env)
    res = run.run()
    waits = [r for r in obs.between(t0, time.perf_counter())
             if r.name == "cohort.wait"]
    assert waits and run.trainer.streaming
    assert res.summary["fleet"]["prefetch_stall_s"] == pytest.approx(
        sum(r.t1 - r.t0 for r in waits), rel=1e-9, abs=1e-12)
    assert {r.parent for r in waits} == {None}


# ---------------------------------------------------------------------------
# named device phases
# ---------------------------------------------------------------------------

def test_block_program_carries_the_round_phase_scopes(env):
    run = Experiment(run_spec(seed=2)).build(env=env)
    engine = run.trainer.engine
    lowered = []

    def capture(name):
        fn = getattr(engine, name)

        def wrapped(*args):
            lowered.append(fn.lower(*[
                jax.ShapeDtypeStruct(a.shape, a.dtype)
                if isinstance(a, jax.Array) else a for a in args]))
            return fn(*args)
        setattr(engine, name, wrapped)

    capture("_blk_shared")
    capture("_blk_multi")
    run.run()
    assert lowered
    text = lowered[0].compile().as_text()
    op_names = " ".join(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("round.threshold", "round.masks", "round.clients",
                  "round.aggregate", "round.update"):
        assert f"/{scope}/" in op_names, scope
