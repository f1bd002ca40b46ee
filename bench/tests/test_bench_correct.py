"""`correct` at a size a test run holds, on the CPU.

For each cell: a sound run of the harness passes; the same run with the
timed path broken underneath comes out not correct, once for each fault a
training cell can have (a step that returns its state unchanged; half of
each batch left out, the mean taken over the rest; a client's labels
altered where its data is built); a check too short to reach every block
program the window uses comes out not correct; and the control, the
reference in bfloat16 put in the program's place, fails the cell's limits.
LeNet-5 with a traffic that asks for a trimmed mean is correct, and not
with the mean in its place, and every stand-in fails its limits. The
device check is skipped; everything else is a whole run.
"""
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness, models

ROOT = Path(__file__).resolve().parents[2]
BENCH = harness.load_benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
TRIMMED_TRAFFIC = Path(__file__).resolve().parent / "data" / "trimmed" / \
    "traffic.json"


def tiny_files(cell_name: str) -> dict:
    """The cell's files at a test size, as the model's file cuts them: the
    model's widths are kept, the data and the federation shrink."""
    cell = harness.find_cell(BENCH, cell_name)
    files = harness.cell_files(BENCH, cell, ROOT)
    return models.of(files["config"]).test_size(files)


def trimmed_files() -> dict:
    """LeNet-5's cell with a traffic that asks for a trimmed mean (every
    client every round, beta 0.1, no eval) in place of its own, at a test
    size, held to LeNet-5's limits."""
    files = harness.cell_files(BENCH, harness.find_cell(
        BENCH, "lenet5.sweep-proposed"), ROOT)
    files["traffic"] = json.loads(TRIMMED_TRAFFIC.read_text())
    return models.of(files["config"]).test_size(files)


def run(cell_name: str, files: dict) -> dict:
    cell = harness.find_cell(BENCH, cell_name)
    return harness.run_cell(BENCH, cell, files, seed=2 ** 32 + 77,
                            seconds=0.01, trace=False,
                            t_start=time.perf_counter())


@pytest.fixture
def broken(monkeypatch):
    """Plant a fault in RoundEngine.block_step, under the trainer, or in
    the clients' data as the environment builds it."""
    from repro.api import experiment
    from repro.core.round_engine import RoundEngine
    original = RoundEngine.block_step

    def plant(kind, classes):
        if kind == "mean_for_trimmed":
            import jax.numpy as jnp
            from repro.core import aggregators
            from repro.kernels import ops

            def mean(self, grads, cweights):
                n = (cweights > 0).sum().astype(jnp.float32)
                return (ops.packed_weighted_grad_sum(grads, cweights) / n,
                        jnp.int32(0))
            monkeypatch.setattr(aggregators.TrimmedMean, "reduce", mean)
            return
        if kind == "wrong_labels":
            client_data_class = experiment.ClientData

            def client_data(x, y):
                return client_data_class(x, (y + 1) % classes)
            monkeypatch.setattr(experiment, "ClientData", client_data)
            return

        def block_step(self, w, v, store, cids, idxs, lams, counts, **kw):
            if kind == "half_batch":
                sw = np.ones(np.shape(idxs), np.float32)
                sw[..., sw.shape[-1] // 2:] = 0.0
                kw["sample_weights"] = sw
            out = original(self, w, v, store, cids, idxs, lams, counts, **kw)
            if kind == "state_unchanged":
                return (w, v) + tuple(out[2:])
            return out
        monkeypatch.setattr(RoundEngine, "block_step", block_step)
    return plant


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch",
                                   "wrong_labels"])
def test_a_planted_fault_makes_the_run_not_correct(cell, fault, broken):
    files = tiny_files(cell)
    if fault:
        broken(fault, files["config"]["data"]["classes"])
    out = run(cell, files)
    assert out["correct"] is (fault is None), out["compared"]
    if fault is None:
        assert out["compared"]["unchecked_programs"]["value"] == 0
    assert out["attempted"] > 0
    assert list(out["metrics"]) == ["rounds_per_s", "setup_s"]
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("cell", CELLS)
def test_a_check_that_misses_a_block_program_is_not_correct(cell):
    files = tiny_files(cell)
    files["traffic"]["check_rounds"] = 1      # the K = 1 block alone
    out = run(cell, files)
    assert out["compared"]["unchecked_programs"]["value"] > 0
    assert out["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_fails_the_limits(cell):
    files = tiny_files(cell)
    cfg, tr = files["config"], files["traffic"]
    _, env, r, images = harness.set_up(cfg, tr, 5)
    check = harness.run_check(r, cfg, tr, 5)
    got = harness.readings(check, r.trainer.clients, images, cfg, tr,
                           ("control",))
    for v in got.values():
        v["unchecked_programs"] = 0
    assert got["program"]["foreign_rows"] == 0
    assert [b[:2] for b in got["program"]["by_block"]] == [
        b[:2] for b in check.blocks]
    assert harness.judge(got["program"], files["limits"])[0], got["program"]
    assert not harness.judge(got["control"], files["limits"])[0], \
        got["control"]


@pytest.mark.parametrize("fault", [None, "mean_for_trimmed"])
def test_a_trimmed_mean_run_is_correct_and_not_with_the_mean_in_its_place(
        fault, broken):
    files = trimmed_files()
    if fault:
        broken(fault, files["config"]["data"]["classes"])
    out = run("lenet5.sweep-proposed", files)
    assert out["correct"] is (fault is None), out["compared"]
    assert out["compared"]["unchecked_programs"]["value"] == 0


def test_every_stand_in_fails_the_limits_of_a_trimmed_mean_cell():
    files = trimmed_files()
    cfg, tr = files["config"], files["traffic"]
    _, env, r, images = harness.set_up(cfg, tr, 6)
    check = harness.run_check(r, cfg, tr, 6)
    stand_ins = harness.stand_ins_of(tr)
    assert stand_ins == ("control", "half_batch", "mean_for_trimmed")
    got = harness.readings(check, r.trainer.clients, images, cfg, tr,
                           stand_ins)
    for v in got.values():
        v["unchecked_programs"] = 0
    assert harness.judge(got["program"], files["limits"])[0], got["program"]
    for name in stand_ins:
        assert not harness.judge(got[name], files["limits"])[0], \
            (name, got[name])
