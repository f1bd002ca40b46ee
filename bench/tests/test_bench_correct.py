"""`correct` at a size a test run holds, on the CPU.

For each cell: a sound run of the harness passes; the same run with the
timed path broken underneath comes out not correct, once for each fault a
training cell can have (a step that returns its state unchanged; half of
each batch left out, the mean taken over the rest; a client's labels
altered where its data is built); a check too short to reach every block
program the window uses comes out not correct; and the control, the
reference in bfloat16 put in the program's place, fails the cell's limits.
The device check is skipped; everything else is a whole run.
"""
import copy
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = harness.load_benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]


def tiny_files(cell_name: str) -> dict:
    """The cell's files at a test size: the model's widths are kept, the
    ResNet is cut to depth 8, the data and the federation shrink."""
    cell = harness.find_cell(BENCH, cell_name)
    files = copy.deepcopy(harness.cell_files(BENCH, cell, ROOT))
    cfg, tr = files["config"], files["traffic"]
    if cfg["model"]["name"] == "resnet":
        cfg["model"]["depth"] = 8
        cfg["program_model"]["kwargs"]["depth"] = 8
    cfg["data"].update(train=800, test=100)
    tr.update(clients=4, rounds=6, eval_every=3, rounds_per_dispatch=2)
    return files


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

    def plant(kind):
        if kind == "wrong_labels":
            client_data_class = experiment.ClientData

            def client_data(x, y):
                return client_data_class(x, (y + 1) % 10)
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
    if fault:
        broken(fault)
    out = run(cell, tiny_files(cell))
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
