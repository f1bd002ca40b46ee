"""A configuration of a model the benchmark had no file for is added by
adding files alone: the model's file, its configuration, traffic, limits
and entries in BENCHMARK.json, planted here under a temporary root beside
an unchanged copy of bench/. The system's registered `mlp-edge` then runs
through `run_cell` from that copy, in a process of its own, on the CPU at
the size its file's `test_size` gives: a sound run is correct, and a step
that returns its state unchanged or leaves half of each batch out is not.
A model with no file is an error that names the missing file."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import flops, harness, reference

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data" / "mlp-edge"
CELL = "mlp.fixsel-c2"

DRIVE = r'''
import json, sys, time
import numpy as np
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from bench import flops, harness, models
from repro.core.round_engine import RoundEngine

original = RoundEngine.block_step


def planted(kind):
    def block_step(self, w, v, store, cids, idxs, lams, counts, **kw):
        if kind == "half_batch":
            sw = np.ones(np.shape(idxs), np.float32)
            sw[..., sw.shape[-1] // 2:] = 0.0
            kw["sample_weights"] = sw
        out = original(self, w, v, store, cids, idxs, lams, counts, **kw)
        if kind == "state_unchanged":
            return (w, v) + tuple(out[2:])
        return out
    return block_step


bench = harness.load_benchmark()
cell = harness.find_cell(bench, sys.argv[3])
files = harness.cell_files(bench, cell)
files = models.of(files["config"]).test_size(files)
for fault in ("none", "state_unchanged", "half_batch"):
    RoundEngine.block_step = original if fault == "none" else planted(fault)
    out = harness.run_cell(bench, cell, files, seed=2 ** 32 + 91,
                           seconds=0.01, trace=False,
                           t_start=time.perf_counter())
    print(json.dumps({"fault": fault, "correct": out["correct"],
                      "compared": out["compared"],
                      "metrics": sorted(out["metrics"]),
                      "flops": flops.forward_flops(files["config"]),
                      "harness": harness.__file__,
                      "model": models.of(files["config"]).__file__}),
          flush=True)
'''


def plant(root: Path) -> None:
    """bench/ as it is, with the new model's files added."""
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = root / "bench"
    shutil.copy(DATA / "model.py", b / "models" / "mlp-edge.py")
    shutil.copy(DATA / "config.json", b / "configs" / "mlp-edge-cifar10.json")
    shutil.copy(DATA / "traffic.json", b / "traffic" / "fixsel-c2.json")
    shutil.copy(DATA / "limits.json", b / "workloads" / f"{CELL}.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((DATA / "config.json").read_text())
    bench["configs"].append({"name": cfg["name"], "source": cfg["source"],
                             "file": "bench/configs/mlp-edge-cifar10.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": CELL, "config": cfg["name"],
                               "traffic": "fixsel-c2", "chips": 1,
                               "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_new_model_is_added_checked_and_counted_by_adding_files(tmp_path):
    plant(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", DRIVE, str(tmp_path), str(ROOT / "src"), CELL],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    runs = {r["fault"]: r for r in map(json.loads, p.stdout.splitlines())}
    for r in runs.values():
        assert Path(r["harness"]).is_relative_to(tmp_path)
        assert Path(r["model"]) == tmp_path / "bench/models/mlp-edge.py"
        assert r["metrics"] == ["rounds_per_s", "setup_s"]
    sound = runs["none"]
    assert sound["correct"] is True, sound["compared"]
    assert sound["compared"]["foreign_rows"]["value"] == 0
    assert sound["compared"]["unchecked_programs"]["value"] == 0
    for fault in ("state_unchanged", "half_batch"):
        assert runs[fault]["correct"] is False, runs[fault]["compared"]
    assert sound["flops"] == 2 * (32 * 32 * 3 * 128 + 128 * 10)


def test_a_model_without_a_file_is_an_error_that_names_it():
    cfg = json.loads((DATA / "config.json").read_text())
    cfg["model"]["name"] = "no-such-model"
    traffic = json.loads((DATA / "traffic.json").read_text())
    calls = [lambda: harness.set_up(cfg, traffic, 1),
             lambda: flops.forward_flops(cfg),
             lambda: reference.init_params(cfg, None),
             lambda: reference.Reference(cfg, 0.1)]
    for call in calls:
        with pytest.raises(KeyError, match="bench/models/no-such-model.py"):
            call()
