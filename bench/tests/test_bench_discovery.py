"""The harness finds cells, configurations, traffic, limits and per-layer
readers by name from files, and BENCHMARK.json resolves in full."""
import json
import shutil
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    w = harness.find_cell(BENCH, cell)
    files = harness.cell_files(BENCH, w, ROOT)
    cfg, traffic, limits = files["config"], files["traffic"], files["limits"]
    assert cfg["name"] == w["config"]
    assert traffic["unit"] in ("repeat", "sweep")
    assert set(limits["limits"]) <= {"loss_gap", "grad_gap", "change_gap",
                                     "foreign_rows", "unchecked_programs"}
    exact = {"foreign_rows", "unchecked_programs"}
    assert {k: limits["limits"][k] for k in exact} == dict.fromkeys(exact, 0)
    assert all(v > 0 for k, v in limits["limits"].items() if k not in exact)
    assert traffic["check_rounds"] > traffic["eval_every"]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(harness.load_reader(metric, ROOT))


def test_configs_match_their_files():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] == []


def test_a_new_cell_config_and_metric_are_found_from_new_files(tmp_path):
    """A later change adds a cell by adding files only: the harness finds
    the new configuration, traffic, limits and reader by their names."""
    root = tmp_path
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/lenet5-mnist.json").read_text())
    cfg["name"] = "lenet5-wide"
    cfg["model"]["widths"] = [12, 32, 120, 84]
    (root / "bench/configs/lenet5-wide.json").write_text(json.dumps(cfg))
    traffic = json.loads(
        (ROOT / "bench/traffic/sweep-proposed.json").read_text())
    traffic["clients"] = 40
    (root / "bench/traffic/sweep-c40.json").write_text(json.dumps(traffic))
    (root / "bench/workloads/lenet5w.sweep-c40.json").write_text(json.dumps(
        {"limits": {"loss_gap": 1.0, "grad_gap": 1.0, "change_gap": 1.0}}))
    (root / "bench/metrics/unit.count.py").write_text(
        "def read(ctx):\n    return ctx.units\n")
    bench["configs"].append({"name": "lenet5-wide", "source": "x",
                             "file": "bench/configs/lenet5-wide.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "lenet5w.sweep-c40",
                               "config": "lenet5-wide",
                               "traffic": "sweep-c40", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "unit.count", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "rounds_per_s",
                               "workloads": ["lenet5w.sweep-c40"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    b = harness.load_benchmark(root)
    cell = harness.find_cell(b, "lenet5w.sweep-c40")
    files = harness.cell_files(b, cell, root)
    assert files["config"]["model"]["widths"] == [12, 32, 120, 84]
    assert files["traffic"]["clients"] == 40
    assert files["limits"]["limits"]["loss_gap"] == 1.0
    names = [m["name"] for m in harness.cell_metrics(b, cell, "per_layer")]
    assert "unit.count" in names and "sweep.build_ms" not in names
    other = harness.find_cell(b, "resnet44.fixsel-c20")
    assert "unit.count" not in [
        m["name"] for m in harness.cell_metrics(b, other, "per_layer")]
    ctx = type("Ctx", (), {"units": 7})()
    assert harness.load_reader("unit.count", root)(ctx) == 7
    with pytest.raises(KeyError):
        harness.find_cell(b, "no.such-cell")


def test_unknown_device_kind_is_an_error():
    assert harness.peak_entry("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        harness.peak_entry("TPU v9 imaginary")


def test_unit_seeds_are_fixed_and_distinct_for_large_seeds():
    s = 2 ** 33 + 12345
    a = [harness.unit_seed(s, u) for u in range(5)]
    assert a == [harness.unit_seed(s, u) for u in range(5)]
    assert len(set(a)) == 5
    assert all(0 <= x < 2 ** 31 for x in a)
    assert harness.unit_seed(s + 1, 0) != a[0]
