"""The per-layer readers of the program's span recorder (`repro.obs`),
fed a synthetic record and window: they keep the spans inside the window,
take self time and divide per round as documented, and read nothing where
the record of the window is not whole or, for the sweep metrics, where no
`sweep.build` span ran."""
import collections
import dataclasses
import statistics
from pathlib import Path

import pytest

from bench import harness
from repro import obs
from repro.obs import Record

ROOT = Path(__file__).resolve().parents[2]
W0, W1 = 100.0, 200.0
ROUNDS = 10


def rec(name, t0, t1, parent=None, counts=None):
    return Record(name, t0, t1, parent, counts)


# before the window: set-up's spans, then the window's
RECORDS = [
    rec("trainer.plan", 10.0, 11.0),
    rec("trainer.wait", 12.0, 12.5, "trainer.materialize"),
    rec("trainer.materialize", 12.0, 13.0, counts={"d2h": 99}),
    rec("trainer.plan", 101.0, 101.010),
    rec("trainer.draw", 101.010, 101.016),
    rec("trainer.draw", 101.020, 101.024),
    rec("trainer.slice", 101.025, 101.028),
    rec("trainer.wait", 101.030, 101.070, "trainer.materialize"),
    rec("trainer.materialize", 101.030, 101.080, counts={"d2h": 8}),
    rec("trainer.unpack", 101.081, 101.091),
    rec("eval.wait", 101.092, 101.093, "eval"),
    rec("eval.wait", 101.094, 101.097, "eval"),
    rec("eval", 101.091, 101.100, counts={"d2h": 4}),
    rec("trainer.reset", 150.0, 150.002),
    rec("trainer.reset", 160.0, 160.006),
    rec("trainer.reset", 170.0, 170.004),
    rec("experiment.init", 150.010, 150.013),
    rec("experiment.init", 160.010, 160.011),
    rec("experiment.init", 170.010, 170.012),
    rec("ao.resources", 150.020, 150.030),
    rec("ao.resources", 150.040, 150.045),
    rec("ao.pruning", 150.050, 150.060),
    rec("ao.selection", 150.070, 150.100),
    rec("ao.resources", 160.020, 160.022),
    rec("ao.selection", 160.070, 160.080),
    rec("ao.resources", 170.020, 170.021),
    rec("ao.selection", 170.070, 170.090),
    # closes after the window
    rec("trainer.plan", 199.0, 201.0),
]
SWEEP_BUILDS = [("sweep.build", 150.0, 150.5), ("sweep.build", 160.0, 160.5),
                ("sweep.build", 170.0, 170.5), ("sweep.build", 50.0, 50.5)]


@dataclasses.dataclass
class Ctx:
    spans: harness.Spans
    window: tuple = (W0, W1)
    rounds: int = ROUNDS


def ctx(builds=SWEEP_BUILDS):
    spans = harness.Spans()
    spans.items = list(builds)
    return Ctx(spans=spans)


@pytest.fixture
def recorder(monkeypatch):
    """The recorder holding RECORDS, with room for more."""
    d = collections.deque(RECORDS, maxlen=len(RECORDS) + 1)
    monkeypatch.setattr(obs, "_records", d)
    return d


def read(name, c):
    return harness.load_reader(name, ROOT)(c)


EXPECTED = {
    # only the window's spans, summed over the window's rounds
    "trainer.plan_ms_per_round": 10.0 / ROUNDS,
    "trainer.draw_ms_per_round": (6.0 + 4.0) / ROUNDS,
    "trainer.wait_ms_per_round": 40.0 / ROUNDS,
    "trainer.slice_ms_per_round": 3.0 / ROUNDS,
    # self time: materialize less the wait inside it
    "trainer.materialize_ms_per_round": (50.0 - 40.0) / ROUNDS,
    "trainer.unpack_ms_per_round": 10.0 / ROUNDS,
    "trainer.d2h_per_round": (8 + 4) / ROUNDS,
    "trainer.reset_ms": 4.0,
    # waits over eval calls
    "eval.wait_ms": (1.0 + 3.0) / 1,
    "sweep.init_ms": 2.0,
    # per sweep.build inside the window: sums, then the median
    "ao.resources_ms": statistics.median([15.0, 2.0, 1.0]),
    "ao.pruning_ms": statistics.median([10.0, 0.0, 0.0]),
    "ao.selection_ms": statistics.median([30.0, 10.0, 20.0]),
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_the_window(recorder, name):
    assert read(name, ctx()) == pytest.approx(EXPECTED[name], rel=1e-6)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_from_a_truncated_record(monkeypatch, name):
    """The recorder is full and its oldest record closed inside the
    window: spans of the window may have been dropped."""
    kept = [r for r in RECORDS if r.t0 > 101.015]
    monkeypatch.setattr(obs, "_records",
                        collections.deque(kept, maxlen=len(kept)))
    assert read(name, ctx()) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_full_recorder_whose_oldest_record_precedes_the_window_reads(
        monkeypatch, name):
    monkeypatch.setattr(obs, "_records",
                        collections.deque(RECORDS, maxlen=len(RECORDS)))
    assert read(name, ctx()) == pytest.approx(EXPECTED[name], rel=1e-6)


@pytest.mark.parametrize("name", ["ao.resources_ms", "ao.pruning_ms",
                                  "ao.selection_ms"])
def test_sweep_metrics_read_nothing_without_sweep_build(recorder, name):
    assert read(name, ctx(builds=[("sweep.build", 50.0, 50.5)])) is None
    assert read(name, ctx(builds=[])) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_readers_read_nothing_from_an_empty_window(recorder, name):
    c = ctx()
    c.window = (300.0, 400.0)
    assert read(name, c) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_readers_read_nothing_from_a_program_without_the_recorder(
        monkeypatch, name):
    """A program without `repro.obs` (an older checkout): no number, no
    error."""
    import sys

    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(name, ctx()) is None
