"""The `ao.resources_reuse_share` reader, fed a synthetic record and
window: it sums the `p2.pairs` and `p2.solved` counts of the
`ao.resources` spans inside the window's `sweep.build` spans, and reads
nothing where the record of the window is not whole, where no
`sweep.build` span ran, or where the spans carry no such counts."""
import collections
import dataclasses
import sys
from pathlib import Path

import pytest

from bench import harness
from repro import obs
from repro.obs import Record

ROOT = Path(__file__).resolve().parents[2]
W0, W1 = 100.0, 200.0
NAME = "ao.resources_reuse_share"


def rec(name, t0, t1, counts=None):
    return Record(name, t0, t1, None, counts)


RECORDS = [
    # set-up's solve, before the window
    rec("ao.resources", 10.0, 10.5, {"p2.pairs": 900, "p2.solved": 900}),
    rec("trainer.plan", 101.0, 101.010),
    rec("ao.resources", 150.020, 150.030, {"p2.pairs": 600, "p2.solved": 10}),
    rec("ao.resources", 150.040, 150.045, {"p2.pairs": 60, "p2.solved": 2}),
    rec("ao.pruning", 150.050, 150.060),
    rec("ao.resources", 160.020, 160.022, {"p2.pairs": 60, "p2.solved": 11}),
    rec("ao.resources", 170.020, 170.021, {"p2.pairs": 300, "p2.solved": 5}),
    # a solve inside the window but outside any sweep.build
    rec("ao.resources", 180.000, 180.010, {"p2.pairs": 50, "p2.solved": 50}),
    # closes after the window
    rec("trainer.plan", 199.0, 201.0),
]
SWEEP_BUILDS = [("sweep.build", 150.0, 150.5), ("sweep.build", 160.0, 160.5),
                ("sweep.build", 170.0, 170.5), ("sweep.build", 10.0, 10.6)]
EXPECTED = 100.0 * (1.0 - (10 + 2 + 11 + 5) / (600 + 60 + 60 + 300))


@dataclasses.dataclass
class Ctx:
    spans: harness.Spans
    window: tuple = (W0, W1)
    rounds: int = 10


def ctx(builds=SWEEP_BUILDS):
    spans = harness.Spans()
    spans.items = list(builds)
    return Ctx(spans=spans)


def hold(monkeypatch, records, room=1):
    monkeypatch.setattr(obs, "_records",
                        collections.deque(records,
                                          maxlen=len(records) + room))


def read(c):
    return harness.load_reader(NAME, ROOT)(c)


@pytest.mark.parametrize("room", [1, 0], ids=["room", "full"])
def test_reads_the_counts_of_the_window_builds(monkeypatch, room):
    """A full recorder whose oldest record precedes the window is whole."""
    hold(monkeypatch, RECORDS, room)
    assert read(ctx()) == pytest.approx(EXPECTED, rel=1e-12)


def test_reads_nothing_from_a_truncated_record(monkeypatch):
    hold(monkeypatch, [r for r in RECORDS if r.t0 > 101.005], room=0)
    assert read(ctx()) is None


@pytest.mark.parametrize("builds", [[("sweep.build", 10.0, 10.6)], []],
                         ids=["outside-window", "none"])
def test_reads_nothing_without_sweep_build(monkeypatch, builds):
    hold(monkeypatch, RECORDS)
    assert read(ctx(builds)) is None


def test_reads_nothing_from_spans_without_counts(monkeypatch):
    """A program whose `ao.resources` spans carry no `p2.*` counts."""
    hold(monkeypatch, [r._replace(counts=None) for r in RECORDS])
    assert read(ctx()) is None


def test_reads_nothing_from_an_empty_window(monkeypatch):
    hold(monkeypatch, RECORDS)
    c = ctx()
    c.window = (300.0, 400.0)
    assert read(c) is None


def test_reads_nothing_from_a_program_without_the_recorder(monkeypatch):
    """A program without `repro.obs`: no number, no error."""
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(ctx()) is None
