"""The reduction from a profiler trace to busy time, op totals and idle
gaps (bench/trace.py)."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6   # ns


def _planes():
    host = ("/host:CPU", [("python", [
        ("bench:traced", 0.0, 100 * MS),
        ("bench:unit", 0.0, 100 * MS),
        ("bench:eval", 60 * MS, 20 * MS),
        ("unrelated", 10 * MS, 5 * MS),
    ])])
    dev0 = ("/device:TPU:0", [
        ("XLA Ops", [("fusion.1", -5 * MS, 15 * MS),      # clipped to 0..10
                     ("conv", 5 * MS, 20 * MS),           # overlaps: 5..25
                     ("fusion.1", 40 * MS, 10 * MS),
                     ("conv", 95 * MS, 10 * MS)]),        # clipped: 95..100
        ("XLA Modules", [("jit_block", 0.0, 100 * MS)]),
    ])
    dev1 = ("/device:TPU:1", [("XLA Ops", [("conv", 0.0, 50 * MS)])])
    return [host, dev0, dev1]


def test_busy_ops_and_idle_gaps_of_a_hand_made_trace():
    s = trace.reduce_planes(_planes(), devices=1)
    assert s.window_s == pytest.approx(0.1)
    # union of [0,10] [5,25] [40,50] [95,100] = 25 + 10 + 5 = 40 ms
    assert s.busy_s == pytest.approx(0.040)
    # self time: the part of fusion.1's first event that conv overlaps
    # goes to conv
    assert s.op_seconds["conv"] == pytest.approx(0.025)
    assert s.op_seconds["fusion.1"] == pytest.approx(0.015)
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s)
    assert s.op_calls == {"fusion.1": 2, "conv": 2}
    # gaps 25..40 and 50..60 in "unit"; the eval span covers 60..80's
    # midpoint 72.5 of the gap 50..95 -> that gap is the eval's
    assert s.idle_gaps["unit"] == pytest.approx(0.015)
    assert s.idle_gaps["eval"] == pytest.approx(0.045)


def test_busy_time_is_averaged_over_the_devices_used():
    s = trace.reduce_planes(_planes(), devices=2)
    assert s.devices == 2
    assert s.busy_s == pytest.approx((0.040 + 0.050) / 2)


def test_no_window_span_or_no_device_op_reads_nothing():
    planes = _planes()
    assert trace.reduce_planes(planes[1:]) is None
    assert trace.reduce_planes(planes[:1]) is None


def _union_by_cuts(events, w0, w1):
    """Busy seconds by brute force: cut the window at every event edge and
    count each piece that some event covers."""
    cuts = sorted({w0, w1} | {min(max(t, w0), w1) for _, a, d in events
                              for t in (a, a + d)})
    busy = 0.0
    for lo, hi in zip(cuts, cuts[1:]):
        mid = (lo + hi) / 2
        if any(a <= mid < a + d for _, a, d in events):
            busy += hi - lo
    return busy * 1e-9


@pytest.mark.skipif(not (DATA / "chip_trace.json.gz").exists(),
                    reason="no recorded chip trace")
def test_a_trace_recorded_on_the_chip():
    """A stretch of a traced window recorded on one TPU v5e: the reduction's
    busy time agrees with a brute-force sampling of the same events."""
    rec = json.loads(gzip.decompress((DATA / "chip_trace.json.gz")
                                     .read_bytes()))
    planes = [(p, [(ln, [tuple(e) for e in evs]) for ln, evs in lines])
              for p, lines in rec["planes"]]
    s = trace.reduce_planes(planes, devices=1)
    assert s is not None
    (w0, w1), = [(a, a + d) for _, lines in planes for _, evs in lines
                 for n, a, d in evs if n == trace.WINDOW_SPAN]
    ops = [e for p, lines in planes if p.startswith("/device:TPU:0")
           for ln, evs in lines if ln == trace.OPS_LINE for e in evs]
    assert s.busy_s == pytest.approx(_union_by_cuts(ops, w0, w1), rel=1e-9)
    assert 0 < s.busy_s <= s.window_s
    assert s.window_s == pytest.approx(rec["window_s"])
    assert sum(s.idle_gaps.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert rec["kernel"] in s.op_seconds


def test_nested_ops_count_their_self_time_under_short_names():
    host = ("/host:CPU", [("python", [("bench:traced", 0.0, 100 * MS)])])
    dev = ("/device:TPU:0", [("XLA Ops", [
        ("%while.3 = (s32[]) while(...)", 10 * MS, 50 * MS),
        ("%fusion.1 = f32[8] fusion(...)", 15 * MS, 10 * MS),
        ("%packed_fedsgd_update_weighted.8 = (f32[8]) custom-call(...)",
         30 * MS, 5 * MS),
        ("%fusion.1 = f32[8] fusion(...)", 70 * MS, 10 * MS)])])
    s = trace.reduce_planes([host, dev])
    assert s.busy_s == pytest.approx(0.060)
    assert s.op_seconds["while.3"] == pytest.approx(0.035)
    assert s.op_seconds["fusion.1"] == pytest.approx(0.020)
    assert s.op_seconds["packed_fedsgd_update_weighted.8"] == \
        pytest.approx(0.005)
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s)
