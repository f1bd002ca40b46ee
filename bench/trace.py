"""Reduction of a profiler trace to what the per-layer readers need.

The JAX profiler writes an ``.xplane.pb`` file. Device planes are named
``/device:TPU:<n>``; each holds an ``XLA Ops`` line with one event per
operation that ran. The benchmark's own spans are ``TraceAnnotation``s on a
host thread line, named ``bench:<span>``; the one named ``bench:traced``
bounds the traced window.

An op event is named by its HLO instruction (``%fusion.12 = f32[...]
fusion(...)``); the reduction keeps the instruction's name (``fusion.12``;
a Pallas kernel's is its wrapper's, ``packed_fedsgd_update_weighted.8``).
Ops nest: a loop's event spans the events of its body. An op's seconds
are its self time, its span less the spans of the ops directly inside it.

Busy time is the union of the op intervals inside the window, per device,
averaged over the devices used. An idle gap is a stretch of the window with
no op on the device; it is put down to the innermost benchmark span the
host was in at the gap's midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "bench:traced"
SPAN_PREFIX = "bench:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                        # mean over devices used
    op_seconds: dict                     # op name -> seconds, summed
    op_calls: dict                       # op name -> number of events
    idle_gaps: dict                      # host span -> idle seconds
    devices: int


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(directory: str) -> str | None:
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def _union(intervals: list) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce_planes(planes, devices: int | None = None) -> TraceSummary | None:
    """`planes`: [(plane name, [(line name, [(name, start_ns, dur_ns)])])].
    Returns None where the trace holds no window span or no device op."""
    window, spans = None, []
    dev = {}
    for pname, lines in planes:
        if pname.startswith("/device:") and "CUSTOM" not in pname:
            ops = [e for ln, evs in lines if ln == OPS_LINE for e in evs]
            if ops:
                dev[pname] = ops
            continue
        for _, evs in lines:
            for name, start, dur in evs:
                if name == WINDOW_SPAN:
                    window = (start, start + dur)
                elif name.startswith(SPAN_PREFIX):
                    spans.append((start, start + dur, name[len(SPAN_PREFIX):]))
    if window is None or not dev:
        return None
    names = sorted(dev, key=lambda n: int(n.rsplit(":", 1)[-1])
                   if n.rsplit(":", 1)[-1].isdigit() else 0)
    if devices is not None:
        names = names[:devices]
    w0, w1 = window
    op_s: dict = {}
    op_n: dict = {}
    busy_total = 0.0
    gaps: dict = {}
    for i, pname in enumerate(names):
        iv = []
        for name, start, dur in dev[pname]:
            a, b = max(start, w0), min(start + dur, w1)
            if b > a:
                iv.append((a, -b, op_name(name)))
        iv.sort()
        own = [-b - a for a, b, _ in iv]
        stack: list = []
        for j, (a, nb, _) in enumerate(iv):
            while stack and -iv[stack[-1]][1] <= a:
                stack.pop()
            if stack:                    # overlap with the op it sits in
                own[stack[-1]] -= min(-nb, -iv[stack[-1]][1]) - a
            stack.append(j)
        for (_, _, name), t in zip(iv, own):
            op_s[name] = op_s.get(name, 0.0) + t * 1e-9
            op_n[name] = op_n.get(name, 0) + 1
        busy = _union([(a, -nb) for a, nb, _ in iv])
        busy_total += sum(b - a for a, b in busy) * 1e-9
        if i:
            continue                     # idle gaps from the first device
        prev = w0
        for a, b in busy + [[w1, w1]]:
            if a > prev:
                mid = (a + prev) / 2
                inner = [s for s in spans if s[0] <= mid < s[1]]
                who = max(inner)[2] if inner else "outside any span"
                gaps[who] = gaps.get(who, 0.0) + (a - prev) * 1e-9
            prev = max(prev, b)
    n = len(names)
    return TraceSummary(window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n,
                        op_seconds={k: v / n for k, v in op_s.items()},
                        op_calls={k: v / n for k, v in op_n.items()},
                        idle_gaps=gaps, devices=n)


def read_planes(path: str):
    """The planes of an ``.xplane.pb`` file in `reduce_planes`' form."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for p in pd.planes:
        lines = []
        for ln in p.lines:
            lines.append((ln.name, [(e.name, float(e.start_ns),
                                     float(e.duration_ns))
                                    for e in ln.events]))
        out.append((p.name, lines))
    return out


def summarize(path: str, devices: int | None = None) -> TraceSummary | None:
    return reduce_planes(read_planes(path), devices)
