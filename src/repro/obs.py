"""Spans: what the host did, when, on the profiler's clock.

    with obs.span("trainer.materialize") as s:
        ...
        s.count("d2h")            # one device->host read

Each span records ``(name, t0, t1, parent, counts)`` on
``time.perf_counter()`` when it closes. ``parent`` is the name of the span
that was open on the same thread when it opened (the stack of open spans is
per thread, so spans opened on a prefetch thread nest under that thread's
spans, not the caller's). ``counts`` holds the integer counts set while the
span was open (``None`` if none were): counters are recorded at the
boundary where the work happens, with its time.

Every span also enters ``jax.profiler.TraceAnnotation(name)``: while a
profile is being captured it appears on the host thread's line, on the
same clock as the device's operations, so an idle stretch of the device
can be read against what the host was doing. Otherwise the annotation
costs only its own check.

The records go into a bounded in-memory flight recorder (the newest
``CAPACITY`` spans; the oldest are dropped). It is always on and writes
nothing during a run. After a slow or stalled run, read it with
``recent()`` (the newest spans), ``between(t0, t1)`` (the spans that ran
inside an interval of ``time.perf_counter()``) or ``durations(name, t0,
t1)`` (how long each span of one name took there), for example::

    t0 = time.perf_counter()
    Experiment(spec).run()
    for r in obs.between(t0, time.perf_counter()):
        print(r.name, r.parent, 1e3 * (r.t1 - r.t0), r.counts)

Span names: ``trainer.*`` (core/federated.py), ``eval`` and ``eval.wait``
(models/cnn.py), ``experiment.init`` (api/experiment.py), ``ao.*``
(core/optimizer_ao.py), ``cohort.wait`` (core/cohort_store.py),
``checkpoint.save`` (api/callbacks.py).
"""
from __future__ import annotations

import collections
import threading
import time
from typing import NamedTuple

import jax

CAPACITY = 65536


class Record(NamedTuple):
    name: str
    t0: float
    t1: float
    parent: str | None
    counts: dict | None


_records: collections.deque = collections.deque(maxlen=CAPACITY)
_local = threading.local()
_annotation = jax.profiler.TraceAnnotation
_clock = time.perf_counter


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """Context manager: one recorded span named `name` (see the module
    docstring). After it closes, ``t0`` and ``t1`` hold its interval."""

    __slots__ = ("name", "t0", "t1", "parent", "counts", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.counts = None

    def count(self, key: str, n: int = 1) -> None:
        """Add `n` to this span's count `key`."""
        c = self.counts
        if c is None:
            c = self.counts = {}
        c[key] = c.get(key, 0) + n

    def __enter__(self) -> "span":
        stack = _stack()
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self.t0 = _clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = t1 = _clock()
        self._ann.__exit__(None, None, None)
        _stack().pop()
        _records.append(Record(self.name, self.t0, t1, self.parent,
                               self.counts))
        return False


def count(key: str, n: int = 1) -> None:
    """Add `n` to count `key` of the innermost span open on this thread
    (nothing is recorded when none is open)."""
    stack = getattr(_local, "stack", None)
    if stack:
        stack[-1].count(key, n)


def recent(n: int | None = None) -> list[Record]:
    """The newest `n` records (all that are kept when `n` is None), oldest
    first."""
    recs = list(_records)
    return recs if n is None else recs[-n:] if n > 0 else []


def between(t0: float, t1: float) -> list[Record] | None:
    """The records of the spans that opened at or after `t0` and closed at
    or before `t1`, in the order they closed. None when the recorder may
    have dropped some of them: it is full and its oldest record closed
    after `t0`."""
    recs = list(_records)
    if len(recs) == _records.maxlen and recs and recs[0].t1 > t0:
        return None
    return [r for r in recs if r.t0 >= t0 and r.t1 <= t1]


def durations(name: str, t0: float, t1: float) -> list[float] | None:
    """The seconds of each span named `name` inside [t0, t1] (see
    `between`), or None when the record of the interval is not whole."""
    recs = between(t0, t1)
    return None if recs is None else [r.t1 - r.t0 for r in recs
                                      if r.name == name]
