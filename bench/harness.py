"""One run of one cell: set-up, the measured window, the check.

Everything that belongs to one cell is data: `BENCHMARK.json` names the
cell's configuration and traffic, `configs/<config>.json` holds the model
and dataset sizes, `traffic/<traffic>.json` the federation, scheme and
window unit, `workloads/<cell>.json` the limits `correct` is held to, and
`metrics/<metric>.py` one reader per per-layer metric. What belongs to one
model (its data, the dataset the system is served, its weights, its plain
forward pass and FLOP count) is `models/<model>.py`, found by the
configuration's `model.name`. Adding a cell, a configuration, a model or
a metric adds files; nothing here names one.

A window unit is one whole federated run of the traffic's schedule through
the system's normal path (`Experiment` -> `Run.run` -> `FederatedTrainer`
-> `RoundEngine`):

* ``repeat``: the schedule is solved once in set-up; each unit resets the
  trainer to fresh weights from its seed and runs every round;
* ``sweep``: each unit is ``Experiment(spec).build(env=, trainer=)`` with
  only the run seed changed, then ``run()``: the per-cell path of a seed
  sweep, schedule solve included.

Set-up builds the trainer once, drives it through the first rounds of the
schedule from weights the benchmark made (the check), warms every program
with one whole unit, and hands the same trainer to the window.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import importlib.util
import json
import math
import shutil
import tempfile
import time
from contextlib import contextmanager
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; there are "
                   f"{[w['name'] for w in bench['workloads']]}")


def cell_files(bench: dict, cell: dict, root: Path = ROOT) -> dict:
    """The configuration, traffic and limits of `cell`, found by name."""
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "config": load_json(root / cfg_entry["file"]),
        "traffic": load_json(root / "bench" / "traffic"
                             / f"{cell['traffic']}.json"),
        "limits": load_json(root / "bench" / "workloads"
                            / f"{cell['name']}.json"),
    }


def load_reader(name: str, root: Path = ROOT):
    """The `read(ctx)` function of per-layer metric `name`."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: dict, kind: str) -> list[dict]:
    """The end-to-end (`kind="end_to_end"`) or per-layer metrics this cell
    reports: those without a `workloads` list, and those that list it."""
    return [m for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


# ---------------------------------------------------------------------------
# spans: host-clock intervals, mirrored into the profiler trace when on
# ---------------------------------------------------------------------------

class Spans:
    def __init__(self, annotate: bool = False):
        self.items: list[tuple[str, float, float]] = []
        self.annotate = annotate

    @contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.items.append((name, t0, t1))

    def between(self, name: str, t0: float, t1: float) -> list[float]:
        return [b - a for n, a, b in self.items
                if n == name and a >= t0 and b <= t1]

    def wrap(self, obj, attr: str, name: str):
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with self.span(name):
                return fn(*a, **k)
        setattr(obj, attr, wrapped)


# ---------------------------------------------------------------------------
# the system under test, from the cell's files
# ---------------------------------------------------------------------------

def make_spec(cfg: dict, traffic: dict, seed: int):
    from repro.api import (DataSpec, ExperimentSpec, ModelSpec, RunSpec,
                           SchemeSpec, WirelessSpec)
    d, t = cfg["data"], traffic
    noise = {"noise": d["noise"]} if "noise" in d else {}
    return ExperimentSpec(
        data=DataSpec(dataset="bench-" + cfg["name"], n_clients=t["clients"],
                      sigma=t["sigma"], n_train=d["train"], n_test=d["test"],
                      seed=t["partition_seed"], **noise),
        model=ModelSpec(name=cfg["program_model"]["name"],
                        kwargs=dict(cfg["program_model"]["kwargs"])),
        wireless=WirelessSpec(e0=t["e0"], t0=t["t0"], seed=t["channel_seed"],
                              table=cfg.get("table", "auto")),
        scheme=SchemeSpec(name=t["scheme"], rounds=t["rounds"], eta=t["eta"],
                          batch=t["batch"], ao=dict(t["ao"]),
                          aggregator=t["aggregator"],
                          aggregator_kwargs=dict(t["aggregator_kwargs"])),
        run=RunSpec(seed=seed, evaluate=t["evaluate"],
                    eval_every=t["eval_every"], stop_on_budget=False,
                    rounds_per_dispatch=t["rounds_per_dispatch"],
                    shards=t["shards"],
                    device_mem_budget=t["device_mem_budget"]))


def register_dataset(cfg: dict, data) -> None:
    """Serve the benchmark's data to the system under the name the spec
    gives, through its dataset registry, as the model's file says."""
    from bench import models
    from repro.api.registry import DATASETS
    model = models.of(cfg)
    DATASETS.register("bench-" + cfg["name"],
                      lambda spec: model.dataset(cfg, data), override=True)


def unit_seed(seed: int, unit: int) -> int:
    ss = np.random.SeedSequence([seed & 0xFFFFFFFF, seed >> 32, unit])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


@dataclasses.dataclass
class Check:
    """What the first rounds of the program produced, and their feed."""
    w0: object                 # the benchmark's weights (host pytree)
    losses: list
    blocks: list               # per block: (start, rounds, weights, grad),
                               # host leaves in the program's dtype
    feed: list                 # per round: {"cids", "idxs", "lam"}
    programs: set              # block shapes (rounds, clients) dispatched


def noting_shapes(engine, shapes: set):
    """Wrap `engine.block_step` so that each dispatch adds its block shape
    (rounds, clients) to `shapes`; returns the original to restore."""
    block_step = engine.block_step

    def noting(w, v, store, cids, *a, **kw):
        shapes.add(tuple(int(d) for d in np.shape(cids)))
        return block_step(w, v, store, cids, *a, **kw)
    engine.block_step = noting
    return block_step


def run_check(run, cfg: dict, traffic: dict, seed: int) -> Check:
    """Drive the built trainer through the schedule's first
    `check_rounds` rounds from weights the benchmark made, through the
    window's own call: the same `FederatedTrainer.run`, block programs and
    batch draws. The traffic sets `check_rounds` so that these rounds use
    every block program a window unit uses; the run reports any it
    missed (`unchecked_programs`)."""
    import jax
    from bench import reference
    from repro.api.callbacks import Callback

    class Snapshots(Callback):
        """Host copies, in their own dtype, of the weights and the
        broadcast gradient after each block: (start, rounds, weights,
        gradient)."""

        def __init__(self):
            self.blocks: list[tuple[int, int, list, list]] = []

        def on_block_end(self, start, n_rounds, trainer):
            host = lambda t: [np.asarray(a) for a in jax.tree.leaves(t)]
            self.blocks.append((start, n_rounds, host(trainer.params),
                                host(trainer.global_grad)))

    tr, env = run.trainer, run.env
    w0 = jax.jit(lambda k: reference.init_params(cfg, k))(
        jax.random.key(unit_seed(seed, 1 << 20)))
    if jax.tree.structure(w0) != jax.tree.structure(tr.params):
        raise RuntimeError("the benchmark's weights do not have the "
                           "system's parameter layout")
    check_seed = unit_seed(seed, (1 << 20) + 1)
    tr.reset(w0, check_seed)
    w0 = jax.tree.map(np.asarray, w0)
    feed: list = []
    programs: set = set()
    engine = tr.engine
    block_step = noting_shapes(engine, programs)
    noting = engine.block_step

    def recording(w, v, store, cids, idxs, lams, counts, **kw):
        for k in range(len(counts)):
            c = int(counts[k])
            feed.append({"cids": np.asarray(cids[k, :c]).copy(),
                         "idxs": np.asarray(idxs[k, :c]).copy(),
                         "lam": np.asarray(lams[k, :c], np.float64).copy()})
        return noting(w, v, store, cids, idxs, lams, counts, **kw)

    n = min(int(traffic["check_rounds"]), int(traffic["rounds"]))
    s = run.schedule
    short = dataclasses.replace(s, a=s.a[:n], lam=s.lam[:n],
                                power=s.power[:n], freq=s.freq[:n])
    snaps = Snapshots()
    engine.block_step = recording
    try:
        hist = tr.run(short, env.sp, env.ch.uplink, env.ch.downlink,
                      eval_fn=env.eval_fn if traffic["evaluate"] else None,
                      eval_every=traffic["eval_every"], callbacks=[snaps])
    finally:
        engine.block_step = block_step
    tiled = [b[0] for b in snaps.blocks] == list(
        np.cumsum([0] + [b[1] for b in snaps.blocks])[:-1])
    if len(feed) != n or not tiled or sum(b[1] for b in snaps.blocks) != n:
        raise RuntimeError(
            f"the check expected {n} rounds in block dispatches, got "
            f"{len(feed)} rounds in blocks {[b[:2] for b in snaps.blocks]}")
    return Check(w0=w0, losses=[m.train_loss for m in hist],
                 blocks=snaps.blocks, feed=feed, programs=programs)


def row_key(row: np.ndarray) -> bytes:
    """A digest of every byte of one row."""
    return hashlib.blake2b(np.ascontiguousarray(row).tobytes(),
                           digest_size=16).digest()


def row_index(x: np.ndarray) -> dict:
    """Rows of `x` by a digest of the whole row: rows that share a long
    prefix (a system prompt, a document asked about again) stay apart."""
    return {row_key(x[j]): j for j in range(len(x))}


def reference_rounds(check: Check, clients, data,
                     index: dict) -> tuple[list[dict], int]:
    """Each round's batches from the benchmark's own rows and labels.
    The program's client data says only which of the benchmark's rows a
    client's sample is; a sample that is no row of the benchmark's data
    is counted (`foreign_rows`) and fed as the program has it."""
    x_tr, y_tr = data[0], data[1]
    rounds, foreign = [], 0
    for f in check.feed:
        xs, ys = [], []
        for c, batch in zip(f["cids"], f["idxs"]):
            rows = []
            for i in batch:
                got = np.asarray(clients[c].x[i])
                j = index.get(row_key(got))
                if j is None or not np.array_equal(x_tr[j], got):
                    foreign += 1
                    j = None
                rows.append((got, clients[c].y[i]) if j is None
                            else (x_tr[j], y_tr[j]))
            xs.append(np.stack([x for x, _ in rows]))
            ys.append(np.asarray([y for _, y in rows]))
        rounds.append({"x": np.stack(xs), "y": np.stack(ys),
                       "lam": f["lam"]})
    return rounds, foreign


def stand_ins_of(traffic: dict) -> tuple:
    """The stand-ins a cell's limits are calibrated against: the control,
    the half-batch fault and, where the traffic asks for a trimmed mean,
    the mean in its place."""
    return ("control", "half_batch") + (
        ("mean_for_trimmed",) if traffic["aggregator"] == "trimmed_mean"
        else ())


def readings(check: Check, clients, data, cfg: dict, traffic: dict,
             stand_ins: tuple = ()) -> dict:
    """The compared numbers of the program against the float32 reference
    with the traffic's aggregator, and of each stand-in put in the
    program's place: "control" (the reference in bfloat16), "half_batch"
    (the reference with half of each batch left out, the mean taken over
    the rest) and "mean_for_trimmed" (the reference with the mean where
    the traffic asks for the trimmed mean). The reference follows each
    block from the state the program, or the stand-in, held at its
    start."""
    import jax
    import jax.numpy as jnp
    from bench import reference
    rounds, foreign = reference_rounds(check, clients, data,
                                       row_index(data[0]))
    agg = {"aggregator": traffic["aggregator"],
           "aggregator_kwargs": dict(traffic["aggregator_kwargs"])}
    kw = {"reference": agg, "control": {**agg, "dtype": jnp.bfloat16},
          "half_batch": {**agg, "half_batch": True},
          "mean_for_trimmed": {"aggregator": "mean"}}

    def model(name):
        key = (json.dumps(cfg, sort_keys=True), traffic["eta"],
               json.dumps(agg, sort_keys=True), name)
        if key not in _REFERENCES:        # one set of compiled rounds
            _REFERENCES[key] = reference.Reference(cfg, traffic["eta"],
                                                   **kw[name])
        return _REFERENCES[key]

    def against_reference(run):
        ref = reference.follow(model("reference"), check.w0, rounds, run)
        return reference.compare(run, ref, w0)

    w0 = jax.tree.leaves(check.w0)
    lengths = [b[1] for b in check.blocks]
    out = {"program": against_reference(
        {"losses": check.losses, "blocks": check.blocks})}
    out["program"]["foreign_rows"] = foreign
    for name in stand_ins:
        out[name] = against_reference(
            model(name).run(check.w0, rounds, blocks=lengths))
        out[name]["foreign_rows"] = 0
    return out


_REFERENCES: dict = {}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Whether every compared number is within its limit, and the numbers
    each beside its limit. A number that is missing is not within it."""
    shown = {k: {"value": numbers.get(k, math.nan),
                 "limit": limits["limits"][k]}
             for k in limits["limits"]}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in shown.values())
    return ok, shown


def set_up(cfg: dict, traffic: dict, seed: int):
    """The cell's data, environment, schedule and trainer, through the
    system's normal path. Returns (spec, env, run, data); the data are the
    benchmark's own, made by the model's file, read-only, and the system
    is given them."""
    from bench import models
    from repro.api import Experiment
    from repro.api.experiment import build_environment
    data = models.of(cfg).make_data(cfg, seed)
    for a in data:
        a.setflags(write=False)
    register_dataset(cfg, data)
    spec = make_spec(cfg, traffic, unit_seed(seed, 0))
    env = build_environment(spec)
    return spec, env, Experiment(spec).build(env=env), data


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(bench: dict, cell: dict, files: dict, *, seed: int,
             seconds: float, trace: bool, t_start: float) -> dict:
    import jax
    from repro.api import Experiment

    cfg, traffic, limits = files["config"], files["traffic"], files["limits"]
    spans = Spans(annotate=trace)
    chips = int(cell["chips"])
    devices = jax.devices()[:chips]

    # -- set-up -------------------------------------------------------------
    with spans.span("setup.build"):
        spec, env, run, data = set_up(cfg, traffic, seed)
    trainer = run.trainer
    with spans.span("setup.check"):
        check = run_check(run, cfg, traffic, seed)
    sweep = traffic["unit"] == "sweep"

    def one_unit(u: int) -> tuple[int, int]:
        s = unit_seed(seed, 2 + u)
        if sweep:
            sp = dataclasses.replace(spec, run=dataclasses.replace(
                spec.run, seed=s))
            with spans.span("sweep.build"):
                r = Experiment(sp).build(env=env, trainer=trainer)
        else:
            r = run
            trainer.reset(env.init_fn(jax.random.key(s)), s)
        res = r.run()
        bad = sum(1 for m in res.history if not math.isfinite(m.train_loss))
        return len(res.history), bad + trainer.n_fallback_rounds

    window_programs: set = set()
    with spans.span("setup.warmup"):
        block_step = noting_shapes(trainer.engine, window_programs)
        try:
            one_unit(-1)
        finally:
            trainer.engine.block_step = block_step
    # what set-up left on the heap is not the window's garbage to collect
    gc.collect()
    gc.freeze()
    if trace:
        spans.wrap(trainer.engine, "block_step", "engine.dispatch")
        spans.wrap(env, "eval_fn", "eval")
        spans.wrap(trainer, "run", "trainer.run")
    traces_before = trainer.engine.n_traces
    setup_s = time.perf_counter() - t_start

    # -- the window ---------------------------------------------------------
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    rounds = failed = units = traced_rounds = 0
    watch = Watch()
    w_start = time.perf_counter()
    while True:
        u_start = time.perf_counter()
        if trace and units == 0:
            jax.profiler.start_trace(tmp)
            with spans.span("traced"):
                with spans.span("unit"):
                    r, f = one_unit(units)
            jax.profiler.stop_trace()
            traced_rounds = r
        else:
            with spans.span("unit"):
                r, f = one_unit(units)
        rounds += r
        failed += f
        units += 1
        watch.unit_done(time.perf_counter() - u_start)
        if time.perf_counter() - w_start >= seconds:
            break
    w_end = time.perf_counter()
    watch.close()
    gc.unfreeze()
    compiles = trainer.engine.n_traces - traces_before
    if compiles:
        failed = rounds
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    # -- the check, after the window, the program's state freed -------------
    clients = trainer.clients
    selected_per_round = [int(a.sum()) for a in run.schedule.a]
    del one_unit, block_step, run, trainer, env, spec
    gc.collect()
    numbers = readings(check, clients, data, cfg, traffic)["program"]
    numbers["unchecked_programs"] = len(window_programs - check.programs)
    correct, numbers = judge(numbers, limits)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": rounds, "failed": failed}
    if not trace:
        vals = {"rounds_per_s": rounds / (w_end - w_start),
                "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell_metrics(bench, cell, "end_to_end")}
    else:
        from bench import trace as tr_mod
        path = tr_mod.find_xplane(tmp)
        summary = tr_mod.summarize(path, chips) if path else None
        shutil.rmtree(tmp, ignore_errors=True)
        ctx = Context(
            spans=spans, window=(w_start, w_end), rounds=rounds, units=units,
            compiles_in_window=compiles, memory_peak_bytes=peak,
            trace=summary, traced_rounds=traced_rounds,
            cfg=cfg, traffic=traffic, chips=chips,
            peaks=load_json(BENCH / "peaks.json")["devices"].get(
                devices[0].device_kind),
            selected_per_round=selected_per_round)
        metrics = {}
        for m in cell_metrics(bench, cell, "per_layer"):
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            out["breakdown"] = {
                "device_ops": sorted(summary.op_seconds.items(),
                                     key=lambda kv: -kv[1])[:10],
                "idle_gaps": sorted(summary.idle_gaps.items(),
                                    key=lambda kv: -kv[1])[:10]}
    out["device"] = device
    out["units"] = watch.report()
    out["compared"] = numbers
    return out


class Watch:
    """Per window unit: its seconds, the seconds the garbage collector ran
    and the compilations JAX reported, to tell a slow unit's cause."""

    def __init__(self):
        import jax
        self.seconds: list[float] = []
        self.gc_s: list[float] = []
        self.compiles: list[int] = []
        self._gc = self._n = 0
        self._t0 = None
        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._open = True

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self._gc += time.perf_counter() - self._t0
            self._t0 = None

    def _on_event(self, event, duration, **kw):
        if self._open and "compile" in event and "cache" not in event:
            self._n += 1

    def unit_done(self, seconds: float) -> None:
        self.seconds.append(seconds)
        self.gc_s.append(self._gc)
        self.compiles.append(self._n)
        self._gc = self._n = 0

    def close(self) -> None:
        self._open = False
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def report(self) -> dict:
        return {"seconds": self.seconds, "gc_s": self.gc_s,
                "compile_events": self.compiles}


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""
    spans: Spans
    window: tuple
    rounds: int
    units: int
    compiles_in_window: int
    memory_peak_bytes: int
    trace: object              # trace.TraceSummary or None
    traced_rounds: int
    cfg: dict
    traffic: dict
    chips: int
    peaks: dict | None
    selected_per_round: list

    def window_spans(self, name: str) -> list[float]:
        return self.spans.between(name, *self.window)

    def setup_spans(self, name: str) -> list[float]:
        return self.spans.between(name, 0.0, self.window[0])


def peak_entry(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]

