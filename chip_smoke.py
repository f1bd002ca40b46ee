#!/usr/bin/env python3
"""On-chip check of the federated round engine.

    python chip_smoke.py [--seed N]      # one TPU: kernels, main, cohort64
    python chip_smoke.py --chips 4       # four TPUs: sharded vs one shard

Drives the normal path — ``Experiment(spec).run()`` -> ``FederatedTrainer``
-> ``RoundEngine`` with the packed backend and its Pallas kernels — on
random weights and synthetic data made from ``--seed`` (nothing is
downloaded), and checks what comes out against the repository's own
references:

* kernels  — each Pallas kernel of the packed engine against its XLA
  mirror at ResNet-20's packed width, exact where the CPU tests are exact;
* main     — the paper's evaluation model (ResNet-20, width 16) under the
  proposed scheme for 8 rounds, against ``backend="reference"``;
* cohort64 — 64 clients every round, per-client pruning ratios and the
  trimmed-mean reducer, against ``backend="reference"``;
* ``--chips 4`` runs only the main and cohort64 specs with the client axis
  sharded over four chips, each against the same spec on one shard.

Every phase prints what it saw. The last line of standard output is one
JSON object naming the device. The script exits non-zero, and prints no
such line, when JAX finds no TPU or when any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Per-round train losses are compared in two tiers, as relative gaps.
# Rounds 0 and 1 are held tight: round 0's loss checks the threshold, the
# mask and the forward pass, round 1's the first gradient, aggregate and
# update, before any difference can compound; a wrong kernel moves them by
# far more (round 1's step moves the loss by half). Later rounds inherit
# every earlier gap, which ResNet-20's unstable first rounds at eta 0.1
# (loss 3.7 -> 5.9 -> 2.4) amplify about threefold per round, so they are
# held only to the loose tier, which catches a run that diverges.
#
# Packed engine vs eager reference: at the default matmul precision a TPU
# multiplies fp32 convolution operands in one bf16 pass, and the fused
# round program and the reference's per-client program need not round the
# same intermediates; the Pallas aggregate may also round w - eta*g once
# where the reference rounds twice (core/federated.py).
REF_TIGHT, REF_LOOSE = 1e-3, 5e-2
# Sharded (four chips) vs one shard, mean path: the cross-shard psum
# reassociates the client sum, about 1 ulp per round
# (core/round_engine.py). The robust path all-gathers the full client
# stack and reduces it replicated, so it must match bit for bit
# (DESIGN.md §11).
SHARD_TIGHT, SHARD_LOOSE = 1e-5, 5e-2

# jitted entry points of RoundEngine that the normal (fault-free,
# noiseless, replicated-store) path dispatches to
_ENTRIES = ("_blk_shared", "_blk_multi", "_step_shared", "_step_multi")


# The schedules are solved under the paper's budgets (WirelessSpec's E0 = 4 J,
# T0 = 40 s), which is what makes them prune: with budgets too loose to bind,
# the solver picks lambda = 0 and one client per round, and no threshold,
# mask or per-client kernel would do any work. `stop_on_budget=False` keeps
# the ledger from cutting a run short at the budget's edge.


def main_spec(seed: int, **run):
    from repro.api import (DataSpec, ExperimentSpec, ModelSpec, RunSpec,
                           SchemeSpec, WirelessSpec)
    return ExperimentSpec(
        data=DataSpec(dataset="synthetic-cifar10", n_clients=20, sigma=1.0,
                      seed=seed),
        model=ModelSpec(name="resnet", kwargs={"depth": 20, "width": 16}),
        wireless=WirelessSpec(seed=seed),
        scheme=SchemeSpec(name="proposed", rounds=8, eta=0.1, batch=32,
                          ao={"outer_iters": 1}),
        run=RunSpec(seed=seed, evaluate=False, stop_on_budget=False,
                    rounds_per_dispatch="auto", **run))


def cohort64_spec(seed: int, **run):
    from repro.api import (DataSpec, ExperimentSpec, ModelSpec, RunSpec,
                           SchemeSpec, WirelessSpec)
    return ExperimentSpec(
        data=DataSpec(dataset="synthetic-cifar10", n_clients=64, sigma=1.0,
                      n_train=6400, seed=seed),
        model=ModelSpec(name="resnet", kwargs={"depth": 20, "width": 16}),
        wireless=WirelessSpec(seed=seed),
        scheme=SchemeSpec(name="fixed_selection", rounds=4, eta=0.1,
                          batch=32, ao={"outer_iters": 1},
                          aggregator="trimmed_mean",
                          aggregator_kwargs={"beta": 0.1}),
        run=RunSpec(seed=seed, evaluate=False, stop_on_budget=False,
                    rounds_per_dispatch="auto", **run))


class _Recorder:
    """Stands in for one jitted engine entry point and keeps the abstract
    arguments of its first call, so the program it ran can be compiled
    again (from the compile cache) and inspected."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        import jax
        if self.args is None:
            # an uncommitted array lets jit place it; keep that freedom
            self.args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, weak_type=a.weak_type,
                    sharding=a.sharding if a.committed else None)
                if isinstance(a, jax.Array) else a, args)
        return self.fn(*args)

    def compiled_text(self) -> str:
        return self.fn.lower(*self.args).compile().as_text()


@dataclasses.dataclass
class Outcome:
    losses: "object"            # np.ndarray [rounds]
    params: list                # host copies of the final param leaves
    seconds: float
    trainer: "object"
    run: "object"


def _execute(spec, *, record: bool = False, env=None, trainer=None):
    import jax
    import numpy as np
    from repro.api import Experiment
    run = Experiment(spec).build(env=env, trainer=trainer)
    if record and run.trainer.engine is not None:
        for name in _ENTRIES:
            setattr(run.trainer.engine, name,
                    _Recorder(getattr(run.trainer.engine, name)))
    t0 = time.perf_counter()
    res = run.run()
    params = [np.asarray(p) for p in jax.tree.leaves(run.trainer.params)]
    seconds = time.perf_counter() - t0
    losses = np.asarray([m.train_loss for m in res.history])
    return Outcome(losses, params, seconds, run.trainer, run)


def _param_gap(a: list, b: list) -> tuple[float, float]:
    """(max |a - b|, max |a - b| / max |b|) over every leaf."""
    import numpy as np
    d = max(float(np.max(np.abs(x - y))) for x, y in zip(a, b))
    scale = max(float(np.max(np.abs(y))) for y in b)
    return d, d / scale


def _custom_calls(trainer) -> tuple[int, int]:
    """(programs inspected, tpu_custom_call count over them) for every
    engine entry point the run dispatched to."""
    ran = [getattr(trainer.engine, n) for n in _ENTRIES]
    ran = [r for r in ran if isinstance(r, _Recorder) and r.args is not None]
    if not ran:
        raise AssertionError("the packed engine dispatched no round program")
    counts = [r.compiled_text().count("tpu_custom_call") for r in ran]
    if min(counts) == 0:
        raise AssertionError(
            f"a compiled round program holds no Pallas kernel "
            f"(tpu_custom_call per program: {counts})")
    return len(counts), sum(counts)


def _losses(out: Outcome) -> str:
    return " ".join(f"{x:.6f}" for x in out.losses)


def _trajectory_gap(tag: str, what: str, got: Outcome, want: Outcome,
                    tight: float, loose: float) -> None:
    """Print the per-round relative loss gap of `got` against `want` and
    hold rounds 0-1 to `tight`, every round to `loose`."""
    import numpy as np
    rel = np.abs(got.losses - want.losses) / np.abs(want.losses)
    dp, dp_rel = _param_gap(got.params, want.params)
    print(f"[{tag}] {what} relative loss gap per round: "
          + " ".join(f"{x:.2e}" for x in rel), flush=True)
    print(f"[{tag}] largest {what} gap: rounds 0-1 {rel[:2].max():.3e} "
          f"(tolerance {tight:g}), all rounds {rel.max():.3e} (tolerance "
          f"{loose:g}); final params {dp:.3e} ({dp_rel:.3e} of max |w|)",
          flush=True)
    if not (rel[:2].max() <= tight and rel.max() <= loose):
        raise AssertionError(f"[{tag}] {what} gap outside its tolerance")


def _describe(tag: str, out: Outcome) -> None:
    eng = out.trainer.engine
    print(f"[{tag}] packed rows {eng.pack.rows} ({eng.pack.n_total} params), "
          f"client buckets {sorted(eng.buckets_used)}, "
          f"round buckets {sorted(eng.k_buckets_used)}, "
          f"shards {eng.shards}", flush=True)
    print(f"[{tag}] train loss per round: {_losses(out)}", flush=True)


def _check_finite(tag: str, out: Outcome) -> None:
    import numpy as np
    if not np.isfinite(out.losses).all():
        raise AssertionError(f"[{tag}] non-finite train loss: {out.losses}")
    if not all(np.isfinite(p).all() for p in out.params):
        raise AssertionError(f"[{tag}] non-finite parameters")


def phase_kernels(seed: int, rows: int = 2304,
                  clients: tuple[int, ...] = (8, 64)) -> None:
    """Each Pallas kernel vs its XLA mirror on the chip, at ResNet-20's
    packed width (2304 rows) and at C = 8 and 64 clients."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.round_engine import kth_smallest_threshold
    from repro.kernels import ops
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    w, v = f32(rows, 128), f32(rows, 128)
    pr = jnp.asarray((rng.random((rows, 128)) < 0.9), jnp.float32)
    t0 = time.perf_counter()

    def same(name, a, b):
        if not bool(jnp.all(a == b)):
            raise AssertionError(f"[kernels] {name}: Pallas != XLA mirror")

    q_p, m_p = ops.packed_importance_mask(w, v, pr, 0.2, impl="pallas")
    q_x, m_x = ops.packed_importance_mask(w, v, pr, 0.2, impl="xla")
    same("importance mask q", q_p, q_x)
    same("importance mask", m_p, m_x)
    same("exponent histogram",
         ops.packed_exponent_histogram(q_x, pr, impl="pallas"),
         ops.packed_exponent_histogram(q_x, pr, impl="xla"))
    ks = jnp.asarray([0, 1, 1000, int(pr.sum()) // 2], jnp.int32)
    same("threshold", kth_smallest_threshold(q_x, pr, ks, coarse="histogram",
                                             hist_impl="pallas"),
         kth_smallest_threshold(q_x, pr, ks, coarse="bisect"))
    worst = 0.0
    for c in clients:
        thr = jnp.asarray(rng.random(c) * 2.0, jnp.float32)
        same(f"batched masks C={c}",
             ops.packed_importance_masks(w, v, pr, thr, impl="pallas")[1],
             ops.packed_importance_masks(w, v, pr, thr, impl="xla")[1])
        g = f32(c, rows, 128)
        cw = jnp.asarray(np.arange(c) < c - 3, jnp.float32)
        inv = np.float32(1.0 / (c - 3))
        wp, gp, sp = ops.packed_fedsgd_update_weighted(w, g, cw, inv, 0.05,
                                                       impl="pallas")
        wx, gx, sx = ops.packed_fedsgd_update_weighted(w, g, cw, inv, 0.05,
                                                       impl="xla")
        same(f"aggregate mean C={c}", gp, gx)
        same(f"aggregate step C={c}", sp, sx)
        # the kernel may fuse w - eta*g into one rounding, skipping the
        # product's: at most one ulp of the step plus one of the result
        wx, sx = np.asarray(wx), np.asarray(sx)
        bound = np.spacing(np.abs(sx)) + np.spacing(np.abs(wx))
        worst = max(worst, float(np.max(np.abs(np.asarray(wp) - wx)
                                        / bound)))
        sv_p = ops.packed_client_rank_sort(g, cw, impl="pallas")
        sv_x = ops.packed_client_rank_sort(g, cw, impl="xla")
        same(f"rank sort C={c}", sv_p[:c - 3], sv_x[:c - 3])
    if worst > 1.0:
        raise AssertionError(f"[kernels] aggregate w' off by {worst:.2f} of "
                             "its one-rounding bound")
    print(f"[kernels] R={rows}, C in {clients}: mask, histogram, threshold, "
          f"batched masks, aggregate mean/step and rank sort equal the XLA "
          f"mirrors bit for bit; aggregate w' gap {worst:.2f} of one "
          f"rounding (ulp(eta*g) + ulp(w')); "
          f"compile+run {time.perf_counter() - t0:.1f} s", flush=True)


def phase_vs_reference(tag: str, spec) -> None:
    """Packed engine (Pallas kernels) on the normal path vs the eager
    reference backend."""
    import numpy as np
    out = _execute(spec, record=True)
    _describe(tag, out)
    _check_finite(tag, out)
    if out.trainer.n_fallback_rounds:
        raise AssertionError(f"[{tag}] {out.trainer.n_fallback_rounds} "
                             "rounds fell back to the eager reference")
    n_prog, n_calls = _custom_calls(out.trainer)
    print(f"[{tag}] tpu_custom_call: {n_calls} in {n_prog} compiled round "
          f"program(s); n_fallback_rounds 0", flush=True)
    warm = _execute(spec, env=out.run.env, trainer=out.trainer)
    if not np.array_equal(warm.losses, out.losses):
        raise AssertionError(f"[{tag}] a second run of the same spec gave "
                             "other losses")
    tl, ta = out.run.env.eval_fn(out.trainer.params)
    print(f"[{tag}] final eval: test loss {float(tl):.4f}, "
          f"accuracy {float(ta):.4f}", flush=True)
    if not np.isfinite(float(tl)):
        raise AssertionError(f"[{tag}] non-finite test loss")
    ref = _execute(dataclasses.replace(
        spec, run=dataclasses.replace(spec.run, backend="reference")),
        env=out.run.env)
    _check_finite(tag + "/reference", ref)
    print(f"[{tag}] reference loss per round: {_losses(ref)}", flush=True)
    print(f"[{tag}] seconds: packed compile+run {out.seconds:.1f}, warm "
          f"run {warm.seconds:.1f}; reference {ref.seconds:.1f}", flush=True)
    _trajectory_gap(tag, "packed-vs-reference", out, ref,
                    REF_TIGHT, REF_LOOSE)


def phase_sharded(tag: str, spec_fn, seed: int, *, bitwise: bool) -> None:
    """Client axis over four chips vs the same spec on one shard."""
    import numpy as np
    four = _execute(spec_fn(seed, shards=4), record=True)
    _describe(tag, four)
    _check_finite(tag, four)
    mesh = four.trainer.engine.mesh
    if mesh is None:
        raise AssertionError(f"[{tag}] shards=4 built no mesh")
    print(f"[{tag}] mesh devices {[d.id for d in mesh.devices.flat]}; "
          f"params on devices "
          f"{sorted(d.id for d in four.trainer._w.sharding.device_set)}",
          flush=True)
    if len(set(mesh.devices.flat)) != 4:
        raise AssertionError(f"[{tag}] the mesh does not span four chips")
    if four.trainer.n_fallback_rounds:
        raise AssertionError(f"[{tag}] rounds fell back to the reference")
    n_prog, n_calls = _custom_calls(four.trainer)
    print(f"[{tag}] tpu_custom_call: {n_calls} in {n_prog} compiled round "
          f"program(s); n_fallback_rounds 0", flush=True)
    one = _execute(spec_fn(seed, shards=1), env=four.run.env)
    _check_finite(tag + "/1 shard", one)
    print(f"[{tag}] one-shard loss per round: {_losses(one)}", flush=True)
    print(f"[{tag}] seconds: 4 shards compile+run {four.seconds:.1f}, 1 "
          f"shard {one.seconds:.1f}", flush=True)
    if bitwise:
        same = (np.array_equal(four.losses, one.losses)
                and all(np.array_equal(a, b)
                        for a, b in zip(four.params, one.params)))
        print(f"[{tag}] 4-vs-1 shard losses and final params bit-equal: "
              f"{same} (tolerance: bitwise)", flush=True)
        if not same:
            print(f"[{tag}] 4-vs-1 shard gap: loss %.3e, params %.3e (%.3e "
                  "of max |w|)" % (np.max(np.abs(four.losses - one.losses)),
                                   *_param_gap(four.params, one.params)),
                  flush=True)
            raise AssertionError(f"[{tag}] the robust path is not bitwise "
                                 "across shards")
    else:
        _trajectory_gap(tag, "4-vs-1 shard", four, one,
                        SHARD_TIGHT, SHARD_LOOSE)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the data, weights and batch draws")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-vs-one-shard comparison")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository source next to this script "
              f"({ROOT / 'src' / 'repro'} is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found — JAX's devices are {platform!r}; "
              "this check runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    print(f"device: {kind} x{len(devices)} (platform {platform})", flush=True)
    print(f"compile cache: {cache_dir}", flush=True)

    if args.chips == 4:
        phases = [
            ("main x4", lambda: phase_sharded("main x4", main_spec,
                                              args.seed, bitwise=False)),
            ("cohort64 x4", lambda: phase_sharded("cohort64 x4",
                                                  cohort64_spec, args.seed,
                                                  bitwise=True)),
        ]
    else:
        phases = [
            ("kernels", lambda: phase_kernels(args.seed)),
            ("main", lambda: phase_vs_reference(
                "main", main_spec(args.seed, shards=1))),
            ("cohort64", lambda: phase_vs_reference(
                "cohort64", cohort64_spec(args.seed, shards=1))),
        ]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:                    # report, then run the rest
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAILED after {time.perf_counter() - t0:.1f} s",
                  flush=True)
        else:
            print(f"[{name}] ok in {time.perf_counter() - t0:.1f} s",
                  flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
