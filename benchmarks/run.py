"""Benchmark entry point — one harness per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--full | --smoke]

Default is the fast profile (reduced sigmas/budgets/rounds) so the whole
suite completes on one CPU core; --full reproduces the paper-scale sweeps.
--smoke is the CI profile: the round-engine harness, the sweep-service
scaling probe, and the fleet-streaming probe, tiny configs, with reports
diffed against the committed BENCH_round_engine.json /
BENCH_sweep_scaling.json / BENCH_fleet_scaling.json (the cross-PR compare
mode) so perf regressions surface without running the whole suite.
Output: ``name,us_per_call,derived`` CSV per harness.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: round_engine only, compared against the "
                         "committed BENCH_round_engine.json")
    ap.add_argument("--only", default=None,
                    help="comma list: fig3,...,fig8,theory,selection,"
                         "roofline,round_engine,sweep_scaling,fleet_scaling")
    args = ap.parse_args()
    fast = not args.full
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    if args.smoke:
        from benchmarks import round_engine
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        report = round_engine.main(
            fast=True, smoke=True,
            compare=os.path.join(root, "BENCH_round_engine.json"))
        rows = report.get("compare", {}).get("rows", [])
        if not rows:
            # a gate that silently checked nothing must not stay green
            print("FAILED: smoke compare produced no rows (baseline "
                  "missing or no overlapping configs)")
            sys.exit(1)
        # block-mode gates: the sweep must have run, must have been
        # compared against the committed baseline, and the block path must
        # not have uploaded any per-round batch data
        block = report.get("block_sweep")
        if not block:
            print("FAILED: smoke did not run the rounds_per_dispatch sweep")
            sys.exit(1)
        if not any(r["config"].startswith("block/") for r in rows):
            print("FAILED: no block-mode rows in the compare (committed "
                  "baseline predates the block sweep? re-run the fast "
                  "profile to refresh BENCH_round_engine.json)")
            sys.exit(1)
        leaky = [rpd for rpd, p in block["per_rpd"].items()
                 if rpd != "1" and p["batch_h2d_uploads_per_round"] != 0]
        if leaky:
            print("FAILED: block path uploaded per-round batch data at "
                  "rounds_per_dispatch", leaky)
            sys.exit(1)
        # Block speedups are throttle-sensitive in a way the interleaved
        # packed-vs-reference ratio is not: one K-round dispatch is a long
        # uninterrupted compute burst, so cgroup CFS throttling hits it
        # harder than K short dispatches whose host gaps refill the quota
        # (measured on this box: 1.65x quiet -> 0.93x under load at rpd=8,
        # see ROADMAP). The 10% delta rule therefore only WARNS for block
        # rows; the hard gate is an absolute floor that load noise never
        # reaches but structural regressions (a reintroduced per-round
        # sync/upload, a per-block retrace storm) do.
        block_floor = 0.75
        warned = [r["config"] for r in rows
                  if r["config"].startswith("block/") and r["regressed"]]
        if warned:
            print("WARNING: block speedup below committed baseline "
                  "(throttle-sensitive, not gated):", warned)
        # the floor is an absolute ratio from THIS run, so it needs no
        # baseline overlap — every swept rpd leg is covered even when the
        # committed report predates a change to the rpd ladder
        floored = [f"rpd{r}" for r, p in block["per_rpd"].items()
                   if r != "1" and p["speedup_vs_1"] < block_floor]
        if floored:
            print(f"FAILED: block speedup below the {block_floor} floor "
                  "(structural regression):", floored)
            sys.exit(1)
        regressed = [r["config"] for r in rows
                     if r["regressed"] and not r["config"].startswith("block/")]
        if regressed:
            print("FAILED: speedup regression vs committed report:",
                  regressed)
            sys.exit(1)
        # sweep-service gates: parity is checked inside main() (it raises
        # on a bitwise violation); the speedup ratio only fails on a
        # structural collapse vs the committed baseline
        from benchmarks import sweep_scaling
        sc = sweep_scaling.main(
            fast=True,
            compare=os.path.join(root, "BENCH_sweep_scaling.json"))
        if sc.get("compare", {}).get("regressed_floor"):
            print("FAILED: sweep-service worker-pool speedup collapsed vs "
                  "committed BENCH_sweep_scaling.json")
            sys.exit(1)
        # fleet-streaming gates: streamed-vs-replicated parity and the
        # flat-peak invariant are checked inside main() (it raises on
        # either violation); the compare adds the committed-baseline peak
        # gate — peak device bytes growing past the flat factor is a HARD
        # failure (cohort residency regressing toward population
        # residency), wall-clock deltas warn inside _compare only
        from benchmarks import fleet_scaling
        fs = fleet_scaling.main(
            fast=True,
            compare=os.path.join(root, "BENCH_fleet_scaling.json"))
        if fs.get("compare", {}).get("peak_regressed"):
            print("FAILED: fleet-streaming peak device bytes regressed vs "
                  "committed BENCH_fleet_scaling.json")
            sys.exit(1)
        return

    from benchmarks import (fig3_generalization_statement, fig4_accuracy_vs_sigma,
                            fig5_loss_vs_time, fig6_loss_vs_energy,
                            fig7_accuracy_vs_delay, fig8_accuracy_vs_energy,
                            fleet_scaling, roofline, round_engine,
                            selection_ablation, sweep_scaling,
                            theory_validation)
    suite = {
        "fig3": fig3_generalization_statement.main,
        "fig4": fig4_accuracy_vs_sigma.main,
        "fig5": fig5_loss_vs_time.main,
        "fig6": fig6_loss_vs_energy.main,
        "fig7": fig7_accuracy_vs_delay.main,
        "fig8": fig8_accuracy_vs_energy.main,
        "theory": theory_validation.main,
        "selection": selection_ablation.main,
        "roofline": roofline.main,
        "round_engine": round_engine.main,
        "sweep_scaling": sweep_scaling.main,
        "fleet_scaling": fleet_scaling.main,
    }
    only = set(args.only.split(",")) if args.only else set(suite)
    failures = []
    for name, fn in suite.items():
        if name not in only:
            continue
        print(f"== {name} ==", flush=True)
        t0 = time.time()
        try:
            fn(fast=fast)
        except Exception:
            failures.append(name)
            traceback.print_exc()
        print(f"== {name} done in {time.time() - t0:.1f}s ==", flush=True)
    if failures:
        print("FAILED:", failures)
        sys.exit(1)


if __name__ == "__main__":
    main()
