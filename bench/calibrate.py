#!/usr/bin/env python3
"""Readings the limits of `correct` are set from, for one cell, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3

For each seed, in one process: the cell's set-up and the check's first
rounds through the system, then the compared numbers of the program
against the float32 reference and of each stand-in put in the program's
place (the bfloat16 control; the reference with half of each batch left
out; where the traffic asks for a trimmed mean, the reference with the
mean in its place), each block followed from the state the program or
the stand-in held at its start. No window is run: a training cell's
readings need none. One JSON line per seed, then the largest program
reading and the smallest stand-in reading of each number.

A "state left unchanged" fault reads 1 on `change_gap` by its definition
(no change against the reference's) and needs no run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    cache = ROOT / ".jax_cache" / "bench"
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import harness
    from bench.run import check_devices
    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, args.workload)
    files = harness.cell_files(bench, cell, ROOT)
    why = check_devices(int(cell["chips"]))
    if why:
        print(f"bench/calibrate.py: {why}", file=sys.stderr)
        return 2
    cfg, traffic = files["config"], files["traffic"]
    stand_ins = harness.stand_ins_of(traffic)
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        _, env, run, images = harness.set_up(cfg, traffic, seed)
        check = harness.run_check(run, cfg, traffic, seed)
        r = harness.readings(check, run.trainer.clients, images, cfg,
                             traffic, stand_ins)
        r["seed"] = seed
        r["losses"] = check.losses
        r["programs"] = sorted(check.programs)
        r["seconds"] = time.perf_counter() - t0
        rows.append(r)
        print(json.dumps(r), flush=True)
    keys = ("loss_gap", "grad_gap", "change_gap")
    summary = {"program_max": {k: max(r["program"][k] for r in rows)
                               for k in keys}}
    for s in stand_ins:
        summary[s + "_min"] = {k: min(r[s][k] for r in rows) for k in keys}
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      **summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
