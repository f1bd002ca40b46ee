#!/usr/bin/env python3
"""Chip benchmark of the federated round engine: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the repository root. The cell is looked up in ``BENCHMARK.json``;
its configuration, traffic and limits are files under ``bench/`` (see
``bench/harness.py``). Inputs and weights are made from ``--seed``.

Set-up (reported as ``setup_s``) runs from process start to the first timed
unit: device start, the synthetic data, the environment and schedule
solve, the trainer and client store, the check's first rounds, and one
whole unit that compiles or loads every program the window uses. The
window then runs whole units until ``--seconds`` have passed.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from host spans, the program's counters and a
profiler trace of the window's first unit. Either way the first rounds are
compared with the plain reference in ``bench/reference.py`` after the
window, and ``correct`` says whether every compared number is within its
limit. The last line of standard output is one JSON object; the compared
numbers, each with its limit, are also the last lines of standard error.

The run exits non-zero, and prints no result, when JAX finds no
accelerator, fewer chips than the cell asks for, or a device that
``bench/peaks.json`` does not list.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 2


def check_devices(chips: int) -> str | None:
    """Why this process cannot run a cell on `chips` chips, or None."""
    import jax
    from bench.harness import peak_entry
    devices = jax.devices()
    if devices[0].platform == "cpu":
        return "JAX finds no accelerator (its devices are CPUs)"
    if len(devices) < chips:
        return f"the cell needs {chips} chips; JAX finds {len(devices)}"
    try:
        peak_entry(devices[0].device_kind)
    except KeyError as e:
        return str(e)
    return None


def main(argv: list[str] | None = None, *, device_check=check_devices) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the system under test is missing: no {ROOT}/src/repro")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    try:
        bench = harness.load_benchmark(ROOT)
        cell = harness.find_cell(bench, args.workload)
        files = harness.cell_files(bench, cell, ROOT)
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot load the cell: {e}")

    # JAX's persistent compilation cache lives inside the checkout at a
    # fixed path, every program cached, so only a checkout's first run
    # compiles
    cache = ROOT / ".jax_cache" / "bench"
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    why = device_check(int(cell["chips"]))
    if why:
        return fail(why)

    out = harness.run_cell(bench, cell, files, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           t_start=T_START)
    compared = out.pop("compared")
    out["compared"] = compared          # the key the compared numbers go under, last
    for name, v in compared.items():
        print(f"compared {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
