#!/usr/bin/env bash
# Test/CI entrypoint: install declared deps (best effort — offline containers
# fall back to tests/_hypothesis_stub.py via tests/conftest.py), then run the
# tier-1 suite + the experiment-API CLI smoke + the sweep-CLI smoke + the
# feddyn chaos smoke (SIGTERM a checkpointing FedDyn run, resume, assert
# the per-client correction state came back bitwise) + the sweep-resume
# chaos smoke (SIGTERM a --workers 2 sweep mid-matrix, then
# --resume it) + the fleet smoke (1000-client streamed cohort store vs the
# replicated oracle, bitwise), then the sharded smoke leg (round/block-engine
# + API + sweep/service/axes/fleet tests and the same CLI smokes on a forced
# 4-device host mesh, exercising the shard_map client axis on CPU).
#
# Tiering (pytest.ini): the default run selects tier-1 only (-m "not slow");
# pass --all as the FIRST argument to include slow-marked tests. Remaining
# arguments are forwarded to pytest.
set -euo pipefail
cd "$(dirname "$0")/.."

# Every leg runs on the CPU, on a machine with a TPU too: the suite's
# bitwise packed-vs-reference contract holds for the XLA kernel mirrors
# that kernel_impl="auto" picks off-TPU (the Pallas kernels run in
# interpret mode there). The chip path is checked by `python chip_smoke.py`.
export JAX_PLATFORMS=cpu

MARKER=(-m "not slow")
if [[ "${1:-}" == "--all" ]]; then
    MARKER=()
    shift
fi

if ! python -c "import hypothesis" >/dev/null 2>&1; then
    python -m pip install -q -r requirements.txt 2>/dev/null \
        || echo "pip install unavailable (offline?); using vendored hypothesis shim"
fi

# CLI smoke: run a 4-round synthetic spec through `python -m repro.api.cli
# run`, then `resume` from the mid-run checkpoint it wrote (round 2 is the
# latest checkpoint, so resume really executes round 3). Runs in BOTH legs
# — single-device and forced-4-device — so the spec -> build -> run ->
# checkpoint -> resume path is exercised on the sharded client axis too.
# NOTE: callers invoke this as `cli_smoke || status=$?`, which disables
# set -e INSIDE the function body — so every step's failure is recorded
# explicitly in `ok` (otherwise the trailing rm -rf's exit 0 would mask a
# broken CLI and the smoke legs could never fail CI).
cli_smoke() {
    local work ok=0
    work="$(mktemp -d)"
    cat > "$work/spec.json" <<'EOF'
{
  "data": {"dataset": "synthetic-mnist", "n_clients": 6, "sigma": 5.0,
           "n_train": 240, "n_test": 60, "seed": 0},
  "model": {"name": "mlp-edge"},
  "wireless": {"e0": 1000000.0, "t0": 1000000.0, "seed": 0},
  "scheme": {"name": "proposed", "rounds": 4, "eta": 0.1, "batch": 8,
             "ao": {"outer_iters": 1}},
  "run": {"seed": 0, "eval_every": 2, "checkpoint_every": 2,
          "rounds_per_dispatch": 2}
}
EOF
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli run "$work/spec.json" \
        --checkpoint-dir "$work/ckpt" --out "$work/run.jsonl" || ok=1
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli resume "$work/ckpt" \
        --out "$work/resumed.jsonl" || ok=1
    test -s "$work/run.jsonl" || ok=1
    test -s "$work/resumed.jsonl" || ok=1
    rm -rf "$work"
    return "$ok"
}

# Sweep-CLI smoke: 2 seeds x 2 schemes over one spec template, streamed as
# per-run JSONL into --out-dir (4 run files + the sweep.jsonl index), then
# the report's seed-aggregated mean±std section over the directory glob.
# Same error discipline as cli_smoke.
sweep_smoke() {
    local work ok=0 n
    work="$(mktemp -d)"
    cat > "$work/spec.json" <<'EOF'
{
  "data": {"dataset": "synthetic-mnist", "n_clients": 6, "sigma": 5.0,
           "n_train": 240, "n_test": 60, "seed": 0},
  "model": {"name": "mlp-edge"},
  "wireless": {"e0": 1000000.0, "t0": 1000000.0, "seed": 0},
  "scheme": {"name": "proposed", "rounds": 3, "eta": 0.1, "batch": 8,
             "ao": {"outer_iters": 1}},
  "run": {"seed": 0, "eval_every": 2}
}
EOF
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli sweep "$work/spec.json" \
        --seeds 0,1 --schemes proposed,no_gen \
        --out-dir "$work/runs" || ok=1
    n="$(ls "$work"/runs/0*.jsonl 2>/dev/null | wc -l)"
    [[ "$n" -eq 4 ]] || { echo "sweep smoke: expected 4 run files, got $n"; ok=1; }
    test -s "$work/runs/sweep.jsonl" || ok=1
    # plain grep (not -q) drains the whole pipe, so the report never dies
    # on a broken pipe mid-print
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m benchmarks.report --runs "$work/runs/*.jsonl" \
        | grep "seed-aggregated" >/dev/null || ok=1
    rm -rf "$work"
    return "$ok"
}

# Chaos smoke: the cli_smoke spec under a byzantine upload attack
# (ScaledMalicious, exactly 2 of 6 attackers per round) defended by the
# trimmed-mean robust aggregator, run -> resume from the mid-run
# checkpoint -> assert both the fault counters and the aggregation
# counters surfaced in the exported JSONL. `fixed_selection` keeps every
# client in every round so the trim statistic is nonzero. Same error
# discipline as cli_smoke.
chaos_smoke() {
    local work ok=0
    work="$(mktemp -d)"
    cat > "$work/spec.json" <<'EOF'
{
  "data": {"dataset": "synthetic-mnist", "n_clients": 6, "sigma": 5.0,
           "n_train": 240, "n_test": 60, "seed": 0},
  "model": {"name": "mlp-edge"},
  "wireless": {"e0": 1000000.0, "t0": 1000000.0, "seed": 0,
               "fault_model": "scaled_malicious",
               "fault_kwargs": {"rate": 0.34, "scale": -10.0,
                                "exact": true, "seed": 7}},
  "scheme": {"name": "fixed_selection", "rounds": 4, "eta": 0.1, "batch": 8,
             "ao": {"outer_iters": 1},
             "aggregator": "trimmed_mean",
             "aggregator_kwargs": {"beta": 0.34}},
  "run": {"seed": 0, "eval_every": 2, "checkpoint_every": 2,
          "rounds_per_dispatch": 2}
}
EOF
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli run "$work/spec.json" \
        --checkpoint-dir "$work/ckpt" --out "$work/run.jsonl" || ok=1
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli resume "$work/ckpt" \
        --out "$work/resumed.jsonl" || ok=1
    grep '"n_corrupt_finite"' "$work/run.jsonl" >/dev/null \
        || { echo "chaos smoke: no fault counters in run.jsonl"; ok=1; }
    grep '"aggregation"' "$work/run.jsonl" >/dev/null \
        || { echo "chaos smoke: no aggregation block in run.jsonl"; ok=1; }
    grep '"n_trimmed"' "$work/resumed.jsonl" >/dev/null \
        || { echo "chaos smoke: no aggregation counters in resumed.jsonl"; ok=1; }
    rm -rf "$work"
    return "$ok"
}

# FedDyn chaos smoke: a checkpointing FedDyn run (stateful per-client
# correction buffer h rides every checkpoint) is SIGTERMed as soon as a
# checkpoint lands, then resumed. Asserts (a) the killed run's latest
# checkpoint npz really carries the h leaf, and (b) the resumed export's
# round records are BYTE IDENTICAL to an uninterrupted oracle's — the
# post-resume rounds replay through the restored h, so byte equality here
# IS the h-restored-bitwise assertion. Same error discipline as
# cli_smoke.
feddyn_chaos_smoke() {
    local work ok=0 pid i
    work="$(mktemp -d)"
    cat > "$work/spec.json" <<'EOF'
{
  "data": {"dataset": "synthetic-mnist", "n_clients": 6, "sigma": 5.0,
           "n_train": 240, "n_test": 60, "seed": 0},
  "model": {"name": "mlp-edge"},
  "wireless": {"e0": 1000000.0, "t0": 1000000.0, "seed": 0},
  "scheme": {"name": "proposed", "rounds": 6, "eta": 0.1, "batch": 8,
             "ao": {"outer_iters": 1},
             "local_scheme": "feddyn", "local_steps": 2,
             "local_kwargs": {"alpha": 0.1}},
  "run": {"seed": 0, "eval_every": 3, "checkpoint_every": 1}
}
EOF
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli run "$work/spec.json" \
        --out "$work/oracle.jsonl" || ok=1
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli run "$work/spec.json" \
        --checkpoint-dir "$work/ckpt" --out "$work/run.jsonl" \
        >/dev/null 2>&1 &
    pid=$!
    for i in $(seq 1 600); do
        ls "$work"/ckpt/*.npz >/dev/null 2>&1 && break
        kill -0 "$pid" 2>/dev/null || break
        sleep 0.1
    done
    kill -TERM "$pid" 2>/dev/null || true
    wait "$pid" 2>/dev/null || true
    python - "$work/ckpt" <<'EOF' \
        || { echo "feddyn chaos smoke: no per-client h leaf in checkpoint"; ok=1; }
import glob
import sys

import numpy as np

paths = sorted(glob.glob(sys.argv[1] + "/*.npz"))
if not paths:
    sys.exit(1)
with np.load(paths[-1]) as d:
    sys.exit(0 if "['h']" in d.files else 1)
EOF
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli resume "$work/ckpt" \
        --out "$work/resumed.jsonl" || ok=1
    grep '"kind": "round"' "$work/oracle.jsonl" > "$work/o.rounds" || ok=1
    grep '"kind": "round"' "$work/resumed.jsonl" > "$work/r.rounds" || ok=1
    cmp -s "$work/o.rounds" "$work/r.rounds" \
        || { echo "feddyn chaos smoke: resumed trajectory diverged from the uninterrupted oracle (h not restored bitwise?)"; ok=1; }
    rm -rf "$work"
    return "$ok"
}

# Sweep-resume chaos smoke: a 2x2 matrix run with --workers 2 is
# SIGTERMed as soon as the service has durable state (a mid-cell
# checkpoint dir or a completed per-run file), then relaunched with
# --resume. The resume must report its skip/ran split, and the final
# sink directory must hold all 4 per-run files with every cell named in
# the sweep.jsonl index (as sweep_run or sweep_skip). Same error
# discipline as cli_smoke. checkpoint_every=1 makes mid-cell state
# appear within one round, so the kill lands mid-matrix rather than
# racing the whole sweep.
sweep_resume_smoke() {
    local work ok=0 pid i n f name
    work="$(mktemp -d)"
    cat > "$work/spec.json" <<'EOF'
{
  "data": {"dataset": "synthetic-mnist", "n_clients": 6, "sigma": 5.0,
           "n_train": 240, "n_test": 60, "seed": 0},
  "model": {"name": "mlp-edge"},
  "wireless": {"e0": 1000000.0, "t0": 1000000.0, "seed": 0},
  "scheme": {"name": "proposed", "rounds": 4, "eta": 0.1, "batch": 8,
             "ao": {"outer_iters": 1}},
  "run": {"seed": 0, "eval_every": 2, "checkpoint_every": 1}
}
EOF
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli sweep "$work/spec.json" \
        --seeds 0,1 --schemes proposed,no_gen \
        --out-dir "$work/runs" --workers 2 >/dev/null 2>&1 &
    pid=$!
    for i in $(seq 1 600); do
        if [[ -d "$work/runs/ckpt" ]] \
            || ls "$work"/runs/0*.jsonl >/dev/null 2>&1; then
            break
        fi
        kill -0 "$pid" 2>/dev/null || break
        sleep 0.1
    done
    kill -TERM "$pid" 2>/dev/null || true
    wait "$pid" 2>/dev/null || true
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli sweep "$work/spec.json" \
        --seeds 0,1 --schemes proposed,no_gen \
        --out-dir "$work/runs" --workers 2 --resume \
        > "$work/resume.out" || ok=1
    grep "resume: skipped" "$work/resume.out" >/dev/null \
        || { echo "sweep-resume smoke: no resume skip/ran summary"; ok=1; }
    n="$(ls "$work"/runs/0*.jsonl 2>/dev/null | wc -l)"
    [[ "$n" -eq 4 ]] \
        || { echo "sweep-resume smoke: expected 4 run files, got $n"; ok=1; }
    for f in "$work"/runs/0*.jsonl; do
        name="$(basename "$f" .jsonl)"
        grep -F "\"name\": \"$name\"" "$work/runs/sweep.jsonl" >/dev/null \
            || { echo "sweep-resume smoke: $name missing from index"; ok=1; }
    done
    rm -rf "$work"
    return "$ok"
}

# Fleet smoke: a 1000-client synthetic-fleet population through the
# streamed cohort store (`random_k` scheme — the paper solvers are O(N)
# per client and fleet-infeasible), run twice: streamed and with the
# replicated-store oracle. The per-round records of the two exports must
# be BYTE IDENTICAL (streaming moves data, never results), the streamed
# summary must carry the fleet counters, and a mid-sweep SIGTERM +
# --resume with streaming on must finish the matrix (the cohort schedule
# is selection-pure, so the resumed leg replays it bit-for-bit). Same
# error discipline as cli_smoke.
fleet_smoke() {
    local work ok=0 pid i n
    work="$(mktemp -d)"
    cat > "$work/streamed.json" <<'EOF'
{
  "data": {"dataset": "synthetic-fleet", "n_clients": 1000,
           "n_train": 8000, "n_test": 64, "seed": 5},
  "model": {"name": "mlp-edge", "kwargs": {"hidden": 16}},
  "wireless": {"e0": 1000000.0, "t0": 1000000.0, "seed": 0},
  "scheme": {"name": "random_k", "rounds": 6, "eta": 0.1, "batch": 8,
             "ao": {"k": 6, "seed": 1}},
  "run": {"seed": 2, "eval_every": 3, "stop_on_budget": false,
          "rounds_per_dispatch": 3, "client_store": "streamed",
          "checkpoint_every": 2}
}
EOF
    sed 's/"streamed"/"replicated"/' "$work/streamed.json" \
        > "$work/replicated.json"
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli run "$work/streamed.json" \
        --out "$work/streamed.jsonl" || ok=1
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli run "$work/replicated.json" \
        --out "$work/replicated.jsonl" || ok=1
    grep '"fleet"' "$work/streamed.jsonl" >/dev/null \
        || { echo "fleet smoke: no fleet counters in streamed export"; ok=1; }
    grep '"fleet"' "$work/replicated.jsonl" >/dev/null \
        && { echo "fleet smoke: fleet counters leaked into replicated export"; ok=1; }
    grep '"kind": "round"' "$work/streamed.jsonl" > "$work/s.rounds" || ok=1
    grep '"kind": "round"' "$work/replicated.jsonl" > "$work/r.rounds" || ok=1
    cmp -s "$work/s.rounds" "$work/r.rounds" \
        || { echo "fleet smoke: streamed round records diverged from the replicated oracle"; ok=1; }
    # mid-sweep SIGTERM + --resume with streaming on (2 seeds x 1 scheme)
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli sweep "$work/streamed.json" \
        --seeds 0,1 --out-dir "$work/runs" >/dev/null 2>&1 &
    pid=$!
    for i in $(seq 1 600); do
        if [[ -d "$work/runs/ckpt" ]] \
            || ls "$work"/runs/0*.jsonl >/dev/null 2>&1; then
            break
        fi
        kill -0 "$pid" 2>/dev/null || break
        sleep 0.1
    done
    kill -TERM "$pid" 2>/dev/null || true
    wait "$pid" 2>/dev/null || true
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
        python -m repro.api.cli sweep "$work/streamed.json" \
        --seeds 0,1 --out-dir "$work/runs" --resume \
        > "$work/resume.out" || ok=1
    grep "resume: skipped" "$work/resume.out" >/dev/null \
        || { echo "fleet smoke: no resume skip/ran summary"; ok=1; }
    n="$(ls "$work"/runs/0*.jsonl 2>/dev/null | wc -l)"
    [[ "$n" -eq 2 ]] \
        || { echo "fleet smoke: expected 2 run files, got $n"; ok=1; }
    rm -rf "$work"
    return "$ok"
}

# run all legs even if an earlier one fails (the seed ships with
# known-failing arch/serving suites); exit non-zero if any leg failed
status=0
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m pytest -x -q ${MARKER[@]+"${MARKER[@]}"} "$@" \
    || status=$?

echo "== CLI smoke leg: spec run + checkpoint resume (1 device) =="
cli_smoke || status=$?

echo "== sweep-CLI smoke leg: 2 seeds x 2 schemes, streamed JSONL (1 device) =="
sweep_smoke || status=$?

echo "== chaos smoke leg: byzantine attack + robust aggregator (1 device) =="
chaos_smoke || status=$?

echo "== feddyn chaos leg: SIGTERM mid-run + resume with per-client state (1 device) =="
feddyn_chaos_smoke || status=$?

echo "== sweep-resume chaos leg: SIGTERM mid-matrix + --resume (1 device) =="
sweep_resume_smoke || status=$?

echo "== fleet smoke leg: streamed cohorts vs replicated oracle (1 device) =="
fleet_smoke || status=$?

echo "== sharded smoke leg: round/block engines + API under 4 forced host devices =="
# forced flag goes LAST: XLA takes the final occurrence of a duplicated
# flag, so an inherited force-count must not override the leg's; an
# inherited shard-count override would likewise silently unshard the leg.
# The per-round, multi-round-block, experiment-API, sweep, and scenario-axes
# parity suites all run here (the 1-device leg above already ran them
# unsharded), so every engine path is exercised on the mesh.
XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=4" \
    REPRO_ROUND_SHARDS= \
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m pytest -x -q ${MARKER[@]+"${MARKER[@]}"} \
        tests/test_round_engine.py tests/test_block_engine.py \
        tests/test_api.py tests/test_sweep.py tests/test_sweep_service.py \
        tests/test_scenario_axes.py \
        tests/test_faults.py tests/test_aggregators.py \
        tests/test_fleet.py tests/test_local_schemes.py \
    || status=$?

echo "== CLI smoke leg: spec run + checkpoint resume (4 forced devices) =="
(
    export XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=4"
    export REPRO_ROUND_SHARDS=
    cli_smoke
) || status=$?

echo "== sweep-CLI smoke leg: streamed sweep (4 forced devices) =="
(
    export XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=4"
    export REPRO_ROUND_SHARDS=
    sweep_smoke
) || status=$?

echo "== chaos smoke leg: byzantine attack + robust aggregator (4 forced devices) =="
(
    export XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=4"
    export REPRO_ROUND_SHARDS=
    chaos_smoke
) || status=$?

echo "== feddyn chaos leg: SIGTERM mid-run + resume with per-client state (4 forced devices) =="
(
    export XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=4"
    export REPRO_ROUND_SHARDS=
    feddyn_chaos_smoke
) || status=$?

echo "== sweep-resume chaos leg: SIGTERM mid-matrix + --resume (4 forced devices) =="
(
    export XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=4"
    export REPRO_ROUND_SHARDS=
    sweep_resume_smoke
) || status=$?

echo "== fleet smoke leg: streamed cohorts vs replicated oracle (4 forced devices) =="
(
    export XLA_FLAGS="${XLA_FLAGS:+$XLA_FLAGS }--xla_force_host_platform_device_count=4"
    export REPRO_ROUND_SHARDS=
    fleet_smoke
) || status=$?

exit $status
