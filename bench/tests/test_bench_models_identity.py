"""Each model's file gives what the benchmark made before the models moved
into files of their own, and the check that keeps states in their own
dtype, and of the reference only norms, reads what it read with float64
copies of every state.

The digests were taken from the tree before the move, at the size the
CPU tests run (`test_size`): data for two seeds, the weights from
`jax.random.key(3)`, and the FLOP counts at the test size and in full."""
import hashlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, models, reference

ROOT = Path(__file__).resolve().parents[2]
BENCH = harness.load_benchmark(ROOT)

BEFORE = {
    "lenet5-mnist": {
        "data": {5: "0068d2efced11c1c0f0530b9b82b87fa"
                    "b736287a7b74588d599eb18e7a4a1305",
                 2 ** 33 + 1: "a0f7c9629c99edca68c918a8015a6a5a"
                              "5687742428e4092f82cfa5a5a79e7d5d"},
        "init": "473db94347de7c7e9e778ceef44d287c"
                "c0471526abe5f3b032140f13e2196897",
        "flops": (1_386_000, 1_386_000),
    },
    "resnet44-cifar10": {
        "data": {5: "eecacf71381dcedca538870c69ceb640"
                    "d9af14c6df04f48583675229be005edf",
                 2 ** 33 + 1: "c151eb7fbf8789af148944ac5cd6c9f5"
                              "2f0da95144d3bf8de84f4dfcb36173ab"},
        "init": "d9358cdcfe9b80e24c58fcb8ec1025c4"
                "65f02dc3a20bf6d7c3478c912bc5f733",
        "flops": (25_003_264, 194_872_576),
    },
}
CELLS = {w["config"]: w["name"] for w in BENCH["workloads"]}


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def cell_files(config: str, test_size: bool = True) -> dict:
    files = harness.cell_files(BENCH, harness.find_cell(BENCH, CELLS[config]),
                               ROOT)
    return models.of(files["config"]).test_size(files) if test_size else files


@pytest.mark.parametrize("config", sorted(BEFORE))
@pytest.mark.parametrize("seed", [5, 2 ** 33 + 1])
def test_make_data_is_what_it_was(config, seed):
    cfg = cell_files(config)["config"]
    assert digest(models.of(cfg).make_data(cfg, seed)) == \
        BEFORE[config]["data"][seed]


@pytest.mark.parametrize("config", sorted(BEFORE))
def test_init_params_and_flops_are_what_they_were(config):
    cfg = cell_files(config)["config"]
    w = jax.jit(lambda k: reference.init_params(cfg, k))(jax.random.key(3))
    assert digest(jax.tree.leaves(w)) == BEFORE[config]["init"]
    full = cell_files(config, test_size=False)["config"]
    model = models.of(cfg)
    assert (model.forward_flops(cfg), model.forward_flops(full)) == \
        BEFORE[config]["flops"]


# -- the check as it was: float64 host copies of every state ----------------

def _f64(tree) -> list:
    return [np.asarray(a, np.float64) for a in jax.tree.leaves(tree)]


def _follow_f64(ref, w0, rounds, run):
    treedef = jax.tree.structure(w0)
    w, v = w0, None
    losses, snaps = [], []
    for start, n, w_end, v_end in run["blocks"]:
        r = ref.run(w, rounds[start:start + n], v0=v)
        losses += r["losses"]
        snaps.append((start, n) + tuple(_f64(t) for t in r["blocks"][0][2:]))
        w = jax.tree.unflatten(treedef, [np.asarray(a, np.float32)
                                         for a in w_end])
        v = jax.tree.unflatten(treedef, [np.asarray(a, np.float32)
                                         for a in v_end])
    return {"losses": losses, "blocks": snaps}


def _leaf_norm_gaps_f64(got, want, keep):
    ng = [float(np.linalg.norm(a)) for a in got]
    nw = [float(np.linalg.norm(b)) for b in want]
    med = float(np.median(nw))
    return [abs(a - b) / max(b, med) if max(b, med) > 0 else 0.0
            for a, b, k in zip(ng, nw, keep) if k]


def _compare_f64(run, ref, w0):
    lp, lr = np.asarray(run["losses"]), np.asarray(ref["losses"])
    rel = np.abs(lp - lr) / np.abs(lr)
    gn = [float(np.linalg.norm(g)) for g in ref["blocks"][0][3]]
    floor = 1e-3 * float(np.median(gn))
    keep = [g >= floor for g in gn]
    start_w = w0
    by_block = []
    for (s, n, w_run, v_run), (_, _, w_ref, v_ref) in zip(run["blocks"],
                                                          ref["blocks"]):
        g = max(_leaf_norm_gaps_f64(v_run, v_ref, [True] * len(w0)))
        c = max(_leaf_norm_gaps_f64([a - b for a, b in zip(w_run, start_w)],
                                    [a - b for a, b in zip(w_ref, start_w)],
                                    keep))
        by_block.append((s, n, float(rel[s]), g, c))
        start_w = w_run
    return {"loss_gap": max(b[2] for b in by_block),
            "grad_gap": by_block[0][3],
            "change_gap": max(b[4] for b in by_block),
            "leaves_left_out": int(len(keep) - sum(keep)),
            "by_block": by_block}


def _readings_f64(check, clients, data, cfg, traffic):
    rounds, _ = harness.reference_rounds(check, clients, data,
                                         harness.row_index(data[0]))
    eta = traffic["eta"]
    ref = reference.Reference(cfg, eta)
    w0 = _f64(check.w0)
    program = {"losses": check.losses,
               "blocks": [(s, n, [np.asarray(a, np.float64) for a in w],
                           [np.asarray(a, np.float64) for a in v])
                          for s, n, w, v in check.blocks]}
    out = {"program": program}
    lengths = [b[1] for b in check.blocks]
    for name, kw in (("control", {"dtype": jnp.bfloat16}),
                     ("half_batch", {"half_batch": True})):
        r = reference.Reference(cfg, eta, **kw).run(check.w0, rounds,
                                                    blocks=lengths)
        out[name] = {"losses": r["losses"],
                     "blocks": [(s, n, _f64(w), _f64(v))
                                for s, n, w, v in r["blocks"]]}
    return {k: _compare_f64(r, _follow_f64(ref, check.w0, rounds, r), w0)
            for k, r in out.items()}


@pytest.mark.parametrize("config", sorted(BEFORE))
def test_readings_are_bitwise_those_of_float64_copies(config):
    files = cell_files(config)
    cfg, tr = files["config"], files["traffic"]
    seed = 2 ** 32 + 77
    _, _, run, data = harness.set_up(cfg, tr, seed)
    check = harness.run_check(run, cfg, tr, seed)
    assert all(a.dtype == np.float32
               for _, _, w, v in check.blocks for a in w + v)
    got = harness.readings(check, run.trainer.clients, data, cfg, tr,
                           ("control", "half_batch"))
    want = _readings_f64(check, run.trainer.clients, data, cfg, tr)
    for name in ("program", "control", "half_batch"):
        assert got[name].pop("foreign_rows") == 0
        assert got[name] == want[name], name
