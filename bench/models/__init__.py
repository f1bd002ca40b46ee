"""One file per model: `bench/models/<model>.py`, where `<model>` is a
configuration's `model.name`. The harness, the reference and the FLOP
count find a model's file by that name and name no model themselves, so a
configuration of a new model is added with a file here beside its
configuration, traffic, limits and readers.

A model file defines:

make_data(cfg, seed)       (x_train, y_train, x_test, y_test) as host numpy
                           arrays made from the seed; the same seed gives
                           the same arrays;
dataset(cfg, data)         the object the system is served under
                           "bench-" + cfg["name"] (its dataset registry);
init_params(cfg, key)      the benchmark's weights in the system's
                           parameter layout (jittable: one device call);
forward(params, x, precision)
                           logits [..., classes] whose leading shape is the
                           labels' shape, in plain jax.numpy at `precision`;
forward_flops(cfg)         forward FLOPs per row of x_train;
test_size(files)           a copy of a cell's files cut to the size the CPU
                           tests run, widths kept.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


_LOADED: dict = {}


def load(name: str):
    """The module of model `name`, from `bench/models/<name>.py`, run once
    per process."""
    path = HERE / f"{name}.py"
    if path not in _LOADED:
        if not path.is_file():
            raise KeyError(f"no benchmark file for model {name!r}: "
                           f"bench/models/{name}.py is missing")
        spec = importlib.util.spec_from_file_location(
            "bench_model_" + name.replace("-", "_").replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def of(cfg: dict):
    """The module of the configuration's model."""
    return load(cfg["model"]["name"])
