"""Family `lenet`: the DBA reference repo's MNIST LeNet on uint8 28x28x1
images with the pixel trigger; no running statistics. The plain reference is
`chipbench/reference/lenet.py`.

What this file names in the program (`chipbench/program.py` lists the rest):
`Experiment.image_data` with `{train,test}_{images,labels}`, and the flax
auto-names of the LeNet tree (`Conv_0`, `Conv_1`, `Dense_0`, `Dense_1`, each
with `kernel` and `bias`; an empty `batch_stats`).
"""
from __future__ import annotations

import jax

from chipbench import program
from chipbench.reference import images
from chipbench.reference import lenet as ref

check_round = program.check_round
MODULES = {"conv1": "Conv_0", "conv2": "Conv_1", "fc1": "Dense_0",
           "fc2": "Dense_1"}


def is_stat(name: str) -> bool:
    return False


def init_weights(seed: int, model: dict):
    return ref.init_weights(seed, model["num_classes"])


def window_state(state, population, model: dict):
    return state


def population_of(exp):
    return images.population_of(exp.image_data)


def path_of(name: str):
    """torch-style reference name -> (collection, module path, leaf)."""
    module, leaf = name.split(".")
    return "params", (MODULES[module],), {"weight": "kernel", "bias": "bias"}[leaf]


def to_program(shapes, state):
    return program.to_program(shapes, state, path_of)


def from_program(model_vars, names):
    return program.from_program(model_vars, names, path_of)


def reference_round(p, model, state0, population, feed, precision):
    return images.reference_round(p, state0, population, feed, precision,
                                  forward=ref.forward, is_stat=is_stat)


def engine_conditions(exp) -> dict:
    return {}


def model_flops(model: dict, batch: int = 64) -> dict:
    state = jax.eval_shape(lambda: init_weights(0, model))
    return images.model_flops(ref.forward, is_stat, state, model["image"], batch)
