"""Family `resnet18`: the DBA reference repo's two ResNet-18 variants
(`model.variant`: `cifar_narrow`, `imagenet_tv`) on uint8 images with the pixel
trigger. The plain reference is `chipbench/reference/resnet18.py`.

What this file names in the program (`chipbench/program.py` lists the rest):
`Experiment.image_data` with `{train,test}_{images,labels}`, and the flax
auto-names of the ResNet tree (`Conv_0`, `BatchNorm_0`, `BasicBlock_<i>`,
`Dense_0`; collections `params` and `batch_stats`).
"""
from __future__ import annotations

import functools

import jax

from chipbench import program
from chipbench.reference import images
from chipbench.reference import resnet18 as ref

is_stat = ref.is_stat
check_round = program.check_round
STATS_BATCH = 256  # images whose statistics the window's state carries


def init_weights(seed: int, model: dict):
    return ref.init_weights(seed, model["variant"], model["num_classes"])


def window_state(state, population, model: dict):
    return ref.with_batch_statistics(
        state, population["train_inputs"][:STATS_BATCH], model["variant"])


def population_of(exp):
    return images.population_of(exp.image_data)


def path_of(name: str):
    """torch-style reference name -> (collection, module path, leaf)."""
    parts = name.split(".")
    leaf = parts[-1]
    if name == "conv1":
        return "params", ("Conv_0",), "kernel"
    if name.startswith("fc."):
        return "params", ("Dense_0",), {"weight": "kernel", "bias": "bias"}[leaf]
    if parts[0] == "bn1":
        mod: tuple = ("BatchNorm_0",)
    else:
        block = f"BasicBlock_{2 * (int(parts[0][5:]) - 1) + int(parts[1])}"
        sub = parts[2:]
        if sub[0] == "shortcut":
            sub_mod = {"conv": "Conv_2", "bn": "BatchNorm_2"}[sub[1]]
        else:
            sub_mod = {"conv1": "Conv_0", "bn1": "BatchNorm_0",
                       "conv2": "Conv_1", "bn2": "BatchNorm_1"}[sub[0]]
        mod = (block, sub_mod)
        if sub_mod.startswith("Conv"):
            return "params", mod, "kernel"
    coll, leaf = {"weight": ("params", "scale"), "bias": ("params", "bias"),
                  "running_mean": ("batch_stats", "mean"),
                  "running_var": ("batch_stats", "var")}[leaf]
    return coll, mod, leaf


def to_program(shapes, state):
    return program.to_program(shapes, state, path_of)


def from_program(model_vars, names):
    return program.from_program(model_vars, names, path_of)


@functools.lru_cache(maxsize=None)
def _forward(variant: str):
    """One function object a variant: the reference's jitted clients are
    cached by it."""
    return lambda state, x, train: ref.forward(state, x, variant, train)


def reference_round(p, model, state0, population, feed, precision):
    return images.reference_round(p, state0, population, feed, precision,
                                  forward=_forward(model["variant"]),
                                  is_stat=is_stat)


def engine_conditions(exp) -> dict:
    return {}


def model_flops(model: dict, batch: int = 64) -> dict:
    state = jax.eval_shape(lambda: init_weights(0, model))
    return images.model_flops(_forward(model["variant"]), is_stat, state,
                              model["image"], batch)
