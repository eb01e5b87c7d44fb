"""Test configuration: force an 8-device virtual CPU platform.

Multi-device tests exercise the `clients` mesh axis without TPU hardware — the
TPU-world equivalent of a fake backend (SURVEY.md §4). Both variables must be
in the environment before jax is imported.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

# Persistent compile cache: the suite's cost is XLA compiles of model-sized
# programs; cache them across runs (safe to delete anytime).
from dba_mod_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()
assert jax.device_count() == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}")

import dataclasses

import pytest

import dba_mod_tpu.fl.experiment as _experiment
import dba_mod_tpu.models as _models
from dba_mod_tpu.models.resnet import ResNet

# Full width lives in tests/test_models.py (against the torch twins) and on
# the chip. A test whose subject is control flow — batch_stats through the
# client loop, the scaling epilogue, FedAvg, the mesh, a loader — trains the
# same ResNet class (blocks, stem, pool, auto-names) at these widths: the
# narrowest at which every bound the full-width tests asserted still holds
# (PR 29: at (8, 16, 32, 64) the two frameworks' Tiny-ImageNet backdoor
# accuracies part by 3.1 points against a bar of 1.0; at (16, 32, 64, 128)
# everything holds and a round takes two to four times as long).
NARROW_WIDTHS = (12, 24, 48, 96)
_build_full = _models.build_model


def build_narrow_model(params):
    """`build_model`'s ModelDef; a ResNet comes back at NARROW_WIDTHS."""
    mdef = _build_full(params)
    if isinstance(mdef.module, ResNet):
        mdef = dataclasses.replace(
            mdef, module=mdef.module.clone(widths=NARROW_WIDTHS))
    return mdef


@pytest.fixture
def narrow_resnets(monkeypatch):
    """Every `Experiment` built under this fixture gets the narrow ResNet
    for `cifar` / `tiny-imagenet-200`. Returns the widths (the torch twins
    of benchmarks/parity_ab.py take them as an argument)."""
    monkeypatch.setattr(_models, "build_model", build_narrow_model)
    monkeypatch.setattr(_experiment, "build_model", build_narrow_model)
    return NARROW_WIDTHS
