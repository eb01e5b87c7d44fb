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
