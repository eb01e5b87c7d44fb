"""The two tests the output check is kept honest by, at a size a CPU holds
(`python -m pytest chipbench/tests -q`; not part of the repo's tier-1 suite):

- the control: the program with its own lower-precision path switched on
  (`compute_dtype: bfloat16`) comes out as not agreeing with the reference,
  while the float32 program agrees, under the limits of
  `limits/cifar_resnet18_dba.clean_rounds.json`;
- a harness run with the timed path broken underneath — a round program that
  returns the global state unchanged; one that leaves a part of every batch
  out of the loss — sees its check fail.

They skip the harness's look for a chip (`rehearse=True`: tiny sizes from
`rehearsal.json`, the narrow CIFAR ResNet-18, the plain jnp update) and drive
the rest of a run: build, seeded weights, the two check rounds through the
round program, a warm round, a window, the reference, the comparison.
"""
from __future__ import annotations

import argparse
from pathlib import Path

import pytest

from chipbench import run as harness

BENCH = Path(__file__).resolve().parent / "bench_small.json"
FAST = {"fused_updates": False, "fused_interpret": False}


def run(seed, sabotage=None, **overrides):
    args = argparse.Namespace(
        workload="cifar_dba_pretrain", seed=seed, seconds=0.5, trace=0,
        rehearse=True, benchmark_file=str(BENCH), override=None,
        overrides={**FAST, **overrides})
    return harness.run_cell(args, sabotage=sabotage)


def test_float32_agrees_and_is_never_correct_in_a_rehearsal():
    result = run(7)
    assert result["check_ok"], result
    assert result["correct"] is False and result["metrics"] == {}
    assert result["conditions"]["no_compile_in_window"]
    assert result["conditions"]["rows_recorded"]


def test_control_bfloat16_does_not_agree():
    result = run(7, compute_dtype="bfloat16")
    assert not result["check_ok"], result


def _wrap(exp, make):
    real = exp.engine.round_fn
    wrapped = make(real)
    wrapped._cache_size = real._cache_size
    exp.engine.round_fn = wrapped


def unchanged_state(exp):
    def make(real):
        def round_fn(global_vars, fg_state, *rest):
            _, new_fg, payload = real(global_vars, fg_state, *rest)
            return global_vars, new_fg, payload
        return round_fn
    _wrap(exp, make)


def half_batch(exp):
    def make(real):
        def round_fn(gv, fg, tasks, idx, mask, *rest):
            mask = mask.at[..., mask.shape[-1] // 2:].set(False)
            return real(gv, fg, tasks, idx, mask, *rest)
        return round_fn
    _wrap(exp, make)


@pytest.mark.parametrize("sabotage", [unchanged_state, half_batch])
def test_broken_timed_path_is_not_correct(sabotage):
    result = run(7, sabotage=sabotage)
    assert not result["check_ok"], result
