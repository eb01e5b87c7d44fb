"""The second model family against its plain reference, at a size a CPU holds
and in under a minute (`python -m pytest chipbench/tests/test_lenet.py -q`):
the MNIST LeNet's two check rounds through the program's compiled round
program (the configuration's own `rehearsal.cut`), judged under the limits of
`limits/mnist_lenet_dba.attack_rounds.json`:

- the float32 program is inside every limit, at both step counts;
- the control, the program with its own lower-precision path switched on
  (`compute_dtype: bfloat16`), breaks at least one.
"""
from __future__ import annotations

import pytest

from chipbench import check, families, program
from chipbench import run as harness

BENCH = harness.HERE / "tests" / "bench_small.json"
SEED = 2147480032


@pytest.fixture(scope="module")
def compared(tmp_path_factory):
    """{compute dtype: the rows `judge` gives for the two check rounds}."""
    _, cell, config, traffic = harness.load_cell("mnist_dba_attack", BENCH)
    family = families.of(config)
    lim = check.limits(cell["config"], cell["traffic"])
    rows = {}
    for dtype in ("float32", "bfloat16"):
        params, raw = program.make_params(
            config, traffic, tmp_path_factory.mktemp(dtype),
            harness.FIRST_WINDOW_EPOCH, config["rehearsal"]["cut"],
            {"compute_dtype": dtype})
        exp, _ = program.build_experiment(params)
        state0, checks = harness.seeded_check_rounds(
            exp, family, config, traffic, SEED, harness.FIRST_WINDOW_EPOCH,
            harness.CompileEvents())
        rows[dtype] = harness.judge(family, raw, config["model"], state0,
                                    family.population_of(exp), checks, lim)
    return rows


@pytest.mark.parametrize("steps", harness.CHECK_STEPS)
def test_float32_lenet_is_inside_every_limit(compared, steps):
    rows = [r for r in compared["float32"]
            if r["number"].endswith(f".k{steps}")]
    assert rows and all(r["ok"] for r in rows), rows


def test_control_bfloat16_lenet_breaks_a_limit(compared):
    assert not all(r["ok"] for r in compared["bfloat16"]), compared["bfloat16"]
