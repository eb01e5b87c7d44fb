"""Model families: everything the benchmark knows about one kind of model, in
one module a family, found by the name a configuration's file gives
(`"model": {"family": "<name>", ...}` -> `chipbench/families/<name>.py`). The
window, the end-to-end arithmetic, the trace reduction, the readers and the
comparison import no family.

A family module offers, each by this name (`model` is the configuration's
`model` object, `state` a dict of arrays under the plain reference's names):

- `init_weights(seed, model)` -> state: seeded float32 weights, made on the
  device in one jitted call.
- `window_state(state, population, model)` -> state: what the window starts
  from (running statistics as a trained model carries them; a family without
  any returns its input).
- `population_of(exp)` -> `{"train_inputs", "train_labels", "test_inputs",
  "test_labels"}`: the host arrays of the program's population that the
  reference follows the check feeds on.
- `to_program(shapes, state)` -> the program's tree on the device, refusing a
  tree whose structure or shapes are not `shapes` (`program.tree_shapes` of
  the program's own); `from_program(model_vars, names)` -> state on the host.
- `is_stat(name)` -> bool: a running statistic (compared apart, never stepped).
- `reference_round(p, model, state0, population, feed, precision)` ->
  `{"new": state, "loss_sum": [C], "delta_norms": [C], "global_loss": float}`:
  the plain reference's federated round on a check feed — the clients' steps
  with the family's own trigger, FedAvg, the evaluation; `p` the parameters as
  run, `precision` a `jax.default_matmul_precision` name or `default`.
- `check_round(exp, epoch, real_steps)` -> the feed and what the program made
  of it (`program.check_round`, unless the family's round program takes
  another feed).
- `engine_conditions(exp)` -> `{name: bool}`: what else has to hold of the
  engine that ran, for `correct` (beside `program.engine_report`'s own).
- `model_flops(model, batch)` -> `{"forward", "train_step"}`: operations a
  sample, counted on the plain reference (`chipbench/flops.py`).

A later PR brings a family with `families/<name>.py`, its plain reference
under `reference/`, a configuration file that names it, the cell's limits, and
entries in `BENCHMARK.json`: no edit to a file that is here.
"""
from __future__ import annotations

import importlib
from pathlib import Path

HERE = Path(__file__).resolve().parent
INTERFACE = ("init_weights", "window_state", "population_of", "to_program",
             "from_program", "is_stat", "reference_round", "check_round",
             "engine_conditions", "model_flops")


def load(name: str):
    """The family's module; an error that names the file where there is none,
    or the names it lacks where it is not whole."""
    path = HERE / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"chipbench: no model family {name!r}: {path} "
                         "is not there")
    module = importlib.import_module(f"chipbench.families.{name}")
    missing = [n for n in INTERFACE if not callable(getattr(module, n, None))]
    if missing:
        raise SystemExit(f"chipbench: {path} lacks {', '.join(missing)}")
    return module


def of(config: dict):
    """The family a configuration's file names."""
    model = config.get("model") or {}
    if "family" not in model:
        raise SystemExit(f"chipbench: configuration {config.get('name')!r} "
                         "names no model.family")
    return load(model["family"])
