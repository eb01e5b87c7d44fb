"""Everything the benchmark calls in the program under test that does not
depend on the model family — in this one file (a family's own file under
`chipbench/families/` lists what it names beside these: the attribute that
holds its population, the names of its model tree).

A PR that renames one of these has to keep the benchmark running:

- `dba_mod_tpu.config.Params.from_dict`
- `dba_mod_tpu.fl.experiment.Experiment(params, save_results=True)` and its
  attributes `select_rng`, `plan_rng`, `rng_key`, `global_vars`, `fg_state`
  (assigned after the build: from `--seed` for the check rounds, from the
  population's seed for the warm round and the window; `select_rng` again
  from the population's seed at the start of every period), `engine`,
  `folder`, `mesh`, `steps_per_epoch`, `epochs_max`, `_use_donated_round`,
  `last_global_loss`
- `Experiment.run_round`, `dispatch_round`, `finalize_round`, `save_model`
  (the program's own sequential loop) and `build_static_round_inputs` (the
  feed of the output check, at the window's own shape)
- `engine.round_fn_donated` / `engine.round_fn` (the compiled round program the
  window drives), `engine.fused_interpret`
- `RoundInFlight.payload`, and the key `agents` of a finished round's result
- `dba_mod_tpu.models.ModelVars` (collections `params` and `batch_stats`)
- `dba_mod_tpu.utils.compile_cache.enable_compile_cache`
- the payload order `(locals, globals, metrics, delta_norms, ...)` of the round
"""
from __future__ import annotations

import random
import time
from pathlib import Path
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def enable_cache() -> str:
    """The program's own persistent compile cache (its directory: the
    environment's, else `.jax_cache/` in the checkout), with the per-entry cap
    of the chip machine lifted in this process: the round executable carries
    the dataset and is larger than the 192 MiB the machine allows."""
    from dba_mod_tpu.utils.compile_cache import enable_compile_cache
    jax.config.update("jax_compilation_cache_max_size", -1)
    return enable_compile_cache()


def make_params(config: Dict[str, Any], traffic: Dict[str, Any], out_dir: Path,
                first_window_epoch: int, cut: Dict[str, Any] | None = None,
                overrides: Dict[str, Any] | None = None):
    """The configuration's parameters with the traffic's schedule laid over
    them: which rounds of a period are poisoned, and by whom, repeated over
    `periods_max` periods of `period_rounds` rounds from the window's first
    epoch on (adversary i poisons the i-th of `poison_window_rounds` in every
    period). Returns the program's Params and the plain dict they were made
    from (the reference reads the dict, never the program's object)."""
    from dba_mod_tpu.config import Params
    raw = dict(config["params"])
    raw["is_poison"] = bool(traffic["is_poison"])
    rounds = list(traffic.get("poison_window_rounds", []))
    period, periods = int(traffic["period_rounds"]), int(traffic["periods_max"])
    if any(not 1 <= r <= period for r in rounds):
        raise SystemExit("chipbench: a poisoned round outside the traffic's period")
    for i in range(int(raw["trigger_num"])):
        raw[f"{i}_poison_epochs"] = (
            [first_window_epoch - 1 + p * period + rounds[i]
             for p in range(periods)] if i < len(rounds) else [])
    raw["num_devices"] = int(traffic.get("num_devices", 0))
    raw["epochs"] = 10 ** 6
    raw["run_dir"] = str(out_dir / "runs")
    raw["run_name"] = "window"
    raw["checkpoint_dir"] = str(out_dir / "saved_models")
    raw.update(cut or {})
    raw.update(overrides or {})
    return Params.from_dict(raw), raw


def build_experiment(params):
    from dba_mod_tpu.fl.experiment import Experiment
    t0 = time.perf_counter()
    exp = Experiment(params, save_results=True)
    jax.block_until_ready((exp.global_vars, exp.fg_state))
    return exp, time.perf_counter() - t0


# ------------------------------------------------- reference names <-> tree
def to_program(shapes, state: Dict[str, Any], path_of):
    """The benchmark's weights (host arrays), placed on the device in the
    program's tree; `path_of(name)` -> (collection, module path, leaf) is the
    family's. Refuses a tree whose structure or shapes (`shapes`: those of the
    program's own `global_vars`) are not the reference's."""
    from dba_mod_tpu.models import ModelVars
    tree: dict = {"params": {}, "batch_stats": {}}
    for name, value in state.items():
        coll, mod, leaf = path_of(name)
        node = tree[coll]
        for m in mod:
            node = node.setdefault(m, {})
        node[leaf] = jnp.asarray(value)  # host -> a device buffer of its own
    new = ModelVars(params=tree["params"], batch_stats=tree["batch_stats"])
    got = tree_shapes(new)
    if (jax.tree_util.tree_structure(shapes) != jax.tree_util.tree_structure(got)
            or jax.tree_util.tree_leaves(shapes) != jax.tree_util.tree_leaves(got)):
        raise SystemExit("chipbench: the program's model tree is not the "
                         "reference's layout")
    return new


def tree_shapes(model_vars):
    return jax.tree_util.tree_map(lambda l: (l.shape, str(l.dtype)), model_vars)


def from_program(model_vars, names, path_of) -> Dict[str, np.ndarray]:
    """The program's state on the host, under the reference's names."""
    host = jax.device_get(model_vars)
    out = {}
    for name in names:
        coll, mod, leaf = path_of(name)
        node = host.params if coll == "params" else host.batch_stats
        for m in mod:
            node = node[m]
        out[name] = np.asarray(node[leaf])
    return out


def seed_selection(exp, seed: int) -> None:
    """Which clients the coming rounds select, and in which order."""
    exp.select_rng = random.Random(int(seed))


def seed_state(exp, seed: int, state, to_tree) -> None:
    """Everything of the program that a seed sets, from `seed`: the client
    selection (`select_rng`), the batch order (`plan_rng`), the device RNG
    (`rng_key`: for a model with an objective of its own, its noise) and the
    weights, `state`, which go in through the family's `to_tree(shapes,
    state)`. `state` is a dict of host arrays, or a call that makes one: it is
    called once the old state has left the device, so weights that are made
    on the device never lie there beside the ones they replace.

    The harness calls it with two seeds (`run.py`): `--seed` before each
    check round, and the configuration's `population_seed` before the warm
    round and the window (`run.seed_window`). Weights, noise and batch order
    can all move a round's time (a sparse expert product runs the rows the
    router sent), so the window's are the population's; `run.run_window` sets
    the selection from the same seed again at the start of every period."""
    shapes = tree_shapes(exp.global_vars)
    exp.global_vars = None  # free the old state first: no second copy at the peak
    if callable(state):
        state = state()
    seed_selection(exp, seed)
    exp.plan_rng = np.random.RandomState(int(seed) % (2 ** 32))
    exp.rng_key = jax.random.key(int(seed) % (2 ** 31 - 1))
    exp.global_vars = to_tree(shapes, state)


# ------------------------------------------------------------- the round program
def round_program(exp):
    """The compiled program the window drives."""
    return (exp.engine.round_fn_donated if exp._use_donated_round
            else exp.engine.round_fn)


def engine_report(exp, on_tpu: bool,
                  family_conditions: Dict[str, bool]) -> Dict[str, Any]:
    """The program that ran (chip_smoke.check_engine_is_the_chips, copied): the
    donated round program is built, is the one dispatched, was compiled once,
    and nothing in it is interpreted; beside these the conditions the
    configuration's family sets on its engine (`{name: bool}`)."""
    eng = exp.engine
    donated = eng.round_fn_donated
    seen = {"round_fn_donated_built": donated is not None,
            "use_donated_round": bool(exp._use_donated_round),
            "donated_programs_compiled":
                donated._cache_size() if donated is not None else 0,
            "undonated_programs_compiled": eng.round_fn._cache_size(),
            "fused_interpret": bool(eng.fused_interpret),
            "mesh": exp.mesh is not None,
            **family_conditions}
    if on_tpu:
        ok = bool(
            seen["round_fn_donated_built"] and seen["use_donated_round"]
            and seen["donated_programs_compiled"] == 1
            and seen["undonated_programs_compiled"] == 0
            and not seen["fused_interpret"])
    else:  # a rehearsal's engine is the CPU's: one program, whichever it is
        ok = (seen["donated_programs_compiled"]
              + seen["undonated_programs_compiled"]) == 1
    seen["ok"] = ok and all(family_conditions.values())
    return seen


def check_round(exp, epoch: int, real_steps: int) -> Dict[str, Any]:
    """One call of the window's own compiled round program, at the window's
    own shapes, on a feed in which every client takes only its first
    `real_steps` steps: the rest of the static plan is masked, and the client
    step's loop runs only the steps some lane needs (in chunks of a few), so
    a check round runs one chunk. The clients are those `exp.select_rng`
    draws: `--seed`'s. Returns the feed and what the program produced, on the
    host."""
    tasks_seq, idx_seq, mask_seq, ns, lane = exp.build_static_round_inputs(epoch)
    mask = np.array(mask_seq)
    mask[:, :, 1:] = False
    mask[:, :, 0, real_steps:] = False
    exp.rng_key, round_key = jax.random.split(exp.rng_key)
    rng_t, rng_a = jax.random.split(round_key)
    t0 = time.perf_counter()
    new_vars, new_fg, payload = round_program(exp)(
        exp.global_vars, exp.fg_state, tasks_seq, idx_seq, jnp.asarray(mask),
        lane, ns, rng_t, rng_a)
    jax.block_until_ready(new_vars)
    seconds = time.perf_counter() - t0
    exp.global_vars, exp.fg_state = new_vars, new_fg
    locals_, globals_, metrics, delta_norms = jax.device_get(payload[:4])
    tasks = jax.device_get(tasks_seq)
    return {"seconds": seconds, "epoch": epoch, "real_steps": real_steps,
            "idx": np.asarray(idx_seq)[0, :, 0, :real_steps],    # [C,K,B]
            "mask": mask[0, :, 0, :real_steps],
            "lr": np.asarray(tasks.lr_row)[0, :, 0],
            "scale": np.asarray(tasks.scale)[0],
            "poisoning_per_batch": np.asarray(tasks.poisoning_per_batch)[0],
            "adv_index": np.asarray(tasks.adv_index)[0],
            "new_vars": new_vars,
            "loss_sum": np.asarray(metrics.loss_sum)[0, :, 0],       # [C]
            "delta_norms": np.asarray(delta_norms),                   # [C]
            "global_loss": float(globals_.clean.loss),
            "global_acc": float(globals_.clean.acc)}


def recorded_rows(exp) -> list:
    import json
    path = Path(exp.folder) / "metrics.jsonl"
    if not path.exists():
        return []
    return [json.loads(line) for line in path.read_text().splitlines() if line]


def all_finite(tree) -> bool:
    leaves = jax.tree_util.tree_leaves(tree)
    return bool(jax.jit(lambda ls: jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(l)) for l in ls])))(leaves))
