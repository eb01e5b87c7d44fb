"""The streamed round (fl/streamed.py): one client live at a time.

- on MNIST LeNet it is the stacked FedAvg round to the bit (a clean and a
  poisoned round, the accumulator zero again after each): ties the new
  engine to the old;
- a rule that needs every delta at once is refused at build, by name;
- an `lfm2_moe` experiment runs poisoned rounds through it, records
  main-task and backdoor rows, and puts its counts on the spans;
- the benchmark's family for it offers the whole interface, and its check
  rounds at a toy size are inside toy limits against the plain reference,
  with the bfloat16 control over one;
- the same two for an `sdar_moe` experiment (block diffusion: the objective
  is the model's own, the reference draws the program's noise from the
  feed's key);
- and for a `smallthinker` experiment (two kinds of attention layer, a
  router that reads the pre-attention norm, ReGLU experts), whose plan also
  says what its attention is made of.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import families, program
from chipbench import run as harness
from dba_mod_tpu.config import Params
from dba_mod_tpu.fl.experiment import Experiment
from dba_mod_tpu.fl.rounds import RoundEngine
from dba_mod_tpu.utils import telemetry
from tests import sdar_cases, smallthinker_cases
from tests.lfm2_cases import ARCH, params

SMOKE = "configs/smoke_params.yaml"
LIMITS = {"loss_gap.k1": 1e-4, "update_rel_l2.k1": 1e-3,
          "delta_norm_gap.k1": 1e-3, "loss_gap.k3": 1e-4,
          "update_rel_l2.k3": 1e-3, "update_norm_gap.k3": 1e-2}


@pytest.fixture(scope="module")
def lenet():
    exp = Experiment(Params.from_yaml(SMOKE), save_results=False)
    engine = RoundEngine(exp.params,
                         dataclasses.replace(exp.model_def, streamed=True),
                         exp.device_data, exp.eval_plans)
    return exp, engine


@pytest.mark.parametrize("epoch", [1, 4])   # clean; two adversaries poison
def test_the_streamed_round_is_the_stacked_round_on_lenet(lenet, epoch):
    exp, engine = lenet
    tasks, idx, mask, ns, lane = exp.build_static_round_inputs(epoch)
    assert bool(np.asarray(tasks.poisoning_per_batch).any()) == (epoch == 4)
    k1, k2 = jax.random.split(jax.random.key(epoch))
    new, _, payload = exp.engine.round_fn(
        exp.global_vars, exp.fg_state, tasks, idx, mask, lane, ns, k1, k2)
    got, _, engine.workspace, got_payload = engine.round_fn(
        exp.global_vars, exp.fg_state,
        engine.round_workspace(exp.global_vars), tasks, idx, mask, lane, ns,
        k1, k2, exp.device_data.train_source)
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for slot in (1, 2, 3):                   # global battery, metrics, norms
        for a, b in zip(jax.tree_util.tree_leaves(payload[slot]),
                        jax.tree_util.tree_leaves(got_payload[slot])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the local battery reads the client's own weights, not global + delta /
    # scale: the same model up to rounding
    for a, b in zip(jax.tree_util.tree_leaves(payload[0]),
                    jax.tree_util.tree_leaves(got_payload[0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    assert all(not np.asarray(l).any()
               for l in jax.tree_util.tree_leaves(engine.workspace.acc))
    assert int(got_payload[11].cells) == 0   # LeNet counts nothing


@pytest.mark.parametrize("extra,words", [
    ({"aggregation_methods": "geom_median"}, "flatten_stacked"),
    ({"aggregation_methods": "krum"}, "FedAvg"),
    ({"aggr_epoch_interval": 2}, "aggr_epoch_interval"),
    ({"forensics": True}, "forensics"),
])
def test_what_needs_every_delta_at_once_is_refused_by_name(extra, words):
    with pytest.raises(ValueError, match="streamed round") as e:
        Experiment(params(**extra), save_results=False)
    assert words in str(e.value)


def test_an_lfm2_experiment_trains_poisons_and_records(tmp_path):
    exp = Experiment(params(run_dir=str(tmp_path), telemetry=False),
                     save_results=True)
    assert exp.engine.streamed and exp.engine.wide_from == 5
    mark = len(telemetry.spans())
    results = [exp.run_round(e) for e in (1, 2, 3)]
    assert [r["epoch"] for r in results] == [1, 2, 3]
    assert all(np.isfinite(r["global_acc"]) for r in results)
    assert results[1]["backdoor_acc"] is not None
    rows = program.recorded_rows(exp)
    assert [r["epoch"] for r in rows] == [1, 2, 3]
    assert rows[1]["adversaries"] == ["0", "1", "2", "3"]
    poison_rows = (exp.folder / "posiontest_result.csv").read_text().splitlines()
    assert any(line.startswith("global,2,") for line in poison_rows)
    assert sum(line.split(",")[1] == "2" for line in poison_rows) == 1 + 2 * 4
    spans = telemetry.spans()[mark:]
    plan = [s.counts for s in spans if s.name == "round/plan"]
    record = [s.counts for s in spans if s.name == "round/record"]
    # 4 clients x 2 steps; a poisoned round: every client an adversary's 6
    assert [c["client_steps"] for c in plan] == [8, 24, 8]
    assert all(c["tokens_step"] == 2 * 32 for c in plan)
    held = [c["expert_tokens_held"] for c in record]
    # two expert layers, half the experts held: about a choice a token a layer
    assert all(0.5 < h / (c["client_steps"] * 64 * 2) < 1.5
               for h, c in zip(held, plan))
    assert all(c["expert_tokens_max"] >= c["expert_tokens_mean"] for c in record)
    # on this CPU every held expert runs over every token: 2 layers x 4 held
    # x 64 rows a step, all of them run
    assert all(c["expert_rows_run"] == c["expert_rows_all"]
               == p["client_steps"] * 2 * 4 * 64 for c, p in zip(record, plan))
    # the workspace goes and comes back
    exp.engine.release_workspace()
    assert exp.engine.workspace is None
    assert np.isfinite(exp.run_round(4)["global_acc"])


def test_an_sdar_experiment_trains_poisons_and_records(tmp_path):
    """Block diffusion through `main.py`'s path: the streamed round calls the
    model's own objective; a poisoned round records its backdoor rows; the
    plan's and the record's spans carry the counts the readers read."""
    exp = Experiment(sdar_cases.params(run_dir=str(tmp_path), telemetry=False),
                     save_results=True)
    assert exp.engine.streamed and exp.model_def.objective is not None
    # the streams draw every id but MASK, the vocabulary's last
    assert exp.token_data.vocab_size == 127
    assert int(exp.token_data.train_tokens.max()) <= 126
    mark = len(telemetry.spans())
    results = [exp.run_round(e) for e in (1, 2, 3)]
    assert all(np.isfinite(r["global_acc"]) for r in results)
    assert results[1]["backdoor_acc"] is not None
    rows = program.recorded_rows(exp)
    assert [r["epoch"] for r in rows] == [1, 2, 3]
    assert rows[1]["adversaries"] == ["0", "1", "2", "3"]
    poison_rows = (exp.folder / "posiontest_result.csv").read_text().splitlines()
    assert sum(line.split(",")[1] == "2" for line in poison_rows) == 1 + 2 * 4
    spans = telemetry.spans()[mark:]
    plan = [s.counts for s in spans if s.name == "round/plan"]
    record = [s.counts for s in spans if s.name == "round/record"]
    # 4 clients x 2 rows of their own, a row a step; a poisoned round: every
    # client an adversary's 3 epochs
    assert [c["client_steps"] for c in plan] == [8, 24, 8]
    assert all(c["tokens_step"] == 2 * 32 and c["block_length"] == 4
               for c in plan)
    # packed rows hold no padding: every position is scored, and the noise
    # masks between its bounds of them
    assert [c["positions_scored"] for c in record] == [8 * 32, 24 * 32, 8 * 32]
    assert all(0.45 < c["positions_masked"] / c["positions_scored"] < 0.95
               for c in record)
    # layer 0 routes both streams (64 positions), the last the noisy one (32):
    # 96 positions x 2 choices, half the experts held
    assert all(0.5 < c["expert_tokens_held"] / (p["client_steps"] * 96) < 1.5
               for c, p in zip(record, plan))
    assert all(c["expert_tokens_max"] >= c["expert_tokens_mean"] for c in record)
    # the mean is over (step, layer, held expert) cells: 2 layers x 4 held; the
    # row counts are no part of it, and on this CPU every held expert runs
    # over every position: 4 x (64 + 32) rows a step, all of them run
    assert all(c["expert_tokens_mean"] == pytest.approx(
        c["expert_tokens_held"] / (p["client_steps"] * 2 * 4))
        for c, p in zip(record, plan))
    assert all(c["expert_rows_run"] == c["expert_rows_all"]
               == p["client_steps"] * 4 * 96 for c, p in zip(record, plan))


def test_a_smallthinker_experiment_trains_poisons_and_records(tmp_path):
    """Layers of two attention kinds through `main.py`'s path, next-token
    form, `run_batch` without an objective of the model's own; the plan's and
    the record's spans carry the counts the `st_*` readers read."""
    exp = Experiment(smallthinker_cases.params(run_dir=str(tmp_path),
                                               telemetry=False),
                     save_results=True)
    assert exp.engine.streamed and exp.model_def.objective is None
    mark = len(telemetry.spans())
    results = [exp.run_round(e) for e in (1, 2, 3)]
    assert all(np.isfinite(r["global_acc"]) for r in results)
    assert results[1]["backdoor_acc"] is not None
    rows = program.recorded_rows(exp)
    assert [r["epoch"] for r in rows] == [1, 2, 3]
    assert rows[1]["adversaries"] == ["0", "1", "2", "3"]
    spans = telemetry.spans()[mark:]
    plan = [s.counts for s in spans if s.name == "round/plan"]
    record = [s.counts for s in spans if s.name == "round/record"]
    # 4 clients x 2 rows of their own, a row a step; a poisoned round: every
    # client an adversary's 3 epochs
    assert [c["client_steps"] for c in plan] == [8, 24, 8]
    assert all(c["tokens_step"] == 32 and "block_length" not in c
               for c in plan)
    # rows of 32 under a window of 8: the pairs each kind's mask allows; on
    # this CPU the scores are written out and no tile is counted
    assert all(c["attention_pairs_full"] == 32 * 33 // 2
               and c["attention_pairs_window"] == 8 * 9 // 2 + 24 * 8
               for c in plan)
    assert all(c["attention_tiles_run"] == c["attention_tiles_all"]
               == c["attention_tiles_full"] == c["attention_tiles_window"] == 0
               for c in plan)
    # 2 layers x 32 positions x 3 choices of 16 experts, 4 of them held
    assert all(0.5 < c["expert_tokens_held"] / (p["client_steps"] * 48) < 1.5
               for c, p in zip(record, plan))
    assert all(c["expert_rows_run"] == c["expert_rows_all"]
               == p["client_steps"] * 2 * 4 * 32 for c, p in zip(record, plan))


CONFIG = {"name": "lfm2_toy", "population_seed": 1,
          "model": {"family": "lfm2_moe", "seq_len": 32, "arch": ARCH}}
SDAR_CONFIG = {"name": "sdar_toy", "population_seed": 1,
               "model": {"family": "sdar_moe", "seq_len": 32,
                         "arch": sdar_cases.ARCH}}
ST_CONFIG = {"name": "smallthinker_toy", "population_seed": 1,
             "model": {"family": "smallthinker", "seq_len": 32,
                       "arch": smallthinker_cases.ARCH}}
TRAFFIC = {"is_poison": True, "period_rounds": 10,
           "poison_window_rounds": [3, 5, 7, 9], "periods_max": 1,
           "num_devices": 0}


class Events:
    def snapshot(self):
        return {}


@pytest.mark.parametrize("dtype,inside", [("float32", True), ("bfloat16", False)])
@pytest.mark.parametrize("toy,toy_params", [
    (CONFIG, params), (SDAR_CONFIG, sdar_cases.params),
    (ST_CONFIG, smallthinker_cases.params)],
    ids=["lfm2_moe", "sdar_moe", "smallthinker"])
def test_the_familys_check_rounds_against_the_reference(tmp_path, toy,
                                                        toy_params, dtype,
                                                        inside):
    family = families.of(toy)
    assert all(callable(getattr(family, n)) for n in families.INTERFACE)
    config = {**toy, "params": dict(toy_params().raw)}
    first = harness.FIRST_WINDOW_EPOCH
    p, raw = program.make_params(config, TRAFFIC, tmp_path, first,
                                 overrides={"compute_dtype": dtype})
    exp, _ = program.build_experiment(p)
    state0, checks = harness.seeded_check_rounds(
        exp, family, config, TRAFFIC, 2147483659, first, Events())
    assert [c["real_steps"] for c in checks] == [1, 3]
    assert checks[0]["poisoning_per_batch"].max() == 1   # the poisoned epoch
    assert checks[0]["scale"].max() == 5 and checks[1]["scale"].max() == 1
    population = family.population_of(exp)
    compared = harness.judge(family, raw, config["model"], state0, population,
                             checks, LIMITS)
    assert {row["number"] for row in compared} == set(LIMITS)
    assert all(np.isfinite(row["value"]) for row in compared)
    assert all(row["ok"] for row in compared) == inside, compared
    assert exp.engine.workspace is None      # released for the reference
    report = program.engine_report(exp, False, family.engine_conditions(exp))
    assert report["streamed_round"]
    # a family may ask for kernel forms that only a TPU runs (smallthinker:
    # the CPU writes its scores out and runs every held expert everywhere)
    kernels = {"attention_kernel", "grouped_experts"} & set(report)
    assert report["ok"] == (not kernels) and not any(report[k] for k in kernels)
    flops = family.model_flops(config["model"])
    assert flops["train_step"] == 3 * flops["forward"] > 0
