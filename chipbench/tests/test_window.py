"""The window as whole periods of one selection, on the CPU and in seconds
(`python -m pytest chipbench/tests/test_window.py -q`; no `Experiment` is
built):

- `program.make_params` lays the traffic's schedule over every period;
- `run.run_window` over a stub experiment ends on a period's last round, and
  `run.end_to_end` of it is a rate over all its rounds and all its time;
- the program's real `select_agents`, driven as the window drives it, gives
  the same agent names at two `--seed`s and in the first and second period,
  while the check rounds' selection (still `--seed`'s) differs;
- `run.seed_window` on a stub experiment and a stub family: the state the
  window starts from, its fingerprint, the batch order and the device RNG are
  the same at two `--seed`s and follow `population_seed`, the old state has
  left the program before the new one is made, and `run.seeded_check_rounds`
  runs each check round on `init_weights(--seed)` and on RNGs seeded afresh;
- one rehearsal of a small cell (`bench_small.json`, half a minute a seed) at
  two `--seed`s prints the same `window_seed` and fingerprint on its `window`
  line while the numbers compared differ;
- the two count readers take sums of parts over sums of wholes: on the
  recorded `testdata/steps_sample.json` and on a synthetic context.
"""
from __future__ import annotations

import argparse
import json
import random
import time
from pathlib import Path

import jax
import numpy as np
import pytest
from dba_mod_tpu.fl.selection import select_agents

from chipbench import program
from chipbench import run as harness
from chipbench import selfcheck_steps as sc

CHIPBENCH = Path(harness.__file__).resolve().parent
FIRST = harness.FIRST_WINDOW_EPOCH


def load(config="tiny_resnet18_dba", traffic="attack_rounds"):
    return (json.loads((CHIPBENCH / "configs" / f"{config}.json").read_text()),
            json.loads((CHIPBENCH / "traffic" / f"{traffic}.json").read_text()))


def test_the_schedule_is_laid_over_every_period(tmp_path):
    config, traffic = load()
    traffic = dict(traffic, periods_max=3)
    params, raw = program.make_params(config, traffic, tmp_path, FIRST)
    # window round r of period p is epoch FIRST + 10 p + r - 1
    assert [raw[f"{i}_poison_epochs"] for i in range(4)] == [
        [6, 16, 26], [8, 18, 28], [10, 20, 30], [12, 22, 32]]
    assert [params.poison_epochs_for(i) for i in range(4)] == [
        [6, 16, 26], [8, 18, 28], [10, 20, 30], [12, 22, 32]]
    poisoned = {e: params.scheduled_adversaries([e]) for e in range(1, 40)}
    assert {e: a for e, a in poisoned.items() if a} == {
        6: [0], 8: [20], 10: [74], 12: [95], 16: [0], 18: [20], 20: [74],
        22: [95], 26: [0], 28: [20], 30: [74], 32: [95]}
    _, clean = load(traffic="clean_rounds")
    _, raw = program.make_params(config, clean, tmp_path, FIRST)
    assert not raw["is_poison"]
    assert all(raw[f"{i}_poison_epochs"] == [] for i in range(4))
    with pytest.raises(SystemExit):
        program.make_params(config, dict(traffic, period_rounds=8), tmp_path,
                            FIRST)


class StubExperiment:
    """What `run_window` touches of an `Experiment`, with the program's real
    `select_agents` behind `run_round`."""
    global_vars = None
    last_global_loss = 1.0

    def __init__(self, params=None, round_s=0.0):
        self.params, self.round_s = params, round_s
        self.select_rng = random.Random(0)
        self.epochs, self.saved = [], []

    def run_round(self, epoch):
        time.sleep(self.round_s)
        self.epochs.append(epoch)
        agents = []
        if self.params is not None:
            total = int(self.params["number_of_total_participants"])
            adversaries = list(self.params.adversary_list)
            agents, _ = select_agents(
                self.params, epoch, list(range(total)),
                [n for n in range(total) if n not in adversaries],
                self.select_rng)
        return {"epoch": epoch, "agents": agents}

    def save_model(self, epoch):
        self.saved.append(epoch)


def test_a_window_is_whole_periods():
    exp = StubExperiment(round_s=0.1)
    won = harness.run_window(exp, 0.5, FIRST, period=3, periods_max=32,
                             selection_seed=1)
    # 0.5 s pass in the second period's last round: the window ends with it
    assert won["periods"] == 2 and len(won["rounds_s"]) == 6
    assert exp.epochs == exp.saved == list(range(FIRST, FIRST + 6))
    assert won["failed"] == 0 and won["traced"] is None
    assert 0.6 <= sum(won["rounds_s"]) <= won["window_s"] < 1.2
    numbers = harness.end_to_end(won["rounds_s"], won["failed"], 10,
                                 won["window_s"], 3 * 2 ** 30, 50.0)
    assert numbers["client_updates_per_s"][0] == pytest.approx(
        6 * 10 / won["window_s"])
    assert numbers["round_s_max"][0] == max(won["rounds_s"]) >= 0.1
    # a window of no time starts no period; one whose periods are used up ends
    assert harness.run_window(StubExperiment(), 0.0, FIRST, 3, 32, 1)[
        "rounds_s"] == []
    short = harness.run_window(StubExperiment(), 5.0, FIRST, 3, 2, 1)
    assert short["periods"] == 2 and len(short["rounds_s"]) == 6


def test_a_failed_round_counts_and_the_period_goes_on():
    class Failing(StubExperiment):
        def run_round(self, epoch):
            if epoch == FIRST + 1:
                raise RuntimeError("round lost")
            return super().run_round(epoch)
    exp = Failing(round_s=0.01)
    won = harness.run_window(exp, 0.001, FIRST, 3, 32, 1)
    assert won["failed"] == 1 and len(won["rounds_s"]) == 3
    assert exp.saved == [FIRST, FIRST + 2]


def window_agents(params, seed, period, seconds=0.3):
    """The selection of a run at `seed`: set-up's rounds draw from `--seed`
    (`seed_state` seeds `select_rng` so), the window from the population."""
    exp = StubExperiment(params, round_s=0.02)
    program.seed_selection(exp, seed)       # what seed_state does with --seed
    check_rounds = [exp.run_round(e)["agents"] for e in (FIRST + 2, 2)]
    config, _ = load()
    won = harness.run_window(exp, seconds, FIRST, period, 32,
                             int(config["population_seed"]))
    return check_rounds, [r["agents"] for r in won["results"]]


def test_the_window_selects_the_same_clients_at_every_seed_and_period(tmp_path):
    config, traffic = load()
    params, _ = program.make_params(config, traffic, tmp_path, FIRST)
    period = int(traffic["period_rounds"])
    checks_a, agents_a = window_agents(params, 2147483777, period)
    checks_b, agents_b = window_agents(params, 2147484999, period)
    assert agents_a == agents_b                        # across seeds
    assert len(agents_a) == 2 * period
    assert agents_a[:period] == agents_a[period:]      # across periods
    assert harness.selection_repeats(agents_a, period)
    assert not harness.selection_repeats(
        agents_a[:period] + [agents_a[0]] * period, period)
    assert checks_a != checks_b                        # --seed's, as before
    # the period's schedule: adversaries 0, 20, 74, 95 lead rounds 3, 5, 7, 9
    lead = {r: agents_a[r - 1][0] for r in traffic["poison_window_rounds"]}
    assert lead == {3: 0, 5: 20, 7: 74, 9: 95}
    assert all(len(set(names)) == 10 for names in agents_a)
    assert len({tuple(names) for names in agents_a[:period]}) == period


class StubFamily:
    """What `seed_window` and `seeded_check_rounds` touch of a family: weights
    that follow their seed, a window state that follows the population, and a
    check round that notes what it was given."""

    def __init__(self):
        self.seen = []

    @staticmethod
    def init_weights(seed, model):
        rng = np.random.RandomState(seed % 2 ** 32)
        return {name: rng.standard_normal(model["shape"]).astype(np.float32)
                for name in ("a.weight", "b.weight", "c.weight", "d.stat")}

    @staticmethod
    def window_state(state, population, model):
        return {**state, "d.stat": state["d.stat"] + population["shift"]}

    @staticmethod
    def to_program(shapes, state):
        return dict(state)

    @staticmethod
    def from_program(model_vars, names):
        return {n: np.asarray(model_vars[n]) for n in names}

    def check_round(self, exp, epoch, real_steps):
        self.seen.append({"state": dict(exp.global_vars),
                          "select": exp.select_rng.random(),
                          "plan": exp.plan_rng.randint(2 ** 31),
                          "key": jax.random.key_data(exp.rng_key).tolist()})
        exp.global_vars = {n: v + 1 for n, v in exp.global_vars.items()}
        return {"seconds": 0.0, "epoch": epoch, "real_steps": real_steps,
                "new_vars": exp.global_vars}


class NoEvents:
    @staticmethod
    def snapshot():
        return {}


STUB = {"population_seed": 1, "model": {"shape": [3, 5]}}
POPULATION = {"shift": 0.5}


def seeded_run(seed, config=STUB):
    """Set-up's seeding of a run at `--seed`, as `run_cell` orders it: the
    check rounds, then the window's job."""
    exp, family = StubExperiment(), StubFamily()
    exp.global_vars = family.init_weights(0, config["model"])
    _, traffic = load()
    state0, checks = harness.seeded_check_rounds(
        exp, family, config, traffic, seed, FIRST, NoEvents)
    said = harness.seed_window(exp, family, config, POPULATION)
    return {"state0": state0, "checks": checks, "seen": family.seen,
            "said": said, "state": exp.global_vars,
            "plan": exp.plan_rng.randint(2 ** 31), "select": exp.select_rng.random(),
            "key": jax.random.key_data(exp.rng_key).tolist()}


def same_state(a, b):
    return a.keys() == b.keys() and all(np.array_equal(a[n], b[n]) for n in a)


def test_the_window_starts_from_the_populations_job_at_every_seed():
    a, b = seeded_run(2147483777), seeded_run(2147484999)
    assert a["said"] == b["said"] and a["said"]["window_seed"] == 1
    assert same_state(a["state"], b["state"])
    assert (a["plan"], a["select"], a["key"]) == (b["plan"], b["select"], b["key"])
    # it is the population's seed that sets it, and the family's window rule
    want = StubFamily.window_state(StubFamily.init_weights(1, STUB["model"]),
                                   POPULATION, STUB["model"])
    assert same_state(a["state"], want)
    assert a["said"]["window_fingerprint"] == harness.fingerprint(want)
    assert a["plan"] == np.random.RandomState(1).randint(2 ** 31)
    assert a["key"] == jax.random.key_data(jax.random.key(1)).tolist()
    other = seeded_run(2147483777, dict(STUB, population_seed=2))
    assert other["said"]["window_seed"] == 2
    assert other["said"]["window_fingerprint"] != a["said"]["window_fingerprint"]
    assert (other["plan"], other["key"]) != (a["plan"], a["key"])
    assert not same_state(other["state"], a["state"])


def test_a_fingerprint_names_the_largest_leaves_with_their_float64_sums():
    state = {"bias": np.zeros(3, np.float32), **{
        name: np.random.RandomState(i).standard_normal(n).astype(np.float32)
        for i, (name, n) in enumerate(
            [("b.w", 50), ("a.w", 50), ("head", 70), ("small", 7)])}}
    got = harness.fingerprint(state)
    assert list(got) == ["head", "a.w", "b.w"]             # size, then name
    assert got["a.w"] == float(np.sum(state["a.w"].astype(np.float64)))
    assert harness.fingerprint({"only": np.ones(4, np.float32)}) == {"only": 4.0}
    big = {"w": np.full(2 ** 25, 0.1, np.float32)}        # float32 would drift
    assert harness.fingerprint(big)["w"] == pytest.approx(
        2 ** 25 * float(np.float32(0.1)), rel=1e-12)


def test_the_check_rounds_are_the_seeds():
    a, b = seeded_run(2147483777), seeded_run(2147484999)
    for run, seed in ((a, 2147483777), (b, 2147484999)):
        want = StubFamily.init_weights(seed, STUB["model"])
        assert same_state(run["state0"], want)
        # each check round on those weights and on RNGs seeded afresh, though
        # the round before it moved both
        assert len(run["seen"]) == len(harness.CHECK_STEPS)
        assert all(same_state(seen["state"], want) for seen in run["seen"])
        assert len({(s["select"], s["plan"], str(s["key"]))
                    for s in run["seen"]}) == 1
        assert [c["real_steps"] for c in run["checks"]] == list(harness.CHECK_STEPS)
        assert all(same_state(c["state"], {n: v + 1 for n, v in want.items()})
                   for c in run["checks"])
    assert not same_state(a["state0"], b["state0"])
    assert a["seen"][0]["plan"] != b["seen"][0]["plan"]
    assert a["seen"][0]["key"] != b["seen"][0]["key"]


def test_the_old_state_leaves_the_program_before_the_windows_is_made():
    exp = StubExperiment()
    exp.global_vars = StubFamily.init_weights(0, STUB["model"])
    held = []

    class Watching(StubFamily):
        @staticmethod
        def init_weights(seed, model):
            held.append(exp.global_vars)
            return StubFamily.init_weights(seed, model)
    harness.seed_window(exp, Watching(), STUB, POPULATION)
    assert held == [None] and exp.global_vars is not None


def rehearse(seed, capsys):
    """The result and the findings, by phase, of one rehearsed run (the LeNet
    cell of `bench_small.json`: the whole of `run_cell` but the look for a
    chip)."""
    args = argparse.Namespace(
        workload="mnist_dba_attack", seed=seed, seconds=0.1, trace=0,
        rehearse=True, override=None, overrides=None,
        benchmark_file=str(CHIPBENCH / "tests" / "bench_small.json"))
    result = harness.run_cell(args)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return result, {line["phase"]: line for line in lines}


def test_two_seeds_rehearse_one_window(capsys):
    (res_a, a), (res_b, b) = (rehearse(seed, capsys)
                              for seed in (2147483777, 2147484999))
    config, _ = load("mnist_lenet_dba")
    assert a["window"]["window_seed"] == b["window"]["window_seed"] == int(
        config["population_seed"])
    assert a["window"]["window_fingerprint"] == b["window"]["window_fingerprint"]
    assert len(a["window"]["window_fingerprint"]) == 3
    marks = list(a["window"]["setup_marks_s"].values())       # in set-up's order
    assert marks == sorted(marks) and len(marks) == 4
    assert a["window"]["agents"] == b["window"]["agents"]
    # one job: the rounds' results agree to the digit, where the check rounds,
    # which are --seed's, read other numbers against the reference
    assert a["window"]["global_loss"] == b["window"]["global_loss"]
    assert a["window"]["backdoor_acc"] == b["window"]["backdoor_acc"]
    assert res_a["check_ok"] and res_b["check_ok"]
    assert res_a["compared"] != res_b["compared"]


@pytest.mark.parametrize("reader", sc.NAMES)
def test_count_readers_take_their_shares_over_the_windows_totals(reader):
    _, mod = sc.readers()[reader]
    # synthetic: window rounds of 37, 185 and 0 steps run of 370 planned, with
    # 370, 407 and 0 real lane-steps at 10 lanes
    want = {"train_steps_run_pct": 100 * 222 / 1110,
            "train_lane_fill_pct": 100 * 777 / 2220}[reader]
    assert mod.read(sc.context(sc.synthetic_records(), 3)) == pytest.approx(want)
    medians = {"train_steps_run_pct": 10.0, "train_lane_fill_pct": 61.0}
    assert abs(want - medians[reader]) > 5          # not a median of shares
    # recorded on the chip: two clean window rounds after the warm round
    sample = json.loads(sc.SAMPLE.read_text())
    records = [sc.Span(r["name"], r["start_ns"], r["end_ns"], None, r["round"],
                       r["counts"]) for r in sample["records"]]
    window = [r.counts for r in records if r.name == "round/plan"][-2:]
    by_hand = {
        "train_steps_run_pct": 100 * sum(c["steps_run"] for c in window)
        / sum(c["steps_plan"] for c in window),
        "train_lane_fill_pct": 100 * sum(c["lane_steps_real"] for c in window)
        / sum(c["steps_run"] * c["lanes"] for c in window)}[reader]
    assert mod.read(sc.context(records, 2)) == pytest.approx(by_hand)
    assert by_hand == pytest.approx(sample["readings"][reader])
    # one round of the two: only that round's counts
    one = 100 * {"train_steps_run_pct": window[-1]["steps_run"] / 370,
                 "train_lane_fill_pct": window[-1]["lane_steps_real"]
                 / (window[-1]["steps_run"] * 10)}[reader]
    assert mod.read(sc.context(records, 1)) == pytest.approx(one)


def test_the_traced_span_is_rounds_of_the_period():
    _, traffic = load()
    assert harness.trace_span_of(traffic) == (2, 3)
    with pytest.raises(SystemExit):
        harness.trace_span_of(dict(traffic, trace_window_rounds=[11]))
    with pytest.raises(SystemExit):
        harness.trace_span_of(dict(traffic, trace_window_rounds=[]))
