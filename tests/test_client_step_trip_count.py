"""The client step's loops run only the steps some lane needs (fl/client.py:
`active_steps` -> a full-width `while` over chunks of STEP_CHUNK steps up to
the last step `wide_from` lanes share; `split_steps` -> a job loop that runs
at width 1 what each lane holds after it), and what they compute is what the
full-length loop computed, to the bit.

The reference kept here (`make_full_length_client_step`) is the loop as it
was before: one `lax.scan` over all E x S plan steps, masked steps included.
An engine built with it in place of `make_client_step` is driven on the same
feeds as the program's own, here on one device and at both values of
`wide_from` the engine's rule gives an unsharded engine
(fl/rounds.py::wide_from_of): 2, where only one lane's tail is a job (PR
28's program), and C + 1, where every lane with data is one job and the
full-width loop runs nothing (what the rule gives this LeNet, a model with
convolutions: that engine is built with the rule itself):

- heavy_tail: a Dirichlet population's round with one 6-epoch adversary
  beside 2-epoch benign lanes (the shape of the paper's attack round);
- all_full: every lane real at every step (the loop runs what it ran; E*S
  = 18 is no multiple of the chunk, so the last chunk reaches past the plan);
- empty_client: one lane with no batch at all (no job at either value);
- check_k1 / check_k3: the benchmark's output-check feed
  (chipbench/program.py::check_round): only the first 1 or 3 steps of epoch 0;
- solo_lane: the adversary's lane holds all the data: the full-width loop
  runs nothing and every step is a job's;
- two_tails: a second long lane (the widest benign lane, trained 6 epochs
  as the adversary is): at 2 the full-width loop runs to the last step the
  two share, and the tail starts after the shorter;
- two_jobs: the widest benign lane trained 3 epochs: past the last step it
  shares with the adversary it still holds one of its own, so at 2 the
  round has two jobs (in a plan of whole epochs, the most a round can have).

On the mesh (and with one lane) the engine builds no job loop (`wide_from`
1): the same feeds run the full-width loop to the end
(tests/test_client_step_trip_count_mesh.py; the reference step, the feeds
and the checks both files run are in tests/trip_count_cases.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dba_mod_tpu.fl.rounds as rounds_mod
import trip_count_cases as tc
from dba_mod_tpu import models
from dba_mod_tpu.config import Params
from dba_mod_tpu.data.batching import plan_step_counts
from dba_mod_tpu.fl.client import STEP_CHUNK, active_steps, split_steps


C = tc.CFG["no_models"]


@pytest.fixture(scope="module")
def full_length():
    return tc.make_experiment(0, full_length=True)


@pytest.fixture(scope="module", params=[2, C + 1],
                ids=lambda w: f"wide_from_{w}")
def pair(request, full_length):
    """(the program's Experiment, the full-length loop's). C + 1 is what the
    engine's rule gives this model: that one is built with the rule."""
    exp = tc.make_experiment(
        0, wide_from=None if request.param == C + 1 else request.param)
    assert exp.engine.wide_from == request.param
    return exp, full_length


@pytest.mark.parametrize("case", tc.CASES)
def test_round_is_bit_equal_to_the_full_length_loop(pair, case):
    tc.check_round_is_bit_equal_to_the_full_length_loop(pair, case)


def test_train_phase_is_a_wide_while_then_a_width_1_job_loop(pair):
    tc.check_train_phase_is_a_wide_while_then_a_width_1_job_loop(pair)


def test_one_program_for_every_trip_count_and_the_host_counts_it(pair):
    tc.check_one_program_for_every_trip_count_and_the_host_counts_it(pair)


def test_plan_step_counts_by_hand():
    m = np.zeros((3, 2, 4, 5), bool)     # C=3, E=2, S=4, B=5
    m[0, :, :3, 0] = True                # lane 0: 3 steps in both epochs
    m[1, 0, :1, :2] = True               # lane 1: 1 step of epoch 0
    plan = {"steps_plan": 8, "steps_run": 6, "lane_steps_real": 7, "lanes": 3}
    # one full-width loop: six steps, in chunks of four
    assert plan_step_counts([m], 4, 1) == dict(
        plan, steps_wide=8, lane_steps_narrow=0)
    # from two lanes: they share step 0 alone, so the full-width loop runs
    # one chunk (lane 0's steps 1, 2 and 4 with it) and lane 0 two steps
    assert plan_step_counts([m], 4, 2) == dict(
        plan, steps_wide=4, lane_steps_narrow=2)
    assert plan_step_counts([m, np.zeros_like(m)], 4, 2) == dict(
        plan, steps_plan=16, steps_wide=4, lane_steps_narrow=2)
    # from three lanes (no step has three), and from C + 1: every real
    # lane-step is a job's
    for wide_from in (3, 4):
        assert plan_step_counts([m], 4, wide_from) == dict(
            plan, steps_wide=0, lane_steps_narrow=7)
    # one lane is the full-width loop alone, whatever the engine's value
    assert plan_step_counts([m[:1]], 4, 4) == dict(
        plan, lane_steps_real=6, lanes=1, steps_wide=8, lane_steps_narrow=0)
    order, n_chunks = active_steps(jnp.asarray(m))
    assert int(n_chunks) == 2
    assert list(np.asarray(order)) == [0, 1, 2, 4, 5, 6, 3, 7]
    split = split_steps(jnp.asarray(m), 2)
    assert (int(split.n_wide), int(split.n_jobs)) == (1, 1)
    assert list(np.asarray(split.n_tail)) == [2, 0, 0]
    assert int(split.job_lanes[0]) == 0
    assert list(np.asarray(split.lane_order[0, :2])) == [5, 6]
    split = split_steps(jnp.asarray(m), 4)
    assert (int(split.n_wide), int(split.n_jobs)) == (0, 2)
    assert list(np.asarray(split.n_tail)) == [6, 1, 0]
    assert list(np.asarray(split.job_lanes[:2])) == [0, 1]
    assert list(np.asarray(split.lane_order[0, :6])) == [0, 1, 2, 4, 5, 6]


def _lanes_mask(steps_by_lane, E=4, S=5):
    """[C, E, S, 1] from, a lane, the steps it holds in each of its epochs."""
    m = np.zeros((len(steps_by_lane), E, S, 1), bool)
    for c, per_epoch in enumerate(steps_by_lane):
        for e, n in enumerate(per_epoch):
            m[c, e, :n] = True
    return m


@pytest.mark.parametrize("name, wide_from, steps_by_lane, want", [
    # (steps_wide, lane_steps_narrow, the jobs as {lane: its step ids})
    ("all_masked", 2, [[], []], (0, 0, {})),
    ("equal_split", 2, [[2, 2], [2, 2], [2, 2]], (4, 0, {})),
    ("one_lane_of_two", 2, [[3, 3, 3], []],
     (0, 9, {0: [0, 1, 2, 5, 6, 7, 10, 11, 12]})),
    # positions 0-4 are shared (ids 0, 1, 5, 6, 10): the boundary rounds up
    # to 8 and takes lane 0's ids 11, 12, 13 with it; 14 is left
    ("rounding_takes_tail_steps", 2, [[2, 2, 5], [2, 2, 1]],
     (8, 1, {0: [14]})),
    # five shared positions and nothing after them: the last chunk runs
    # three positions past the steps that run
    ("rounding_past_the_end", 2, [[1, 1, 1], [1, 1, 1], [2, 2]], (8, 0, {})),
    # lane 1 outlasts by steps of the last shared epoch, lane 0 by epochs
    ("two_jobs", 2, [[1, 1, 1], [1, 5]], (4, 3, {0: [10], 1: [8, 9]})),
    # a tail whose steps two lanes hold in turn, never together
    ("interleaved", 2, [[1, 0, 4, 0], [1, 4, 0, 4]],
     (4, 9, {0: [10, 11, 12, 13], 1: [8, 15, 16, 17, 18]})),
    # C + 1: the full-width loop runs nothing, every lane is one job of all
    # its steps in their order, the steps all three share included
    ("every_lane_a_job", 4, [[2, 2], [2, 2], [2, 2]],
     (0, 12, {0: [0, 1, 5, 6], 1: [0, 1, 5, 6], 2: [0, 1, 5, 6]})),
    ("a_lane_without_data_is_no_job", 4, [[3, 3, 3], [], [1]],
     (0, 10, {0: [0, 1, 2, 5, 6, 7, 10, 11, 12], 2: [0]})),
    ("all_masked_no_job", 3, [[], []], (0, 0, {})),
    # in between, from three lanes: only position 0 (id 0) holds three, so
    # one chunk (ids 0, 1, 5, 6) runs at full width, and what two lanes
    # share after it (id 10) runs in both their jobs
    ("from_three_lanes", 3, [[2, 2, 5], [2, 2, 1], [1]],
     (4, 6, {0: [10, 11, 12, 13, 14], 1: [10]})),
])
def test_split_rule_by_hand(name, wide_from, steps_by_lane, want):
    """fl/client.py::split_steps (what the program reads) against
    data/batching.py::plan_step_counts (what the host counts) and against
    the boundary and the jobs worked out by hand; and every real lane-step
    runs exactly once, in its lane's own order."""
    m = _lanes_mask(steps_by_lane)
    C = m.shape[0]
    split = jax.tree_util.tree_map(
        np.asarray, split_steps(jnp.asarray(m), wide_from))
    counts = plan_step_counts([m], STEP_CHUNK, wide_from)
    n_wide = int(split.n_wide) * STEP_CHUNK
    jobs = {int(c): list(split.lane_order[c, :split.n_tail[c]])
            for c in split.job_lanes[:split.n_jobs]}
    assert (n_wide, int(split.n_tail.sum()), jobs) == want
    assert (counts["steps_wide"], counts["lane_steps_narrow"]) == want[:2]
    assert sorted(jobs) == list(np.flatnonzero(split.n_tail))
    real = m.any(axis=-1).reshape(C, -1)
    ran = np.zeros(real.shape, int)
    wide_ids = split.order[:min(n_wide, real.shape[1])]
    ran[:, wide_ids] += real[:, wide_ids]
    for c, ids in jobs.items():
        assert ids == sorted(ids)
        ran[c, ids] += 1
    np.testing.assert_array_equal(ran, real)
    if wide_from > C:
        assert counts["lane_steps_narrow"] == counts["lane_steps_real"]
    # without the job loop the full-width loop runs every step that runs
    flat = plan_step_counts([m], STEP_CHUNK, 1)
    assert flat["lane_steps_narrow"] == 0
    assert flat["steps_wide"] == -(-flat["steps_run"] // STEP_CHUNK) * STEP_CHUNK


@pytest.mark.parametrize("workload, want", [
    ("mnist", "every_lane"), ("cifar", "every_lane"),
    ("tiny-imagenet-200", "every_lane"), ("loan", "one_tail")])
def test_engine_sets_wide_from_from_the_model_and_the_mesh(
        narrow_resnets, workload, want):
    """fl/rounds.py::wide_from_of, the rule PERF.md section 7's table
    (PR 31) decided: a model with a convolution runs every lane as a job
    (`lanes + 1`), the dense LOAN model only one lane's tail (2), and a
    sharded clients axis keeps the full-width loop alone whatever the
    model. From the parameter shapes alone: the narrow ResNets are
    convolutional as the full-width ones are."""
    model_def = models.build_model(
        Params.from_dict(dict(tc.CFG, type=workload)))
    for lanes in (4, 10):
        assert rounds_mod.wide_from_of(model_def, None, lanes) == (
            lanes + 1 if want == "every_lane" else 2)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("clients",))
    assert rounds_mod.wide_from_of(model_def, mesh, 10) == 1


@pytest.mark.parametrize("records", ["counted", "uncounted", "bare", "none"])
@pytest.mark.parametrize("name", ["train_narrow_steps_pct",
                                  "train_slot_fill_pct"])
def test_two_loop_readers(name, records):
    """chipbench/metrics/train_narrow_steps_pct.py and train_slot_fill_pct.py,
    found by name as the harness finds them: sums over the window's rounds;
    nothing (not zero, no exception) from a program whose plan spans do not
    count the two loops."""
    import json
    from chipbench import run as harness
    from chipbench import selfcheck_steps as sc
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    (m, mod), = [(m, mod) for m, mod in harness.load_readers(
        bench, "tiny_dba_attack") if m["name"] == name]
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"],
                                                m["moves"])
    if records == "counted":
        # a check round of set-up (not read), then window rounds 1-3 of the
        # cell: two clean rounds and the one with adversary 0's 224-step tail
        made = [sc.Span("round/plan", i, i + 1, None, i, {
            "steps_plan": 370, "steps_run": run, "lane_steps_real": real,
            "lanes": 10, "steps_wide": wide, "lane_steps_narrow": narrow})
            for i, (run, real, wide, narrow) in enumerate(
                ((1, 10, 4, 0), (48, 330, 44, 5), (56, 368, 56, 0),
                 (280, 534, 56, 224)))]
        want = {"train_narrow_steps_pct": 100 * 229 / 1232,
                "train_slot_fill_pct": 100 * 1232 / (1560 + 229)}[name]
        assert mod.read(sc.context(made, 3)) == pytest.approx(want)
    elif records == "uncounted":   # the parent's records: one loop's counts
        assert mod.read(sc.context(sc.synthetic_records(), 3)) is None
    elif records == "bare":
        bare = [sc.BareSpan(*r[:5]) for r in sc.synthetic_records()]
        assert mod.read(sc.context(bare, 3)) is None
    else:
        assert mod.read(sc.context(None, 0)) is None
        assert mod.read(sc.context([], 3)) is None
