"""The local battery runs its three poison parts only for the rows the
recorder writes (fl/rounds.py::make_local_battery: a list of single-model
jobs read from the round's tasks, one `while` whose trip count is the number
of jobs), and every recorded row is what the four-pass battery recorded, to
the byte. Where `fl/rounds.py::lanes_as_jobs` says so (an unsharded model
with a convolution: the LeNet of every run here but `mesh`) the clean part is
single-model jobs too, a loop of static length over the lanes, and the
reference below, which keeps the stacked clean part, proves those rows too.

The reference kept here (`make_four_pass_battery`) is the battery as it was
before: the clean part and then three passes over all C stacked models (poison
on the pre-scaling model, poison on the submitted model, each lane's own
trigger), whatever the round's tasks say. An engine built with it in place of
`make_local_battery` runs the same rounds into a second folder, and the two
folders' CSV / JSONL files are compared as bytes (wall-clock columns
dropped).

A run's rounds are the cases; who takes part in a round is set on the
experiment (a fixed name list), who poisons by the schedule:

- clean_round: no listed adversary takes part: zero jobs;
- one_adversary / two_adversaries: poisoning lanes get pre, post, trigger;
- adversary_as_benign: a listed adversary off its schedule gets its trigger
  row only;
- baseline: no pre row;
- interval2_first_segment: `aggr_epoch_interval: 2`, poisoning in the first
  segment only: the intermediate battery gates on its own segment, the
  round-final one on any segment;
- forensics: every real lane's post row is computed (the forensic record
  reads its accuracy);
- mesh_padding_forensics: `no_models: 6` on the 8-virtual-device `clients`
  mesh, forensics on: the two padding lanes get nothing.
"""
import csv
import io

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dba_mod_tpu.fl.rounds as rounds_mod
from dba_mod_tpu.config import Params
from dba_mod_tpu.fl.evaluation import (EvalResult, battery_eval_counts,
                                       job_order, local_battery_jobs)
from dba_mod_tpu.fl.experiment import Experiment
from dba_mod_tpu.fl.rounds import LocalEvals
from dba_mod_tpu.models import ModelVars
from dba_mod_tpu.ops.losses import cross_entropy_sum
from dba_mod_tpu.utils import telemetry as tel
from dba_mod_tpu.utils.recorder import canonical_run_outputs

CFG = dict(
    type="mnist", lr=0.1, batch_size=16, epochs=8, no_models=4,
    number_of_total_participants=8, eta=0.8, aggregation_methods="mean",
    internal_epochs=1, internal_poison_epochs=2, is_poison=True,
    synthetic_data=True, synthetic_train_size=320, synthetic_test_size=100,
    momentum=0.9, decay=0.0005, sampling_dirichlet=False, local_eval=True,
    poison_label_swap=2, poisoning_per_batch=8, poison_lr=0.05,
    scale_weights_poison=4.0, adversary_list=[0, 1], trigger_num=2,
    alpha_loss=1.0, random_seed=1, is_random_namelist=False,
    participants_namelist=[0, 1, 2, 3],
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2], [0, 3]],
       "1_poison_pattern": [[3, 0], [3, 1], [3, 2], [3, 3]],
       "0_poison_epochs": [2, 3, 4], "1_poison_epochs": [3]})

# run -> (config, [(case, round's first epoch, who takes part, jobs)])
RUNS = {
    "attack": (CFG, [
        ("clean_round", 1, [2, 3, 4, 5], 0),
        ("one_adversary", 2, [0, 2, 3, 4], 3),
        ("two_adversaries", 3, [0, 1, 2, 3], 6),
        ("adversary_as_benign", 4, [0, 1, 2, 3], 3 + 1)]),
    "baseline": (dict(CFG, baseline=True), [
        ("baseline", 2, [0, 2, 3, 4], 2)]),
    "interval2": (dict(CFG, aggr_epoch_interval=2,
                       **{"0_poison_epochs": [1]}), [
        ("interval2_first_segment", 1, [0, 2, 3, 4], 3 + 3)]),
    "forensics": (dict(CFG, forensics=True), [
        ("forensics", 4, [0, 1, 2, 3], 1 + 4 + 2)]),
    "mesh": (dict(CFG, forensics=True, no_models=6, num_devices=8,
                  participants_namelist=[0, 1, 2, 3, 4, 5]), [
        ("mesh_padding_forensics", 4, [0, 1, 2, 3, 4, 5], 1 + 6 + 2)]),
}
CASES = [(run, *case) for run, (_, cases) in RUNS.items() for case in cases]
# not an attack run: no poison file, so not one of CASES
PRETRAIN = {"pretrain": (dict(CFG, is_poison=False), [
    ("pretrain_clean", 1, [2, 3, 4, 5], 0)])}


def make_four_pass_stacked_eval_fn(model_def, data, poison,
                                   per_client_trigger=False):
    """`make_stacked_eval_fn` before the job list: the poisoned tests of all
    C stacked models in one scan, the stamp shared (combined trigger) or
    under the vmap (`per_client_trigger`: `adv` is a [C] array)."""

    def evaluate_stacked(stacked_vars: ModelVars, idx, slots, mask,
                         adv) -> EvalResult:
        def body(carry, inp):
            loss_sum, correct, count = carry
            bidx, bslot, bmask = inp
            x, y = data.fetch_test(bslot, bidx)
            if poison and not per_client_trigger:
                x, y, _ = data.stamp(x, y, adv, 0, poison_all=True)
            bmaskf = bmask.astype(jnp.float32)

            def per_model(mv: ModelVars, adv_c):
                if poison and per_client_trigger:
                    xx, yy, _ = data.stamp(x, y, adv_c, 0, poison_all=True)
                else:
                    xx, yy = x, y
                logits, _ = model_def.apply(mv, xx, train=False)
                loss = cross_entropy_sum(logits, yy, bmask)
                preds = jnp.argmax(logits, axis=-1)
                return (loss, jnp.sum((preds == yy) * bmaskf),
                        jnp.sum(bmaskf))

            adv_vec = (adv if per_client_trigger else
                       jnp.zeros((loss_sum.shape[0],), jnp.int32))
            dl, dc, dn = jax.vmap(per_model)(stacked_vars, adv_vec)
            return (loss_sum + dl, correct + dc, count + dn), None

        C = jax.tree_util.tree_leaves(stacked_vars)[0].shape[0]
        zeros = jnp.zeros((C,), jnp.float32)
        (loss_sum, correct, count), _ = jax.lax.scan(
            body, (zeros, zeros, zeros), (idx, slots, mask))
        safe = jnp.maximum(count, 1.0)
        return EvalResult(loss=loss_sum / safe, acc=100.0 * correct / safe,
                          correct=correct, count=count)

    return evaluate_stacked


def make_four_pass_battery(model_def, data, plans, is_poison_run, baseline,
                           clean_jobs=False):
    """The local battery before the job list: every part over all C models,
    the clean one too whatever `clean_jobs` says. Takes `tasks` for each
    lane's trigger and ignores every flag."""
    eval_clean_s = make_four_pass_stacked_eval_fn(model_def, data, False)
    eval_poison_s = make_four_pass_stacked_eval_fn(model_def, data, True)
    eval_agent_s = make_four_pass_stacked_eval_fn(model_def, data, True,
                                                  per_client_trigger=True)

    def battery(unscaled, scaled, tasks, forensics) -> LocalEvals:
        clean = eval_clean_s(unscaled, plans.clean_idx, plans.clean_slots,
                             plans.clean_mask, jnp.int32(-1))
        if not is_poison_run:
            zero = EvalResult(*(jnp.zeros_like(clean.loss),) * 4)
            return LocalEvals(clean, zero, zero, zero)
        plan = (plans.poison_idx, plans.poison_slots, plans.poison_mask)
        return LocalEvals(clean,
                          eval_poison_s(unscaled, *plan, jnp.int32(-1)),
                          eval_poison_s(scaled, *plan, jnp.int32(-1)),
                          eval_agent_s(scaled, *plan, tasks.adv_slot[-1]))

    return battery


def _drive(exp, cases):
    """Run each case's round; per case the host's counts (the `round/plan`
    span) and the local rows the program returned."""
    seen = {}
    for case, epoch, names, _jobs in cases:
        exp.participants = list(names)
        n0 = len(tel.spans())
        fl = exp.dispatch_round(epoch)
        locals_, seg_locals = jax.device_get((fl.payload[0], fl.payload[8]))
        exp.finalize_round(fl)
        plan, = [r for r in tel.spans(n0) if r.name == "round/plan"]
        seen[case] = {"counts": dict(plan.counts), "tasks": fl.tasks_list,
                      "batteries": list(seg_locals or ()) + [locals_]}
    return seen


@pytest.fixture(scope="module")
def pair(request, tmp_path_factory):
    """One run's rounds through the program and through an engine with the
    four-pass battery: (the program's Experiment, what `_drive` saw of it,
    the two folders' recorded outputs). Parametrised by the tests, by the
    run's name."""
    cfg, cases = {**RUNS, **PRETRAIN}[request.param]
    tmp = tmp_path_factory.mktemp(f"battery_{request.param}")
    exp = Experiment(Params.from_dict(dict(cfg, run_dir=str(tmp / "jobs"))))
    mp = pytest.MonkeyPatch()
    mp.setattr(rounds_mod, "make_local_battery", make_four_pass_battery)
    try:
        ref = Experiment(Params.from_dict(dict(cfg,
                                               run_dir=str(tmp / "four"))))
    finally:
        mp.undo()
    assert (exp.mesh is not None) == bool(cfg.get("num_devices"))
    seen = _drive(exp, cases)
    for case in _drive(ref, cases).values():  # the reference skips nothing
        assert all(np.asarray(part.count).all()
                   for ev in case["batteries"]
                   for part in (ev if cfg["is_poison"] else ev[:1]))
    return exp, seen, _outputs(exp), _outputs(ref)


def _outputs(exp):
    out = canonical_run_outputs(exp.folder)
    for name in ("client_forensics.csv", "forensics.jsonl"):
        if (exp.folder / name).exists():
            out[name] = (exp.folder / name).read_bytes()
    return out


def _local_rows(outputs, name, epoch_col, epochs):
    rows = list(csv.reader(io.StringIO(outputs[name].decode())))[1:]
    return [r for r in rows if r[0] != "global"
            and int(r[epoch_col]) in epochs]


@pytest.mark.parametrize("pair,case,epoch,names,jobs", CASES,
                         indirect=["pair"], ids=[c[1] for c in CASES])
def test_recorded_rows_are_the_four_pass_batterys(pair, case, epoch, names,
                                                  jobs):
    exp, seen, got, want = pair
    # every file the run wrote, byte for byte (forensics records included)
    assert set(got) == set(want) and "posiontest_result.csv" in got
    assert ("forensics.jsonl" in got) == bool(exp.engine.forensics)
    for name in want:
        assert got[name] == want[name], name
    # the jobs are the rows the recorder wrote for this round (forensics
    # adds the post rows of the lanes that did not poison) ...
    interval = int(exp.params["aggr_epoch_interval"])
    epochs = set(range(epoch, epoch + interval))
    written = (len(_local_rows(got, "posiontest_result.csv", 1, epochs))
               + len(_local_rows(got, "poisontriggertest_result.csv", 3,
                                 epochs)))
    tasks_list = seen[case]["tasks"]
    if exp.engine.forensics:
        poisoning = sum(int(t.poisoning_per_batch[c] > 0)
                        for t in tasks_list for c in range(len(names)))
        written += len(names) - poisoning
    assert written == jobs
    # ... counted on the host from the same tasks ...
    lanes = len(tasks_list[0].adv_slot)
    assert seen[case]["counts"]["battery_evals_run"] == interval * lanes + jobs
    assert seen[case]["counts"]["battery_evals_plan"] == 4 * interval * lanes
    assert (lanes > len(names)) == (exp.mesh is not None)
    # ... and what the program ran: a slot with no job holds zeros
    ran = sum(int(np.count_nonzero(np.asarray(part.count)))
              for ev in seen[case]["batteries"]
              for part in (ev.poison_pre, ev.poison_post, ev.agent_trigger))
    assert ran == jobs
    for ev in seen[case]["batteries"]:
        assert np.count_nonzero(np.asarray(ev.clean.count)) == lanes
        for part in (ev.poison_pre, ev.poison_post, ev.agent_trigger):
            idle = np.asarray(part.count) == 0
            assert not np.asarray(part.loss)[idle].any()
            assert not np.asarray(part.correct)[idle].any()


def _eqns(jaxpr, primitive):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, primitive)


@pytest.mark.parametrize("pair", ["attack"], indirect=True)
def test_one_program_runs_zero_jobs_or_the_jobs_the_host_counts(pair):
    """A clean round and the poisoned ones share one compiled round program;
    its job loop is one `while` on a scalar trip count, which is the host's
    count: 0 in the clean round."""
    exp, seen, _, _ = pair
    rf = exp.engine.round_fn
    assert rf._cache_size() + (
        exp.engine.round_fn_donated._cache_size()
        if exp.engine.round_fn_donated is not None else 0) == 1
    trip_counts = []
    for case, _epoch, _names, jobs in RUNS["attack"][1]:
        tasks = jax.tree_util.tree_map(lambda *ls: np.stack(ls),
                                       *seen[case]["tasks"])
        wanted = local_battery_jobs(
            jnp.asarray(tasks.poisoning_per_batch),
            jnp.asarray(tasks.adv_slot), jnp.asarray(tasks.num_epochs),
            baseline=False)
        order, n_jobs = job_order(jnp.stack(wanted).reshape(-1))
        assert int(n_jobs) == jobs
        lanes = tasks.adv_slot.shape[1]
        assert (seen[case]["counts"]["battery_evals_run"]
                == lanes + int(n_jobs))
        assert battery_eval_counts(seen[case]["tasks"], True, False, False,
                                   True) \
            == {"battery_evals_run": lanes + jobs,
                "battery_evals_plan": 4 * lanes,
                "battery_clean_evals": lanes, "battery_clean_jobs": lanes}
        # job id = part * C + lane, the wanted ones first and in order
        flat = np.stack([np.asarray(w) for w in wanted]).reshape(-1)
        np.testing.assert_array_equal(np.asarray(order)[:jobs],
                                      np.flatnonzero(flat))
        trip_counts.append(int(n_jobs))
    assert trip_counts[0] == 0 and len(set(trip_counts)) >= 3

    tasks_seq, idx_seq, mask_seq, ns, lane = exp.build_static_round_inputs(2)
    key = jax.random.key(0)
    jaxpr = jax.make_jaxpr(exp.engine.round_fn)(
        exp.global_vars, exp.fg_state, tasks_seq, idx_seq, mask_seq, lane,
        ns, key, key).jaxpr
    # the train phase's three `while`s (the full-width loop, its job loop
    # and a job's chunks: tests/test_client_step_trip_count.py), then the
    # battery's poison job loop, all on scalars: `i < n` (the clean jobs'
    # loop has a static length: no `while`)
    loops = list(_eqns(jaxpr, "while"))
    assert len(loops) == 4
    for loop in loops:
        cond = loop.params["cond_jaxpr"].jaxpr
        assert [e.primitive.name for e in cond.eqns] == ["lt"]
        lt, = cond.eqns
        assert all(v.aval.shape == () for v in lt.invars + lt.outvars)
    # inside a job: one scan of static length over the poison plan
    body = loops[3].params["body_jaxpr"].jaxpr
    scan, = _eqns(body, "scan")
    assert scan.params["length"] == exp.eval_plans.poison_idx.shape[0]
    C = idx_seq.shape[1]
    carried = [v.aval.shape for v in body.outvars]
    assert carried[0] == () and carried.count((3 * C,)) == 4


def _battery_jaxpr(exp, tasks_list):
    """The local battery's own program (the closure the round program
    calls) on a round's task rows as the round stacks them: (jaxpr, C)."""
    tasks_seq = jax.tree_util.tree_map(
        lambda *ls: jnp.asarray(np.stack(ls)), *tasks_list)
    lanes = tasks_seq.adv_slot.shape[1]
    deltas = jax.tree_util.tree_map(
        lambda l: jnp.zeros((lanes,) + l.shape, l.dtype), exp.global_vars)
    return jax.make_jaxpr(exp.engine.local_evals_fn.__wrapped__)(
        exp.global_vars, deltas, tasks_seq, deltas).jaxpr, lanes


def _stacked_clean_scans(jaxpr, lanes):
    """The scans of `make_stacked_eval_fn` in a program: over the clean
    plan, carrying three [C] sums."""
    return [e for e in _eqns(jaxpr, "scan")
            if e.params["num_carry"] == 3
            and [v.aval.shape for v in e.outvars[:3]] == [(lanes,)] * 3]


def _clean_job_loops(jaxpr, lanes, clean_steps):
    """The loops of static length C that walk the stacked models one at a
    time through the single-model clean test: a scan over the lanes around
    exactly one scan, over the clean plan, carrying three scalar sums."""
    found = []
    for e in _eqns(jaxpr, "scan"):
        inner = list(_eqns(e.params["jaxpr"].jaxpr, "scan"))
        if e.params["length"] == lanes and len(inner) == 1:
            test, = inner
            assert test.params["length"] == clean_steps
            assert [v.aval.shape for v in test.outvars[:3]] == [()] * 3
            found.append(e)
    return found


@pytest.mark.parametrize("pair", ["attack", "mesh"], indirect=True)
def test_one_program_runs_the_clean_jobs_the_host_counts(pair):
    """The clean test of every lane: on one device (a model with a
    convolution) as many single-model jobs as the host counts, a loop of
    static length, and no stacked scan in the program; on the mesh the
    stacked scan and no clean job."""
    exp, seen, _, _ = pair
    jobs_form = exp.mesh is None
    assert exp.engine.clean_jobs == jobs_form
    assert exp.engine.clean_jobs == rounds_mod.lanes_as_jobs(exp.model_def,
                                                             exp.mesh)
    for case in seen.values():
        lanes = len(case["tasks"][0].adv_slot)
        counts = case["counts"]
        assert counts["battery_clean_evals"] == len(case["tasks"]) * lanes
        assert counts["battery_clean_jobs"] == (
            counts["battery_clean_evals"] if jobs_form else 0)
        # every lane's clean row is of the whole test set, either form
        for ev in case["batteries"]:
            np.testing.assert_array_equal(
                np.asarray(ev.clean.count),
                float(exp.params["synthetic_test_size"]))
    # the round-final battery's program, on the last case's tasks
    jaxpr, lanes = _battery_jaxpr(exp, list(seen.values())[-1]["tasks"][-1:])
    clean_steps = exp.eval_plans.clean_idx.shape[0]
    assert len(_stacked_clean_scans(jaxpr, lanes)) == (0 if jobs_form else 1)
    assert len(_clean_job_loops(jaxpr, lanes, clean_steps)) == (
        1 if jobs_form else 0)
    # the poison parts' job loop is as it was: three parts' rows
    loop, = _eqns(jaxpr, "while")
    body = loop.params["body_jaxpr"].jaxpr
    assert [v.aval.shape for v in body.outvars].count((3 * lanes,)) == 4


@pytest.mark.parametrize("pair", ["pretrain"], indirect=True)
def test_pretraining_run_is_the_stacked_batterys(pair):
    """`is_poison: false`: the clean part alone, C single-model jobs and no
    poison job loop, records what the stacked battery records, to the
    byte."""
    exp, seen, got, want = pair
    assert exp.engine.clean_jobs and not exp.is_poison_run
    assert set(got) == set(want) and "test_result.csv" in got
    for name in want:
        assert got[name] == want[name], name
    (case, epoch, names, _), = PRETRAIN["pretrain"][1]
    lanes = len(names)
    assert len(_local_rows(got, "test_result.csv", 1, {epoch})) == lanes
    assert seen[case]["counts"] == {
        **seen[case]["counts"], "battery_evals_run": lanes,
        "battery_evals_plan": lanes, "battery_clean_evals": lanes,
        "battery_clean_jobs": lanes}
    ev, = seen[case]["batteries"]
    assert np.count_nonzero(np.asarray(ev.clean.count)) == lanes
    for part in (ev.poison_pre, ev.poison_post, ev.agent_trigger):
        assert not any(np.asarray(leaf).any() for leaf in part)
    jaxpr, _ = _battery_jaxpr(exp, seen[case]["tasks"])
    assert not _stacked_clean_scans(jaxpr, lanes)
    assert len(_clean_job_loops(jaxpr, lanes,
                                exp.eval_plans.clean_idx.shape[0])) == 1
    assert not list(_eqns(jaxpr, "while"))


@pytest.mark.parametrize("longer", ["clean", "poison"])
def test_clean_jobs_over_plans_of_unequal_length(longer):
    """The clean jobs walk the clean plan and the poison jobs the poison
    plan, whichever is the longer: every row, clean and poisoned, is to the
    bit what the battery with the stacked clean part gives (`clean_jobs`
    off: the form a dense model and the mesh keep)."""
    from dba_mod_tpu.data import build_eval_plan, load_image_dataset
    from dba_mod_tpu.fl.experiment import poison_test_indices
    from dba_mod_tpu.fl.device_data import make_image_device_data
    from dba_mod_tpu.fl.state import ClientTask
    from dba_mod_tpu.models import build_model
    params = Params.from_dict(CFG)
    data = load_image_dataset(params)
    dd = make_image_device_data(data, params)
    mdef = build_model(params)
    C = 3
    u, s_ = (jax.vmap(mdef.init_vars)(jax.random.split(jax.random.key(k), C))
             for k in (0, 1))
    # 100 test images in batches of 16: 7 steps, the last masked to 4; the
    # poison plan drops the target label's images: 6 steps
    kept = poison_test_indices(data.test_labels, CFG["poison_label_swap"])
    clean_ids = np.arange(100 if longer == "clean" else 40)
    plans = []
    for ids in (clean_ids, kept):
        plan = build_eval_plan(ids, 16)
        idx = jnp.asarray(plan.idx)
        plans += [idx, jnp.zeros_like(idx), jnp.asarray(plan.mask)]
    plans = rounds_mod.EvalPlans(*plans)
    steps = plans.clean_idx.shape[0], plans.poison_idx.shape[0]
    assert (steps[0] > steps[1]) == (longer == "clean") and steps[0] != steps[1]
    tasks = ClientTask(*(None,) * 3, jnp.array([[0, -1, 1]]),
                       jnp.array([[8, 0, 8]]), *(None,) * 3,
                       jnp.array([[2, 1, 2]]))
    got, want = (jax.jit(lambda *a, cj=cj: rounds_mod.make_local_battery(
        mdef, dd, plans, True, False, cj)(*a, False))(u, s_, tasks)
        for cj in (True, False))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(got.clean.count), len(clean_ids))
    np.testing.assert_array_equal(np.asarray(got.poison_pre.count),
                                  [len(kept), 0, len(kept)])


@pytest.mark.parametrize("workload, jobs", [
    ("mnist", True), ("cifar", True), ("tiny-imagenet-200", True),
    ("loan", False)])
def test_lanes_as_jobs_by_hand(narrow_resnets, monkeypatch, workload, jobs):
    """fl/rounds.py::lanes_as_jobs, the one rule the client step and the
    local battery's clean part share: a model with a convolution on one
    device runs lane by lane, the dense LOAN model stacked, anything on a
    sharded clients axis stacked; `wide_from_of` reads it, not a second
    test of the shapes."""
    from dba_mod_tpu import models
    model_def = models.build_model(
        Params.from_dict(dict(CFG, type=workload)))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("clients",))
    assert rounds_mod.lanes_as_jobs(model_def, None) == jobs
    assert not rounds_mod.lanes_as_jobs(model_def, mesh)
    assert rounds_mod.wide_from_of(model_def, None, 10) == (11 if jobs else 2)
    assert rounds_mod.wide_from_of(model_def, mesh, 10) == 1
    monkeypatch.setattr(rounds_mod, "lanes_as_jobs", lambda *_: not jobs)
    assert rounds_mod.wide_from_of(model_def, None, 10) == (2 if jobs else 11)


def test_local_battery_jobs_by_hand():
    ppb = np.array([[0, 8, 0, 0, 0], [0, 0, 0, 8, 0]])    # I=2, C=5
    adv = np.array([[-1, 0, 1, 2, -1]] * 2)
    eps = np.array([[1, 2, 1, 1, 0], [1, 1, 1, 2, 0]])    # lane 4: padding
    t = lambda *rows: [np.array(r, bool) for r in rows]
    jobs = lambda **kw: [np.asarray(j) for j in local_battery_jobs(
        ppb, adv, eps, **{"baseline": False, **kw})]
    np.testing.assert_equal(jobs(), t([0, 1, 0, 1, 0], [0, 1, 0, 1, 0],
                                      [0, 1, 1, 1, 0]))
    np.testing.assert_equal(jobs(baseline=True),
                            t([0] * 5, [0, 1, 0, 1, 0], [0, 1, 1, 1, 0]))
    np.testing.assert_equal(jobs(forensics=True),
                            t([0, 1, 0, 1, 0], [1, 1, 1, 1, 0],
                              [0, 1, 1, 1, 0]))
    # the first segment alone, as its own battery reads it
    np.testing.assert_equal(
        [np.asarray(j) for j in local_battery_jobs(ppb[:1], adv[:1], eps[:1],
                                                   False)],
        t([0, 1, 0, 0, 0], [0, 1, 0, 0, 0], [0, 1, 1, 1, 0]))
    # the host's count of the same round: both batteries, forensics on the
    # last alone; the clean part only outside an attack run
    from dba_mod_tpu.fl.state import ClientTask
    tasks_list = [ClientTask(*(None,) * 3, adv[s], ppb[s], *(None,) * 3,
                             eps[s]) for s in range(2)]
    assert battery_eval_counts(tasks_list, True, False, True, True) == {
        "battery_evals_run": 10 + (1 + 1 + 3) + (2 + 4 + 3),
        "battery_evals_plan": 40, "battery_clean_evals": 10,
        "battery_clean_jobs": 10}
    assert battery_eval_counts(tasks_list, False, False, False, False) == {
        "battery_evals_run": 10, "battery_evals_plan": 10,
        "battery_clean_evals": 10, "battery_clean_jobs": 0}


def _reader(name):
    """chipbench/metrics/<name>.py and its `per_layer` entry, found by name
    as the harness finds them."""
    import json
    from chipbench import run as harness
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    (m, mod), = [(m, mod) for m, mod in harness.load_readers(
        bench, "tiny_dba_attack") if m["name"] == name]
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"],
                                                m["moves"])
    assert m["workloads"] == ["tiny_dba_attack"]
    return mod


def _plan_span(i, **battery):
    from chipbench import selfcheck_steps as sc
    return sc.Span("round/plan", i, i + 1, None, i, {
        "steps_plan": 370, "steps_run": 48, "lane_steps_real": 320,
        "lanes": 10, **battery})


def _reads_nothing(mod, records):
    """Nothing (not zero, no exception) from records without the counts:
    the parent's (step counts only), bare ones, none at all."""
    from chipbench import selfcheck_steps as sc
    if records == "uncounted":
        assert mod.read(sc.context(sc.synthetic_records(), 3)) is None
    elif records == "bare":
        bare = [sc.BareSpan(*r[:5]) for r in sc.synthetic_records()]
        assert mod.read(sc.context(bare, 3)) is None
    else:
        assert mod.read(sc.context(None, 0)) is None
        assert mod.read(sc.context([], 3)) is None


@pytest.mark.parametrize("records", ["counted", "uncounted", "bare", "none"])
def test_evals_run_reader(records):
    """chipbench/metrics/local_battery_evals_run_pct.py: sums over the
    window's rounds; nothing from a program whose plan spans carry no
    battery counts."""
    from chipbench import selfcheck_steps as sc
    mod = _reader("local_battery_evals_run_pct")
    if records != "counted":
        return _reads_nothing(mod, records)
    # a check round of set-up (not read), then window rounds 1-3 of the
    # cell: two clean rounds and one with one adversary
    made = [_plan_span(i, battery_evals_run=run, battery_evals_plan=40)
            for i, run in enumerate((40, 10, 10, 13))]
    assert mod.read(sc.context(made, 3)) == pytest.approx(27.5)


@pytest.mark.parametrize("records", ["counted", "uncounted", "bare", "none"])
def test_clean_jobs_reader(records):
    """chipbench/metrics/local_battery_clean_jobs_pct.py: the clean tests
    that ran as jobs over the clean tests run, summed over the window's
    rounds; nothing from a program whose plan spans do not count them."""
    from chipbench import selfcheck_steps as sc
    mod = _reader("local_battery_clean_jobs_pct")
    old = dict(battery_evals_run=10, battery_evals_plan=40)
    if records != "counted":
        # PR 27's records too: battery counts, none of the clean part
        assert mod.read(sc.context([_plan_span(i, **old) for i in range(4)],
                                   3)) is None
        return _reads_nothing(mod, records)
    # a check round of set-up (not read), then three window rounds: all ten
    # clean tests as jobs; a stacked engine's; an interval-2 round of which
    # (no engine does this) one battery's ran as jobs
    made = [_plan_span(i, **old, battery_clean_evals=evals,
                       battery_clean_jobs=jobs)
            for i, (evals, jobs) in enumerate(
                ((10, 0), (10, 10), (10, 0), (20, 10)))]
    assert mod.read(sc.context(made, 3)) == pytest.approx(50.0)
    assert mod.read(sc.context(made[:2], 1)) == 100.0
    assert mod.read(sc.context(made[:3], 1)) == 0.0
