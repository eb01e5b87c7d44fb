"""The local battery runs its three poison parts only for the rows the
recorder writes (fl/rounds.py::make_local_battery: a list of single-model
jobs read from the round's tasks, one `while` whose trip count is the number
of jobs), and every recorded row is what the four-pass battery recorded, to
the byte.

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


def make_four_pass_battery(model_def, data, plans, is_poison_run, baseline):
    """The local battery before the job list: every part over all C models.
    Takes `tasks` for each lane's trigger and ignores every flag."""
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
    cfg, cases = RUNS[request.param]
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
                   for ev in case["batteries"] for part in ev)
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
        assert battery_eval_counts(seen[case]["tasks"], True, False, False) \
            == {"battery_evals_run": lanes + jobs,
                "battery_evals_plan": 4 * lanes}
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
    # battery's job loop, all on scalars: `i < n`
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
    assert battery_eval_counts(tasks_list, True, False, True) == {
        "battery_evals_run": 10 + (1 + 1 + 3) + (2 + 4 + 3),
        "battery_evals_plan": 40}
    assert battery_eval_counts(tasks_list, False, False, False) == {
        "battery_evals_run": 10, "battery_evals_plan": 10}


@pytest.mark.parametrize("records", ["counted", "uncounted", "bare", "none"])
def test_evals_run_reader(records):
    """chipbench/metrics/local_battery_evals_run_pct.py, found by name as the
    harness finds it: sums over the window's rounds; nothing (not zero, no
    exception) from a program whose plan spans carry no battery counts."""
    import json
    from chipbench import run as harness
    from chipbench import selfcheck_steps as sc
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    (m, mod), = [(m, mod) for m, mod in harness.load_readers(
        bench, "tiny_dba_attack") if m["name"] == "local_battery_evals_run_pct"]
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"],
                                                m["moves"])
    if records == "counted":
        # a check round of set-up (not read), then window rounds 1-3 of the
        # cell: two clean rounds and one with one adversary
        made = [sc.Span("round/plan", i, i + 1, None, i, {
            "steps_plan": 370, "steps_run": 48, "lane_steps_real": 320,
            "lanes": 10, "battery_evals_run": run, "battery_evals_plan": 40})
            for i, run in enumerate((40, 10, 10, 13))]
        assert mod.read(sc.context(made, 3)) == pytest.approx(27.5)
    elif records == "uncounted":   # the parent's records: step counts only
        assert mod.read(sc.context(sc.synthetic_records(), 3)) is None
    elif records == "bare":
        bare = [sc.BareSpan(*r[:5]) for r in sc.synthetic_records()]
        assert mod.read(sc.context(bare, 3)) is None
    else:
        assert mod.read(sc.context(None, 0)) is None
        assert mod.read(sc.context([], 3)) is None
