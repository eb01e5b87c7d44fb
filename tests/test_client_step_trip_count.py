"""The client step's loop runs only the steps some lane needs (fl/client.py:
`active_steps` -> a `while` over chunks of STEP_CHUNK steps), and what it
computes is what the full-length loop computed, to the bit.

The reference kept here (`make_full_length_client_step`) is the loop as it
was before: one `lax.scan` over all E x S plan steps, masked steps included.
An engine built with it in place of `make_client_step` is driven on the same
feeds as the program's own, on one device and on the 8-virtual-device
`clients` mesh:

- heavy_tail: a Dirichlet population's round with one 10-epoch adversary
  beside 2-epoch benign lanes (the shape of the paper's attack round);
- all_full: every lane real at every step (the loop runs what it ran; E*S
  = 30 is no multiple of the chunk, so the last chunk reaches past the plan);
- empty_client: one lane with no batch at all;
- check_k1 / check_k3: the benchmark's output-check feed
  (chipbench/program.py::check_round): only the first 1 or 3 steps of epoch 0.
"""
from typing import Any

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dba_mod_tpu.fl.rounds as rounds_mod
from dba_mod_tpu.config import Params
from dba_mod_tpu.data.batching import plan_step_counts
from dba_mod_tpu.fl.client import (STEP_CHUNK, ClientMetrics, SegmentResult,
                                   _select_tree, active_steps)
from dba_mod_tpu.fl.experiment import Experiment
from dba_mod_tpu.models import ModelVars
from dba_mod_tpu.ops.fused_update import make_fused_step_update
from dba_mod_tpu.ops.losses import cross_entropy, tree_dist_norm
from dba_mod_tpu.ops.sgd import sgd_init
from dba_mod_tpu.utils import telemetry as tel

CFG = dict(
    type="mnist", lr=0.1, batch_size=8, epochs=4, no_models=8,
    number_of_total_participants=16, eta=0.8,
    aggregation_methods="foolsgold", internal_epochs=2,
    internal_poison_epochs=10, is_poison=True, synthetic_data=True,
    synthetic_train_size=96, synthetic_test_size=128, momentum=0.9,
    decay=0.0005, sampling_dirichlet=True, dirichlet_alpha=0.5,
    local_eval=False, poison_label_swap=2, poisoning_per_batch=4,
    poison_lr=0.05, scale_weights_poison=3.0, adversary_list=[9],
    trigger_num=1, alpha_loss=1.0, random_seed=1,
    vis_train_batch_loss=True, batch_track_distance=True,
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2], [0, 3]],
       "0_poison_epochs": [1, 2, 3]})
CASES = ("heavy_tail", "all_full", "empty_client", "check_k1", "check_k3")


def make_full_length_client_step(model_def, data, hyper, fg_enabled,
                                 fused_pallas=False, fused_interpret=False):
    """The steps loop before the trip count: `lax.scan` over every one of
    the E x S plan steps. Takes and ignores `order` and `n_chunks`."""
    fused_update = make_fused_step_update(
        hyper.momentum, hyper.weight_decay, fg_enabled,
        use_pallas=fused_pallas, interpret=fused_interpret)

    def client_step(start_vars: ModelVars, benign_mom: Any, task, idx, mask,
                    rng, order, n_chunks) -> SegmentResult:
        E, S, B = idx.shape
        params0, bn0 = start_vars.params, start_vars.batch_stats
        is_poison_seg = task.poisoning_per_batch > 0
        mom0 = _select_tree(is_poison_seg, sgd_init(params0), benign_mom)
        fg0 = jax.tree_util.tree_map(jnp.zeros_like, params0)
        zeros_e = jnp.zeros((E,), jnp.float32)
        metrics0 = ClientMetrics(zeros_e, zeros_e, zeros_e, zeros_e)

        def step(carry, inp):
            params, bn, mom, fg, m = carry
            step_i, bidx, bmask = inp
            e = step_i // S
            x, y = data.fetch_train(task.slot, bidx)
            x, y, sel = data.stamp(x, y, task.adv_index,
                                   task.poisoning_per_batch)
            step_rng = jax.random.fold_in(
                jax.random.fold_in(rng, e), step_i - e * S)

            def loss_fn(p):
                logits, new_bn = model_def.apply(
                    ModelVars(p, bn), x, train=True, dropout_rng=step_rng)
                ce = cross_entropy(logits, y, bmask)
                if hyper.alpha_loss == 1.0:
                    loss = ce
                else:
                    loss = (task.alpha * ce + (1.0 - task.alpha)
                            * tree_dist_norm(p, params0))
                return loss, (logits, new_bn)

            (loss, (logits, new_bn)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            valid = jnp.sum(bmask) > 0
            params, mom, fg, bn = fused_update(task.lr_row[e], valid, params,
                                               grads, mom, fg, new_bn, bn)
            preds = jnp.argmax(logits, axis=-1)
            bmaskf = bmask.astype(jnp.float32)
            vf = valid.astype(jnp.float32)
            m = ClientMetrics(
                loss_sum=m.loss_sum.at[e].add(vf * loss),
                correct=m.correct.at[e].add(
                    vf * jnp.sum((preds == y) * bmaskf)),
                count=m.count.at[e].add(vf * jnp.sum(bmaskf)),
                poison_count=m.poison_count.at[e].add(
                    vf * jnp.sum(sel * bmaskf)))
            ys = ((vf * loss, vf * tree_dist_norm(params, params0))
                  if hyper.track_batches else None)
            return (params, bn, mom, fg, m), ys

        xs = (jnp.arange(E * S), idx.reshape(E * S, B),
              mask.reshape(E * S, B))
        (params, bn, mom, fg, metrics), ys = jax.lax.scan(
            step, (params0, bn0, mom0, fg0, metrics0), xs)
        batch_loss, batch_dist = (ys if hyper.track_batches
                                  else (jnp.zeros((0,), jnp.float32),) * 2)
        end_vars = ModelVars(
            params=jax.tree_util.tree_map(
                lambda a, w: a + task.scale * (w - a), params0, params),
            batch_stats=jax.tree_util.tree_map(
                lambda a, w: a + task.scale * (w - a), bn0, bn))
        return SegmentResult(end_vars,
                             _select_tree(is_poison_seg, benign_mom, mom), fg,
                             metrics, batch_loss, batch_dist)

    return client_step


@pytest.fixture(scope="module", params=[0, 8], ids=["one_device", "mesh8"])
def pair(request):
    """(the program's Experiment, one whose engine runs the full-length
    loop), on one device or on the clients mesh."""
    cfg = dict(CFG, num_devices=request.param)
    exp = Experiment(Params.from_dict(cfg), save_results=False)
    mp = pytest.MonkeyPatch()
    mp.setattr(rounds_mod, "make_client_step", make_full_length_client_step)
    try:
        ref = Experiment(Params.from_dict(cfg), save_results=False)
    finally:
        mp.undo()
    assert (exp.mesh is not None) == bool(request.param)
    return exp, ref


def _feed(exp, case):
    """The round program's arguments for one case, from the experiment's own
    plan of its poisoned epoch 1 (same RNG streams on both engines: a fresh
    numpy/python RNG per call)."""
    import random
    exp.select_rng = random.Random(7)
    exp.plan_rng = np.random.RandomState(7)
    tasks_seq, idx_seq, mask_seq, ns, lane = exp.build_static_round_inputs(1)
    mask = np.array(mask_seq)
    if case == "all_full":
        mask[:] = True
    elif case == "empty_client":
        mask[:, 2] = False
    elif case.startswith("check_k"):
        mask[:, :, 1:] = False
        mask[:, :, 0, int(case[-1]):] = False
    mask_seq = jnp.asarray(mask)
    if exp.mesh is not None:
        from dba_mod_tpu.parallel.mesh import shard_round_inputs
        tasks_seq, idx_seq, mask_seq, ns = shard_round_inputs(
            exp.mesh, tasks_seq, idx_seq, mask_seq, ns)
    return tasks_seq, idx_seq, mask_seq, ns, lane, mask


def _assert_trees_bit_equal(got, want):
    got_l, tree_g = jax.tree_util.tree_flatten(got)
    want_l, tree_w = jax.tree_util.tree_flatten(want)
    assert tree_g == tree_w
    for g, w in zip(got_l, want_l):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("case", CASES)
def test_round_is_bit_equal_to_the_full_length_loop(pair, case):
    exp, ref = pair
    rng_t, rng_a = jax.random.split(jax.random.key(11))
    out = {}
    for name, e in (("exp", exp), ("ref", ref)):
        tasks_seq, idx_seq, mask_seq, ns, lane, mask = _feed(e, case)
        train = e.engine.train_fn(e.global_vars, tasks_seq, idx_seq,
                                  mask_seq, lane, rng_t)
        # the fused round program (what a cell runs) once a device layout;
        # the other cases reach the new state through the split path's
        # aggregate program, from the same train outputs
        if case == "heavy_tail":
            rest = e.engine.round_fn(
                e.global_vars, e.fg_state, tasks_seq, idx_seq, mask_seq,
                lane, ns, rng_t, rng_a)
        else:
            agg = e.engine.aggregate_fn(
                e.global_vars, e.fg_state, train.deltas, train.fg_grads,
                train.fg_feature, tasks_seq.participant_id[0], ns, rng_a,
                rounds_mod.nbt_client_deltas(mask_seq, tasks_seq.scale))
            rest = (agg.new_vars, agg.new_fg_state, agg.wv)
        out[name] = (train, rest)
    counts = plan_step_counts([mask[0]])
    if case == "all_full":
        assert counts["steps_run"] == counts["steps_plan"]
    elif case == "heavy_tail":
        # the adversary's 10 epochs against the benign lanes' 2
        assert counts["steps_run"] < counts["steps_plan"]
        assert counts["lane_steps_real"] < counts["steps_run"] * counts["lanes"]
    elif case.startswith("check_k"):
        assert counts["steps_run"] == int(case[-1])
    train, ref_train = out["exp"][0], out["ref"][0]
    # something was trained, and tracked per batch, in every case
    assert float(jnp.max(train.delta_norms)) > 0
    assert train.batch_loss.shape[-1] == counts["steps_plan"]
    assert float(jnp.sum(jnp.abs(train.batch_dist))) > 0
    assert float(sum(jnp.sum(jnp.abs(l)) for l in
                     jax.tree_util.tree_leaves(train.fg_grads))) > 0
    # deltas, FoolsGold sums and feature, ClientMetrics, delta norms,
    # batch_loss / batch_dist; then the new global state, FoolsGold memory
    # and (heavy_tail) the payload the host fetches
    _assert_trees_bit_equal(train, ref_train)
    _assert_trees_bit_equal(out["exp"][1], out["ref"][1])


def _eqns(jaxpr, primitive):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, primitive)


def test_train_phase_is_one_while_with_an_unbatched_predicate(pair):
    exp, _ = pair
    tasks_seq, idx_seq, mask_seq, ns, lane, _ = _feed(exp, "heavy_tail")
    jaxpr = jax.make_jaxpr(exp.engine.train_fn)(
        exp.global_vars, tasks_seq, idx_seq, mask_seq, lane,
        jax.random.key(0)).jaxpr
    loops = list(_eqns(jaxpr, "while"))
    assert len(loops) == 1
    cond = loops[0].params["cond_jaxpr"].jaxpr
    # `j < n_chunks` on scalars: a predicate some lane batched would read [C]
    # values and reduce them, and the body would select every carry by it
    assert [e.primitive.name for e in cond.eqns] == ["lt"]
    lt, = cond.eqns
    assert all(v.aval.shape == () for v in lt.invars + lt.outvars)
    C = idx_seq.shape[1]
    body = loops[0].params["body_jaxpr"].jaxpr
    carried = [v.aval.shape for v in body.outvars]
    assert carried[0] == () and any(s[:1] == (C,) for s in carried)
    # inside: one loop of static length, the chunk; no other loop anywhere
    chunk, = _eqns(body, "scan")
    assert chunk.params["length"] == STEP_CHUNK
    assert len(list(_eqns(jaxpr, "scan"))) == 1
    # and it is the only `while` of the whole round program (`local_eval`
    # is off here: the local battery's job loop is the other one,
    # tests/test_local_battery_jobs.py)
    key = jax.random.key(0)
    round_jaxpr = jax.make_jaxpr(exp.engine.round_fn)(
        exp.global_vars, exp.fg_state, tasks_seq, idx_seq, mask_seq, lane,
        ns, key, key).jaxpr
    assert len(list(_eqns(round_jaxpr, "while"))) == 1


def test_one_program_for_every_trip_count_and_the_host_counts_it(pair):
    """Rounds of different n_run share one compiled round program, and the
    host's `steps_run` (the `round/plan` span's counts), in chunks, is the
    trip count the program reads from the same mask."""
    exp, _ = pair
    rf = exp.engine.round_fn
    rng_t, rng_a = jax.random.split(jax.random.key(3))
    trip_counts = set()
    # (the mesh's steps are slow on virtual devices: two trip counts there)
    for case in CASES if exp.mesh is None else ("heavy_tail", "check_k1"):
        tasks_seq, idx_seq, mask_seq, ns, lane, mask = _feed(exp, case)
        jax.block_until_ready(rf(exp.global_vars, exp.fg_state, tasks_seq,
                                 idx_seq, mask_seq, lane, ns, rng_t, rng_a))
        order, n_chunks = active_steps(mask_seq[0])
        n_run = plan_step_counts([mask[0]])["steps_run"]
        assert int(n_chunks) == -(-n_run // STEP_CHUNK)
        active = np.flatnonzero(mask[0].any(axis=(0, 3)).reshape(-1))
        np.testing.assert_array_equal(np.asarray(order)[:n_run], active)
        trip_counts.add(int(n_chunks))
    assert len(trip_counts) >= 2 and 1 in trip_counts
    assert rf._cache_size() == 1

    n0 = len(tel.spans())
    fl = exp.dispatch_round(1)
    exp.finalize_round(fl)
    plan, = [r for r in tel.spans(n0) if r.name == "round/plan"]
    program_chunks = sum(int(active_steps(jnp.asarray(m))[1])
                         for m in fl.mask_list)
    assert -(-plan.counts["steps_run"] // STEP_CHUNK) == program_chunks
    steps = plan_step_counts(fl.mask_list)
    assert {k: plan.counts[k] for k in steps} == steps
    assert 0 < plan.counts["steps_run"] <= plan.counts["steps_plan"]
    assert (plan.counts["lane_steps_real"]
            <= plan.counts["steps_run"] * plan.counts["lanes"])
    assert rf._cache_size() + (
        exp.engine.round_fn_donated._cache_size()
        if exp.engine.round_fn_donated is not None else 0) == 1


def test_plan_step_counts_by_hand():
    m = np.zeros((3, 2, 4, 5), bool)     # C=3, E=2, S=4, B=5
    m[0, :, :3, 0] = True                # lane 0: 3 steps in both epochs
    m[1, 0, :1, :2] = True               # lane 1: 1 step of epoch 0
    assert plan_step_counts([m]) == {"steps_plan": 8, "steps_run": 6,
                                     "lane_steps_real": 7, "lanes": 3}
    assert plan_step_counts([m, np.zeros_like(m)]) == {
        "steps_plan": 16, "steps_run": 6, "lane_steps_real": 7, "lanes": 3}
    order, n_chunks = active_steps(jnp.asarray(m))
    assert int(n_chunks) == 2   # six steps, in chunks of four
    assert list(np.asarray(order)) == [0, 1, 2, 4, 5, 6, 3, 7]
