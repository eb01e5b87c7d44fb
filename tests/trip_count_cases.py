"""What tests/test_client_step_trip_count.py (one device, at `wide_from` 2
and C + 1) and tests/test_client_step_trip_count_mesh.py (the
8-virtual-device `clients` mesh) share: the full-length reference step, the
engines, the feeds and the three checks. Two files so that the scheduler
(`--dist loadfile`) can give each device layout a worker of its own; the
account of the cases is in the first file's docstring."""

from typing import Any

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dba_mod_tpu.fl.rounds as rounds_mod
from dba_mod_tpu.config import Params
from dba_mod_tpu.data.batching import plan_step_counts
from dba_mod_tpu.fl.client import (STEP_CHUNK, ClientMetrics, SegmentResult,
                                   _select_tree, active_steps, split_steps)
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
    internal_poison_epochs=6, is_poison=True, synthetic_data=True,
    synthetic_train_size=96, synthetic_test_size=128, momentum=0.9,
    decay=0.0005, sampling_dirichlet=True, dirichlet_alpha=0.5,
    local_eval=False, poison_label_swap=2, poisoning_per_batch=4,
    poison_lr=0.05, scale_weights_poison=3.0, adversary_list=[9],
    trigger_num=1, alpha_loss=1.0, random_seed=1,
    vis_train_batch_loss=True, batch_track_distance=True,
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2], [0, 3]],
       "0_poison_epochs": [1, 2, 3]})
CASES = ("heavy_tail", "all_full", "empty_client", "check_k1", "check_k3",
         "solo_lane", "two_tails", "two_jobs")


def make_full_length_client_step(model_def, data, hyper, fg_enabled,
                                 fused_pallas=False, fused_interpret=False,
                                 wide_from=2):
    """The steps loop before the trip counts: every lane through one
    `lax.scan` over every one of the E x S plan steps (`wide_from` is
    taken and ignored: there is one loop, at full width)."""
    fused_update = make_fused_step_update(
        hyper.momentum, hyper.weight_decay, fg_enabled,
        use_pallas=fused_pallas, interpret=fused_interpret)

    def client_step(start_vars: ModelVars, benign_mom: Any, task, idx, mask,
                    rng) -> SegmentResult:
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

    return jax.vmap(client_step)


def make_experiment(num_devices, wide_from=None, full_length=False, cfg=CFG):
    """The program's Experiment on one device (0) or on the clients mesh
    (8): as the engine's own rule builds it, or with `wide_from` in the
    rule's place; `full_length`: an engine that runs the full-length loop."""
    mp = pytest.MonkeyPatch()
    if wide_from is not None:
        mp.setattr(rounds_mod, "wide_from_of", lambda *_: wide_from)
    if full_length:
        mp.setattr(rounds_mod, "make_client_step",
                   make_full_length_client_step)
    try:
        exp = Experiment(Params.from_dict(dict(cfg, num_devices=num_devices)),
                         save_results=False)
    finally:
        mp.undo()
    assert (exp.mesh is not None) == bool(num_devices)
    return exp


def _feed(exp, case):
    """The round program's arguments for one case, from the experiment's own
    plan of its poisoned epoch 1 (same RNG streams on both engines: a fresh
    numpy/python RNG per call)."""
    import random
    exp.select_rng = random.Random(7)
    exp.plan_rng = np.random.RandomState(7)
    tasks_seq, idx_seq, mask_seq, ns, lane = exp.build_static_round_inputs(1)
    idx, mask = np.array(idx_seq), np.array(mask_seq)
    steps = mask[0].any(axis=-1).sum(axis=-1)      # [C, E] steps an epoch
    adv, wide = steps.sum(axis=1).argmax(), steps[:, 0].argmax()
    assert adv != wide and steps[adv, -1] < steps[wide, 0]
    if case == "all_full":
        mask[:] = True
    elif case == "empty_client":
        mask[:, 2] = False
    elif case.startswith("check_k"):
        mask[:, :, 1:] = False
        mask[:, :, 0, int(case[-1]):] = False
    elif case == "solo_lane":
        mask[:, np.arange(mask.shape[1]) != adv] = False
    elif case in ("two_tails", "two_jobs"):
        n_ep = mask.shape[2] if case == "two_tails" else 3
        idx[:, wide, :n_ep] = idx[:, wide, :1]
        mask[:, wide, :n_ep] = mask[:, wide, :1]
    idx_seq, mask_seq = jnp.asarray(idx), jnp.asarray(mask)
    if exp.mesh is not None:
        from dba_mod_tpu.parallel.mesh import shard_round_inputs
        tasks_seq, idx_seq, mask_seq, ns = shard_round_inputs(
            exp.mesh, tasks_seq, idx_seq, mask_seq, ns)
    return tasks_seq, idx_seq, mask_seq, ns, lane, mask


def _assert_trees_bit_equal(got, want, job_lanes=()):
    """Bit-equal, leaf for leaf — but for the one thing XLA:CPU computes
    otherwise at width 1: it sums a step's batch-mean loss in another order
    than under `vmap`, so the loss a job's step records may come out a unit
    in the last place off (the gradient, and with it every state the round
    produces, does not). A leaf may differ only if it is a per-lane record
    ([1, C, n] float32: `loss_sum`, `batch_loss` and the payload's copies),
    only in `job_lanes`, and by at most 2 ulp."""
    got_l, tree_g = jax.tree_util.tree_flatten(got)
    want_l, tree_w = jax.tree_util.tree_flatten(want)
    assert tree_g == tree_w
    C = CFG["no_models"]
    for g, w in zip(got_l, want_l):
        g, w = np.asarray(g), np.asarray(w)
        if (len(job_lanes) and g.dtype == np.float32 and g.ndim == 3
                and g.shape[:2] == (1, C)):
            others = np.setdiff1d(np.arange(C), job_lanes)
            np.testing.assert_array_equal(g[:, others], w[:, others])
            np.testing.assert_array_max_ulp(g, w, maxulp=2)
        else:
            np.testing.assert_array_equal(g, w)


def check_round_is_bit_equal_to_the_full_length_loop(pair, case):
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
    wide_from = exp.engine.wide_from
    counts = plan_step_counts([mask[0]], STEP_CHUNK, wide_from)
    if case == "all_full":
        assert counts["steps_run"] == counts["steps_plan"]
    elif case == "heavy_tail":
        # the adversary's 6 epochs against the benign lanes' 2
        assert counts["steps_run"] < counts["steps_plan"]
        assert counts["lane_steps_real"] < counts["steps_run"] * counts["lanes"]
    elif case.startswith("check_k"):
        assert counts["steps_run"] == int(case[-1])
    job_lanes = ()
    if wide_from > 1:
        split = split_steps(jnp.asarray(mask[0]), wide_from)
        job_lanes = np.asarray(split.job_lanes[:int(split.n_jobs)])
    if wide_from == 2:
        assert len(job_lanes) == {"heavy_tail": 1, "empty_client": 1,
                                  "solo_lane": 1, "two_tails": 1,
                                  "two_jobs": 2}.get(case, 0)
        if case == "solo_lane":
            assert counts["steps_wide"] == 0
            assert counts["lane_steps_narrow"] == counts["lane_steps_real"]
        elif case == "two_tails":   # the full-width loop reaches the last epoch
            assert counts["steps_wide"] > counts["steps_plan"] - 2 * STEP_CHUNK
    elif wide_from > counts["lanes"]:
        # every lane with data is one job, and the full-width loop runs nothing
        has_data = mask[0].any(axis=(1, 2, 3))
        np.testing.assert_array_equal(job_lanes, np.flatnonzero(has_data))
        assert len(job_lanes) == {"empty_client": has_data.size - 1,
                                  "solo_lane": 1}.get(case, has_data.size)
        assert counts["steps_wide"] == 0
        assert counts["lane_steps_narrow"] == counts["lane_steps_real"]
    else:
        assert wide_from == 1
        assert counts["lane_steps_narrow"] == 0
        assert counts["steps_wide"] == -(-counts["steps_run"]
                                         // STEP_CHUNK) * STEP_CHUNK
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
    _assert_trees_bit_equal(train, ref_train, job_lanes)
    _assert_trees_bit_equal(out["exp"][1], out["ref"][1], job_lanes)


def _eqns(jaxpr, primitive):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, primitive)


def _assert_scalar_lt(loop):
    """`j < n` on scalars: a predicate some lane batched would read [C]
    values and reduce them, and the body would select every carry by it."""
    cond = loop.params["cond_jaxpr"].jaxpr
    assert [e.primitive.name for e in cond.eqns] == ["lt"]
    lt, = cond.eqns
    assert all(v.aval.shape == () for v in lt.invars + lt.outvars)


def _carried(loop):
    return [v.aval.shape for v in loop.params["body_jaxpr"].jaxpr.outvars]


def check_train_phase_is_a_wide_while_then_a_width_1_job_loop(pair):
    exp, _ = pair
    tasks_seq, idx_seq, mask_seq, ns, lane, _ = _feed(exp, "heavy_tail")
    key = jax.random.key(0)
    jaxpr = jax.make_jaxpr(exp.engine.train_fn)(
        exp.global_vars, tasks_seq, idx_seq, mask_seq, lane, key).jaxpr
    loops = list(_eqns(jaxpr, "while"))
    C = idx_seq.shape[1]
    # first the full-width loop: a C-wide carry around one loop of static
    # length, the chunk
    wide = loops[0]
    _assert_scalar_lt(wide)
    lanes_carry = [s for s in _carried(wide) if s[:1] == (C,)]
    assert _carried(wide)[0] == () and lanes_carry
    chunk, = _eqns(wide.params["body_jaxpr"].jaxpr, "scan")
    assert chunk.params["length"] == STEP_CHUNK
    if exp.mesh is None:
        # then the job loop: it carries the same stack, and its one inner
        # `while` (a lane's chunks) carries one lane's row of it around
        # the same static chunk: no C-wide step in a job
        wide_, jobs, lane_chunks = loops
        assert wide_ is wide
        _assert_scalar_lt(jobs)
        assert ([s for s in _carried(jobs) if s[:1] == (C,)] == lanes_carry)
        inner, = _eqns(jobs.params["body_jaxpr"].jaxpr, "while")
        assert inner is lane_chunks
        _assert_scalar_lt(lane_chunks)
        assert (sorted(s for s in _carried(lane_chunks) if s)
                == sorted(s[1:] for s in lanes_carry))
        chunk, = _eqns(lane_chunks.params["body_jaxpr"].jaxpr, "scan")
        assert chunk.params["length"] == STEP_CHUNK
        # the same three loops at `wide_from` 2 and C + 1: where the
        # full-width loop stops is a value read from the mask, and above C
        # it is 0 for every mask, the fullest included
        full = jnp.ones(mask_seq.shape[1:], bool)
        n_wide = int(split_steps(full, exp.engine.wide_from).n_wide)
        assert n_wide == (0 if exp.engine.wide_from > C
                          else -(-full[0, ..., 0].size // STEP_CHUNK))
        # one lane alone builds no job loop (sequential_debug's calls)
        one = jax.tree_util.tree_map(lambda l: l[:, :1],
                                     (tasks_seq, idx_seq, mask_seq))
        loops_1 = list(_eqns(jax.make_jaxpr(exp.engine.train_fn)(
            exp.global_vars, *one, lane[:1], key).jaxpr, "while"))
        assert len(loops_1) == 1
    else:
        assert exp.engine.wide_from == 1 and len(loops) == 1
    assert len(list(_eqns(jaxpr, "scan"))) == (2 if exp.mesh is None else 1)
    # and they are the only `while`s of the whole round program (`local_eval`
    # is off here: the local battery's job loop is the other one,
    # tests/test_local_battery_jobs.py)
    round_jaxpr = jax.make_jaxpr(exp.engine.round_fn)(
        exp.global_vars, exp.fg_state, tasks_seq, idx_seq, mask_seq, lane,
        ns, key, key).jaxpr
    assert len(list(_eqns(round_jaxpr, "while"))) == len(loops)


def check_one_program_for_every_trip_count_and_the_host_counts_it(pair):
    """Rounds of different trip counts — chunks of the full-width loop, jobs,
    a job's chunks — share one compiled round program, and the host's counts
    (the `round/plan` span's) are what the program reads from the same
    mask."""
    exp, _ = pair
    rf = exp.engine.round_fn
    wide_from = exp.engine.wide_from
    tail = wide_from > 1
    rng_t, rng_a = jax.random.split(jax.random.key(3))
    trip_counts, job_counts = set(), set()
    # (the mesh's steps are slow on virtual devices: two trip counts there)
    for case in CASES if exp.mesh is None else ("heavy_tail", "check_k1"):
        tasks_seq, idx_seq, mask_seq, ns, lane, mask = _feed(exp, case)
        jax.block_until_ready(rf(exp.global_vars, exp.fg_state, tasks_seq,
                                 idx_seq, mask_seq, lane, ns, rng_t, rng_a))
        order, n_chunks = active_steps(mask_seq[0])
        counts = plan_step_counts([mask[0]], STEP_CHUNK, wide_from)
        n_run = counts["steps_run"]
        assert int(n_chunks) == -(-n_run // STEP_CHUNK)
        active = np.flatnonzero(mask[0].any(axis=(0, 3)).reshape(-1))
        np.testing.assert_array_equal(np.asarray(order)[:n_run], active)
        if tail:
            split = split_steps(mask_seq[0], wide_from)
            np.testing.assert_array_equal(split.order, order)
            n_chunks = split.n_wide
            assert int(jnp.sum(split.n_tail)) == counts["lane_steps_narrow"]
            job_counts.add(int(split.n_jobs))
        assert int(n_chunks) * STEP_CHUNK == counts["steps_wide"]
        trip_counts.add(int(n_chunks))
    if wide_from > idx_seq.shape[1]:   # every lane a job: 1 to C of them
        assert trip_counts == {0}
        assert len(job_counts) >= 2 and min(job_counts) == 1
    else:
        assert len(trip_counts) >= 2 and 1 in trip_counts
        assert job_counts == ({0, 1, 2} if tail else set())
    assert rf._cache_size() == 1

    n0 = len(tel.spans())
    fl = exp.dispatch_round(1)
    exp.finalize_round(fl)
    plan, = [r for r in tel.spans(n0) if r.name == "round/plan"]
    program_chunks = sum(int(active_steps(jnp.asarray(m))[1])
                         for m in fl.mask_list)
    assert -(-plan.counts["steps_run"] // STEP_CHUNK) == program_chunks
    steps = plan_step_counts(fl.mask_list, STEP_CHUNK, wide_from)
    assert {k: plan.counts[k] for k in steps} == steps
    assert 0 < plan.counts["steps_run"] <= plan.counts["steps_plan"]
    assert (plan.counts["lane_steps_real"]
            <= plan.counts["steps_run"] * plan.counts["lanes"])
    assert (plan.counts["lane_steps_real"] <= plan.counts["lane_steps_narrow"]
            + plan.counts["steps_wide"] * plan.counts["lanes"])
    assert (plan.counts["lane_steps_narrow"] > 0) == tail
    assert rf._cache_size() + (
        exp.engine.round_fn_donated._cache_size()
        if exp.engine.round_fn_donated is not None else 0) == 1
