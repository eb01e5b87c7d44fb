"""The streamed round: one client live at a time, FedAvg accumulated in place.

The stacked round (fl/rounds.py) keeps every client's weights, momentum,
gradient and delta at once: 4 + 28·C bytes a parameter. A model whose state
is a large share of the device cannot be stacked (`ModelDef.streamed`), so
its round program runs the round's clients one after another: a client
starts from the global model, takes the steps its mask holds, its
model-replacement scale is applied, its batteries run while its weights
still exist, and its delta is added into one accumulator. With the gradient
that is 20 bytes a parameter whatever C is: the global model, the
accumulator, the live client's weights, its momentum, its gradient.

The accumulator, the client's weights and its momentum are the round's
**workspace**: arguments and results of the round program, allocated once
and donated to every round (off the CPU), so they are live buffers that
`memory_stats()` counts and no round allocates them again. The accumulator
enters and leaves a round as zeros; what the other two hold between rounds is
the last client's and is never read.

Same mathematics as the stacked FedAvg round, in the same order of
floating-point operations (tests/test_streamed_round.py holds the two
together on the CPU, to the bit): torch-SGD with fresh momentum a client,
`end = start + scale * (w - start)`, `delta = end - global`, `global += eta
/ no_models * sum(deltas)`. The steps loop runs a client's real steps only,
`STREAM_CHUNK` at a time (an outer loop whose trip count is read from the
mask around an inner loop of static length, as fl/client.py and for its
reason), so a benign client's steps and an adversary's are one compile.

What needs all C deltas at once is not here: the robust rules
(ops/aggregation.py::flatten_stacked over a stack that does not exist), the
fault layer's screens, forensics, FoolsGold's accumulators. `refuse` names
each at build.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from dba_mod_tpu import config as cfg
from dba_mod_tpu.fl.client import STEP_CHUNK, ClientMetrics
from dba_mod_tpu.fl.evaluation import (EvalResult, local_battery_jobs,
                                       make_eval_fn)
from dba_mod_tpu.fl.rounds import LocalEvals
from dba_mod_tpu.models import ModelDef, ModelVars
from dba_mod_tpu.models.decoder_parts import ROWS_COUNTER
from dba_mod_tpu.ops import aggregation as agg
from dba_mod_tpu.ops.fused_update import make_fused_step_update
from dba_mod_tpu.ops.losses import batch_scores


class Workspace(NamedTuple):
    acc: ModelVars      # sum of the round's deltas; zeros between rounds
    client: ModelVars   # the live client's weights and statistics
    momentum: Any       # its SGD momentum buffers (params tree)


class ModelCounts(NamedTuple):
    """What the model counted of its own work over a round's real steps
    (models/lfm2.py, models/sdar.py: tokens given to the held experts;
    models/sdar.py: the rows its expert layers multiplied). All zero for a
    model that counts nothing."""
    held: jax.Array   # sum over steps, layers and held experts
    max: jax.Array    # the most one held expert was given in one step
    cells: jax.Array  # (step, layer, held expert) cells counted
    # [2] sums over steps and layers of the model's `ROWS_COUNTER` entries:
    # the (position, expert) rows multiplied, the rows of every held expert
    # over every position; no part of the three above
    rows: jax.Array
    # {name: sum over the real steps} of what the model's objective tallies
    # (`ModelDef.tallies`; models/sdar.py: positions masked and scored)
    tallies: Any = ()


def fold_counts(counts: ModelCounts, counted, valid) -> ModelCounts:
    """`counts` with what the model counted in one step (its `counters`
    collection) added where the step was real (`valid`): a `ROWS_COUNTER`
    entry into `rows`, every other leaf (tokens given to each held expert,
    a layer an entry) into `held`, `max` and `cells`."""
    for path, n in jax.tree_util.tree_leaves_with_path(counted):
        n = n * valid
        if any(getattr(key, "key", None) == ROWS_COUNTER for key in path):
            counts = counts._replace(rows=counts.rows + n)
        else:
            counts = counts._replace(
                held=counts.held + jnp.sum(n),
                max=jnp.maximum(counts.max, jnp.max(n)),
                cells=counts.cells + n.size * valid)
    return counts


def make_workspace(global_vars: ModelVars) -> Workspace:
    zeros = lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree)
    return Workspace(zeros(global_vars), zeros(global_vars),
                     zeros(global_vars.params))


def stream_chunk(steps_per_epoch: int) -> int:
    """Steps a trip of the steps loop: a client's epoch where that is
    shorter than fl/client.py's chunk, so that a client of one short epoch
    runs no masked step."""
    return min(STEP_CHUNK, steps_per_epoch)


def refuse(params: cfg.Params, mesh, num_segments: int, robust: bool,
           forensics: bool, track_batches: bool) -> None:
    """What the streamed round cannot run, by name, at build."""
    why = None
    if params.aggregation != cfg.AGGR_MEAN:
        why = (f"aggregation_methods: {params.aggregation!r} needs every "
               "client's delta at once (ops/aggregation.py::flatten_stacked); "
               "the streamed round keeps one client and a running sum: "
               "FedAvg ('mean') only")
    elif mesh is not None:
        why = "num_devices: the clients axis is not there to shard"
    elif num_segments != 1:
        why = "aggr_epoch_interval > 1 (a client's state is not kept)"
    elif robust:
        why = "fault_injection / screen_updates (screens read all deltas)"
    elif forensics:
        why = "forensics (per-client cosines read all deltas)"
    elif track_batches:
        why = "vis_train_batch_loss / batch_track_distance"
    elif float(params["alpha_loss"]) != 1.0:
        why = "alpha_loss < 1 (the distance term is not written here)"
    elif bool(params.get("sequential_debug")) or bool(
            params.get("overlap_eval")):
        why = "sequential_debug / overlap_eval (they split the stacked round)"
    if why:
        raise ValueError(f"this model trains through the streamed round "
                         f"(one client live at a time), which does not "
                         f"support {why}")


def make_streamed_round(model_def: ModelDef, data, hyper, plans, local_plans,
                        global_evals, is_poison_run: bool, baseline: bool,
                        do_local_eval: bool):
    """round(global_vars, fg_state, work, tasks_seq, idx_seq, mask_seq, lane,
    num_samples, rng_t, rng_a, source) -> (new_vars, fg_state, work,
    payload). The feed is the stacked round's (`tasks_seq` leaves [1, C,
    ...], `idx_seq`/`mask_seq` [1, C, E, S, B]) plus `work` (a `Workspace`)
    and `source`, the arrays `data.fetch_train` reads: an argument, so the
    population is no constant of the executable. The payload is the stacked
    round's with a `ModelCounts` appended."""
    update = make_fused_step_update(hyper.momentum, hyper.weight_decay,
                                    False, use_pallas=False)
    eval_clean = make_eval_fn(model_def, data, poison=False)
    eval_poison = make_eval_fn(model_def, data, poison=True)
    no_eval = EvalResult(*(jnp.float32(0),) * 4)

    def client_steps(global_vars, carry, task, idx, mask, rng, source):
        """One client's real steps from the global model; `carry` holds the
        workspace's buffers and is overwritten."""
        E, S, B = idx.shape
        chunk = stream_chunk(S)
        idx, mask = idx.reshape(E * S, B), mask.reshape(E * S, B)
        real = jnp.any(mask, axis=1)
        order = jnp.argsort(~real, stable=True)
        n_real = jnp.sum(real, dtype=jnp.int32)

        def step(i, carry):
            params, bn, mom, m, counts = carry
            step_i = order[jnp.minimum(i, E * S - 1)]
            bidx, bmask = idx[step_i], mask[step_i] & (i < n_real)
            e = step_i // S
            x, y = data.fetch_train(task.slot, bidx, source)
            x, y, sel = data.stamp(x, y, task.adv_index,
                                   task.poisoning_per_batch)
            step_rng = jax.random.fold_in(
                jax.random.fold_in(rng, e), step_i - e * S)

            def loss_fn(p):
                out = model_def.run_batch(ModelVars(p, bn), x, y, bmask,
                                          step_rng, train=True)
                return out.loss, out

            (loss, out), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            valid = jnp.sum(bmask) > 0
            with jax.named_scope("optimizer"):
                params, mom, _, bn = update(task.lr_row[e], valid, params,
                                            grads, mom, (), out.batch_stats,
                                            bn)
            vf = valid.astype(jnp.float32)
            _, right, seen = batch_scores(out.logits, out.labels, bmask)
            rows = bmask.astype(jnp.float32)
            m = ClientMetrics(
                loss_sum=m.loss_sum.at[e].add(vf * loss),
                correct=m.correct.at[e].add(vf * right),
                count=m.count.at[e].add(vf * seen),
                poison_count=m.poison_count.at[e].add(
                    vf * jnp.sum(sel * rows)))
            counts = fold_counts(counts, out.counted, valid)
            if out.tallies:
                counts = counts._replace(tallies={
                    name: counts.tallies[name]
                    + (out.tallies[name] * valid).astype(jnp.int32)
                    for name in counts.tallies})
            return params, bn, mom, m, counts

        def chunk_of(j, carry):
            return jax.lax.fori_loop(
                0, chunk, lambda k, c: step(j * chunk + k, c), carry)

        client, mom, counts = carry
        del client  # overwritten: a client starts from the global model
        zeros_e = jnp.zeros((E,), jnp.float32)
        start = (global_vars.params, global_vars.batch_stats,
                 jax.tree_util.tree_map(jnp.zeros_like, mom),
                 ClientMetrics(zeros_e, zeros_e, zeros_e, zeros_e), counts)
        return jax.lax.fori_loop(0, (n_real + chunk - 1) // chunk, chunk_of,
                                 start)

    def gated(wanted, fn):
        return jax.lax.cond(wanted, fn, lambda: no_eval)

    def round_fn(global_vars: ModelVars, fg_state, work: Workspace,
                 tasks_seq, idx_seq, mask_seq, lane, num_samples, rng_t,
                 rng_a, source):
        C = idx_seq.shape[1]
        seg_rng = jax.random.fold_in(rng_t, 0)
        pre_w, post_w, trig_w = local_battery_jobs(
            tasks_seq.poisoning_per_batch, tasks_seq.adv_slot,
            tasks_seq.num_epochs, baseline, False)
        E = idx_seq.shape[2]
        rows0 = {
            "metrics": ClientMetrics(*(jnp.zeros((C, E), jnp.float32),) * 4),
            "delta_norms": jnp.zeros((C,), jnp.float32),
            "locals": [EvalResult(*(jnp.zeros((C,), jnp.float32),) * 4)
                       for _ in range(4)]}
        put = lambda rows, c, value: jax.tree_util.tree_map(
            lambda r, v: r.at[c].set(v), rows, value)

        def client(c, carry):
            acc, live, mom, counts, rows = carry
            task = jax.tree_util.tree_map(lambda l: l[0, c], tasks_seq)
            with jax.named_scope("phase/train"):
                params, bn, mom, metrics, counts = client_steps(
                    global_vars, (live, mom, counts), task, idx_seq[0, c],
                    mask_seq[0, c], jax.random.fold_in(seg_rng, lane[c]),
                    source)
            trained = ModelVars(params, bn)
            evals = [no_eval] * 4
            adv = task.adv_slot
            with jax.named_scope("phase/local_battery"):
                if do_local_eval:
                    lp = local_plans
                    evals[0] = eval_clean(trained, lp.clean_idx,
                                          lp.clean_slots, lp.clean_mask,
                                          jnp.int32(-1))
                    poisoned = lambda mv, trigger: eval_poison(
                        mv, lp.poison_idx, lp.poison_slots, lp.poison_mask,
                        trigger)
                    if is_poison_run:
                        evals[1] = gated(pre_w[c], lambda: poisoned(
                            trained, jnp.int32(-1)))
            # model replacement over the full state, against the global
            # model the client started from (fl/client.py::lane_finish)
            end = jax.tree_util.tree_map(
                lambda a, w: a + task.scale * (w - a), global_vars, trained)
            with jax.named_scope("phase/local_battery"):
                if do_local_eval and is_poison_run:
                    evals[2] = gated(post_w[c], lambda: poisoned(
                        end, jnp.int32(-1)))
                    evals[3] = gated(trig_w[c], lambda: poisoned(end, adv))
            with jax.named_scope("phase/aggregate"):
                delta = jax.tree_util.tree_map(lambda e, g: e - g, end,
                                               global_vars)
                norm = jnp.sqrt(jax.tree_util.tree_reduce(
                    lambda s, d: s + jnp.sum(jnp.square(d)), delta.params,
                    jnp.float32(0.0)))
                acc = jax.tree_util.tree_map(jnp.add, acc, delta)
            rows = {"metrics": put(rows["metrics"], c, metrics),
                    "delta_norms": rows["delta_norms"].at[c].set(norm),
                    "locals": [put(r, c, v)
                               for r, v in zip(rows["locals"], evals)]}
            return acc, end, mom, counts, rows

        counts0 = ModelCounts(jnp.int32(0), jnp.int32(0), jnp.int32(0),
                              jnp.zeros((2,), jnp.int32),
                              {name: jnp.int32(0)
                               for name in model_def.tallies} or ())
        acc, live, mom, counts, rows = jax.lax.fori_loop(
            0, C, client,
            (work.acc, work.client, work.momentum, counts0, rows0))
        with jax.named_scope("phase/aggregate"):
            # the stacked rule over a stack of one: the same scale, the same
            # rounding, the same noise
            new_vars = agg.fedavg_update(
                global_vars, jax.tree_util.tree_map(lambda a: a[None], acc),
                hyper.eta, hyper.no_models,
                hyper.sigma if hyper.diff_privacy else 0.0, rng_a)
            work = Workspace(jax.tree_util.tree_map(jnp.zeros_like, acc),
                             live, mom)
        with jax.named_scope("phase/global_battery"):
            globals_ = global_evals(new_vars)
        locals_ = LocalEvals(*rows["locals"]) if do_local_eval else None
        metrics = jax.tree_util.tree_map(lambda l: l[None], rows["metrics"])
        zeros_c = jnp.zeros((C,), jnp.float32)
        payload = (locals_, globals_, metrics, rows["delta_norms"], zeros_c,
                   zeros_c, None, jnp.asarray(True), None, None, None, counts)
        return new_vars, fg_state, work, payload

    return round_fn
