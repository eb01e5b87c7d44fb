"""The local-training step of one segment: a single client's step body, run
by two loops whose trip counts are read from the round's mask. The
full-width loop runs every lane (`vmap` over the clients axis) through the
steps in which SOME lane has a real batch (`active_steps`), in their
original order, STEP_CHUNK at a time, up to the last step that at least
`wide_from` lanes need; every real step after it is part of its lane's job,
and a job loop runs the jobs one lane at a time at width 1 (`split_steps`).
`wide_from` is the engine's (fl/rounds.py::wide_from_of): 2 finishes one
lane's tail alone (an adversary's extra epochs beside benign lanes), C + 1
makes every lane with data one job and the full-width loop runs nothing,
1 (a sharded clients axis), or one lane, is the full-width loop alone.

Capability parity with the reference client loop (image_train.py:21-315,
loan_train.py:17-261), re-expressed as data-dependent selects so benign and
poison clients share one compiled program:

- fresh torch-SGD per global epoch (momentum buffers start at zero — the
  reference constructs a new optimizer per client per round,
  image_train.py:33, :63);
- per-internal-epoch LR row (benign constant lr; poison MultiStepLR —
  image_train.py:66-68, 118-119);
- loss = α·CE + (1-α)·‖w - w_anchor‖ (image_train.py:85-90);
- batch poisoning of the first `poisoning_per_batch` samples
  (image_helper.py:298-326);
- FoolsGold per-parameter gradient accumulation across every batch
  (image_train.py:94-100);
- model-replacement scaling epilogue w ← w_a + γ·(w - w_a) over the FULL
  state including BN stats (image_train.py:166-171 scales the state_dict).

One call covers ONE global epoch (one `aggr_epoch_interval` segment). The
anchor for the distance loss and the scaling epilogue is the client's state at
the segment start — the reference re-snapshots `last_local_model` at the top
of every global epoch (image_train.py:26-27, :52-54, :306), which equals the
global model only for the first segment of a round. The engine chains
segments and derives Δ = w_end - w_global at the end.

Per-epoch train metrics (loss sum, correct, count, poisoned count) are
accumulated with scatter-adds for CSV-schema parity (csv_record.train_result).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from dba_mod_tpu.models import ModelDef, ModelVars
from dba_mod_tpu.fl.device_data import DeviceData
from dba_mod_tpu.fl.evaluation import job_order
from dba_mod_tpu.fl.state import ClientTask, RoundHyper
from dba_mod_tpu.ops.fused_update import make_fused_step_update
from dba_mod_tpu.ops.losses import cross_entropy, tree_dist_norm
from dba_mod_tpu.ops.sgd import sgd_init


class ClientMetrics(NamedTuple):
    loss_sum: jax.Array      # [E] Σ batch-mean losses (reference total_loss)
    correct: jax.Array       # [E] correct predictions
    count: jax.Array         # [E] samples seen (reference dataset_size)
    poison_count: jax.Array  # [E] poisoned samples seen


class SegmentResult(NamedTuple):
    end_vars: ModelVars      # post-scaling client state (next segment's start)
    benign_mom: Any          # benign-optimizer momentum after this segment
    fg_grads: Any            # grads accumulated THIS segment (params tree)
    metrics: ClientMetrics
    batch_loss: jax.Array    # [E*S] per-batch loss (vis_train_batch_loss,
                             # image_train.py:225-235); [0] when tracking off
    batch_dist: jax.Array    # [E*S] post-step ‖w-w_anchor‖ (batch_track_
                             # distance, image_train.py:236-245); [0] off


def _select_tree(pred, new, old):
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), new, old)


# The steps loop runs in chunks of this many plan steps: an outer loop with a
# trip count read from the mask around an inner loop of static length. The
# inner loop is there for the compiler: XLA:TPU compiled the step body
# differently where it sat directly in a loop (or a conditional) whose trip
# count it does not know — one convolution's weight gradient came out 87 %
# off the plain reference (PERF.md, PR 25) — and as it always had inside a
# loop of known length. Up to STEP_CHUNK - 1 fully masked steps run with the
# last chunk.
STEP_CHUNK = 4


def active_steps(mask):
    """mask [C, E, S, B] of one segment -> (order [E*S], n_chunks): the flat
    ids of the steps in which any lane holds a real batch, first and in their
    original order (the rest follow), and how many chunks of STEP_CHUNK
    positions of `order` hold them all. A step masked in every lane is a
    no-op by construction — the update selects the old state and every
    metric adds 0 — so the loop leaves it out; what a step computes depends
    on its (epoch, step) id, never on how many steps ran before it."""
    active = jnp.any(mask, axis=(0, 3)).reshape(-1)
    n_run = jnp.sum(active, dtype=jnp.int32)
    return (jnp.argsort(~active, stable=True),
            (n_run + STEP_CHUNK - 1) // STEP_CHUNK)


class StepSplit(NamedTuple):
    """How one segment's steps divide between the two loops."""
    order: jax.Array       # [E*S] `active_steps`' order
    n_wide: jax.Array      # chunks of `order` the full-width loop runs
    job_lanes: jax.Array   # [C] the lanes with a tail first, in lane order
    n_jobs: jax.Array      # how many they are: the job loop's trip count
    lane_order: jax.Array  # [C, E*S] a lane's own tail steps first, in order
    n_tail: jax.Array      # [C] how many those are


def split_steps(mask, wide_from: int) -> StepSplit:
    """mask [C, E, S, B] of one segment -> where the full-width loop stops
    and what each lane still has to run alone. The full-width loop runs the
    positions of `order` up to and including the last one at which at least
    `wide_from` lanes hold a real batch, rounded up to STEP_CHUNK; a lane
    with a real step after that is one job: its own real steps past the
    boundary, in their order. Lanes are independent within a segment, so no
    lane's sequence of steps changes. `wide_from` (a static Python integer)
    is the number of live lanes from which a full-width step is the cheaper
    one, ceil(full-width step / width-1 step) on the chip: 13.6 for the
    Tiny-ImageNet ResNet-18 at C = 10 (20.13 ms against 1.483 ms, and 1.11
    ms a job; PERF.md, PRs 28 and 31), so there every step is a job's. With
    2 an equal-split or all-benign round has no job and runs what
    `active_steps` says; with more than C the full-width loop's trip count
    is 0 in every round. data/batching.py::plan_step_counts is the same
    rule in numpy."""
    C = mask.shape[0]
    real = jnp.any(mask, axis=3).reshape(C, -1)            # [C, E*S] by id
    order, _ = active_steps(mask)
    real = real[:, order]                                  # by position
    pos = jnp.arange(real.shape[1], dtype=jnp.int32)
    shared = jnp.sum(real, axis=0, dtype=jnp.int32) >= wide_from
    n_wide = (jnp.max(jnp.where(shared, pos + 1, 0))
              + STEP_CHUNK - 1) // STEP_CHUNK
    tail = real & (pos >= n_wide * STEP_CHUNK)
    n_tail = jnp.sum(tail, axis=1, dtype=jnp.int32)
    return StepSplit(
        order, n_wide, *job_order(n_tail > 0),
        order[jnp.argsort(~tail, axis=1, stable=True)], n_tail)


def make_client_step(model_def: ModelDef, data: DeviceData,
                     hyper: RoundHyper, fg_enabled: bool,
                     fused_pallas: bool = False,
                     fused_interpret: bool = False,
                     wide_from: int = 2):
    """Returns segment_step(start_vars, benign_mom, tasks, idx[C,E,S,B],
    mask[C,E,S,B], rngs[C]) -> SegmentResult, every argument and result
    stacked [C, ...]: one segment of every lane. The single client's step
    body is written once and run by the full-width loop (`vmap` over the
    lanes; one `while` whose predicate no lane batches around STEP_CHUNK
    steps at a time) and, with `wide_from` above 1 and more than one lane,
    by the job loop that runs at width 1 what the full-width loop leaves
    (`split_steps`). The engine gives `wide_from` (fl/rounds.py::
    wide_from_of): 1 where the clients axis is sharded — taking one lane
    out of a sharded stack is a collective — and the full-width loop then
    runs every step that runs.
    `fused_pallas` routes the full-width loop's per-step state update
    through the fused multi-tensor kernel (ops/fused_update.py) when the
    engine runs unsharded on TPU; the math is identical either way."""
    fused_update = make_fused_step_update(
        hyper.momentum, hyper.weight_decay, fg_enabled,
        use_pallas=fused_pallas, interpret=fused_interpret)

    def lane_init(start_vars: ModelVars, benign_mom: Any, task: ClientTask,
                  E: int, S: int):
        params0 = start_vars.params
        # The benign optimizer lives for the whole round (image_train.py:33 is
        # outside the global-epoch loop), so its momentum chains across
        # segments; the poison optimizer is fresh per poison epoch
        # (image_train.py:63 inside the loop) → zero buffers.
        mom0 = _select_tree(task.poisoning_per_batch > 0, sgd_init(params0),
                            benign_mom)
        fg0 = jax.tree_util.tree_map(jnp.zeros_like, params0)
        zeros_e = jnp.zeros((E,), jnp.float32)
        metrics0 = ClientMetrics(zeros_e, zeros_e, zeros_e, zeros_e)
        # [E*S] per-step channels, zero where no step ran; zero-width when
        # tracking is off: shape-compatible, nothing carried or transferred
        tracked0 = (jnp.zeros((E * S if hyper.track_batches else 0,),
                              jnp.float32),) * 2
        return params0, start_vars.batch_stats, mom0, fg0, metrics0, tracked0

    def lane_steps(source, carry, params0, task: ClientTask, idx, mask, rng,
                   order, n_valid, n_chunks):
        """One lane through the steps `order[:n_chunks * STEP_CHUNK]`, of
        which the first `n_valid` may hold a real batch; `source`: the
        arrays the batches are fetched from."""
        E, S, B = idx.shape
        idx, mask = idx.reshape(E * S, B), mask.reshape(E * S, B)

        def step(i, carry):
            params, bn, mom, fg, m, tracked = carry
            # position i of `order`; the last chunk may reach past
            # `n_valid`, or past the plan's end: such a position reads some
            # step with nothing valid in it
            step_i = order[jnp.minimum(i, E * S - 1)]
            bidx, bmask = idx[step_i], mask[step_i] & (i < n_valid)
            e = step_i // S
            x, y = data.fetch_train(task.slot, bidx, source)
            x, y, sel = data.stamp(x, y, task.adv_index,
                                   task.poisoning_per_batch)
            # derive from (epoch, step-within-epoch), NOT the flat index:
            # the flat index depends on the plan width S — dropout streams
            # must not change with the padding
            step_rng = jax.random.fold_in(
                jax.random.fold_in(rng, e), step_i - e * S)

            def loss_fn(p):
                logits, new_bn = model_def.apply(
                    ModelVars(p, bn), x, train=True, dropout_rng=step_rng)
                ce = cross_entropy(logits, y, bmask)
                if hyper.alpha_loss == 1.0:
                    # every reference config sets alpha_loss=1 — the
                    # anomaly-evading distance term is identically zero, so
                    # skip its fwd+bwd (a full extra pass over the params)
                    # at trace time
                    loss = ce
                else:
                    dist = tree_dist_norm(p, params0)
                    loss = task.alpha * ce + (1.0 - task.alpha) * dist
                return loss, (logits, new_bn)

            (loss, (logits, new_bn)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            lr = task.lr_row[e]
            # This lane's padded steps (mask all-false: epochs beyond this
            # client's count, or steps beyond its batches, at a step another
            # lane needs) must be no-ops; the fused op does torch-SGD + the
            # validity selects (+ FoolsGold accumulation) over the whole
            # state in one logical op.
            valid = jnp.sum(bmask) > 0
            params, mom, fg, bn = fused_update(lr, valid, params, grads,
                                               mom, fg, new_bn, bn)

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
            if hyper.track_batches:
                # the reference measures the distance AFTER the step
                # (image_train.py:238: optimizer.step() precedes it)
                bl, bd = tracked  # a step's one real visit adds to a zero
                tracked = (
                    bl.at[step_i].add(vf * loss),
                    bd.at[step_i].add(vf * tree_dist_norm(params, params0)))
            return params, bn, mom, fg, m, tracked

        def chunk(j, carry):
            return jax.lax.fori_loop(
                0, STEP_CHUNK, lambda k, c: step(j * STEP_CHUNK + k, c), carry)

        # a dynamic trip count makes the outer loop a `while` (nothing
        # differentiates through the steps loop: the gradient is inside each
        # step)
        return jax.lax.fori_loop(0, n_chunks, chunk, carry)

    def lane_finish(carry, start_vars: ModelVars, benign_mom: Any,
                    task: ClientTask) -> SegmentResult:
        params, bn, mom, fg, metrics, (batch_loss, batch_dist) = carry
        # a poison segment leaves the benign buffers untouched
        benign_mom_out = _select_tree(task.poisoning_per_batch > 0,
                                      benign_mom, mom)
        # Model-replacement scaling over the FULL state (image_train.py:166-171
        # iterates state_dict — BN stats included) against the segment anchor.
        end_vars = jax.tree_util.tree_map(
            lambda a, w: a + task.scale * (w - a), start_vars,
            ModelVars(params, bn))
        return SegmentResult(end_vars, benign_mom_out, fg, metrics,
                             batch_loss, batch_dist)

    def segment_step(start_vars: ModelVars, benign_mom: Any,
                     tasks: ClientTask, idx, mask, rngs) -> SegmentResult:
        C, E, S, _ = idx.shape
        feed = (start_vars.params, tasks, idx, mask, rngs)
        split = (split_steps(mask, wide_from) if wide_from > 1 and C > 1
                 else None)
        order, n_wide = (active_steps(mask) if split is None
                         else (split.order, split.n_wide))
        # with two loops reading the dataset, one traced value for both:
        # as a constant it would ride in the executable once a loop (1.2 GB
        # each at Tiny-ImageNet's size, and past 2 GiB the executable can
        # no longer be written to the compile cache)
        source = data.train_source
        if split is not None:
            source = jax.lax.optimization_barrier(source)
        carry = jax.vmap(lambda *lane: lane_init(*lane, E, S))(
            start_vars, benign_mom, tasks)
        carry = jax.vmap(lane_steps, in_axes=(None,) + (0,) * 6 + (None,) * 3)(
            source, carry, *feed, order, E * S, n_wide)

        def job(k, carry):
            c = split.job_lanes[k]
            n = split.n_tail[c]
            row = jax.tree_util.tree_map(
                lambda l: jax.lax.dynamic_index_in_dim(l, c, keepdims=False),
                (carry, *feed))
            done = lane_steps(source, *row, split.lane_order[c], n,
                              (n + STEP_CHUNK - 1) // STEP_CHUNK)
            return jax.tree_util.tree_map(
                lambda l, r: jax.lax.dynamic_update_index_in_dim(l, r, c, 0),
                carry, done)

        if split is not None:
            carry = jax.lax.fori_loop(0, split.n_jobs, job, carry)
        return jax.vmap(lane_finish)(carry, start_vars, benign_mom, tasks)

    return segment_step
