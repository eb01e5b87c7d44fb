"""The evaluation battery — jitted equivalents of reference test.py.

Four reference entry points map to two jitted kernels:
- `Mytest` (test.py:7-51)                     → evaluate(poison=False)
- `Mytest_poison` (test.py:54-115)            → evaluate(poison=True, adv=-1)
- `Mytest_poison_trigger` (test.py:118-177)   → evaluate(poison=True, adv=j)
- `Mytest_poison_agent_trigger` (:180-239)    → evaluate(poison=True, adv=slot)

Semantics preserved: loss is a reduction='sum' divided by the count
(test.py:21-22, :40); poisoned accuracy divides by `poison_data_count`
(test.py:105), which equals the valid-sample count since evaluation poisons
every sample; the poisoned image eval runs on the test set with target-label
images dropped (image_helper.py:148-172), expressed in the eval plan's index
set; the LOAN branches iterate every state shard (test.py:13-24) — here the
plan concatenates all shards with a per-row slot array.

Local (per-client) evals run the kernel on one client model per recorded row,
one model after another inside the round program (fl/rounds.py::
make_local_battery; which poisoned rows are recorded: `local_battery_jobs`).
The clean rows of a dense model, and of any model on a sharded clients axis,
instead vmap the kernel's forward pass over the stacked client models
(`make_stacked_eval_fn`): stacked lanes are a batched matmul there, or a lane
a device; stacked convolutions cost more than one model after another
(fl/rounds.py::lanes_as_jobs).
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from dba_mod_tpu.models import ModelDef, ModelVars
from dba_mod_tpu.fl.device_data import DeviceData
from dba_mod_tpu.ops.losses import batch_scores, cross_entropy_sum
from dba_mod_tpu.utils import telemetry


class EvalResult(NamedTuple):
    loss: jax.Array      # average loss (sum / count)
    acc: jax.Array       # percentage
    correct: jax.Array
    count: jax.Array     # dataset_size / poison_data_count


def instrument_eval(fn, name: str, batches: int = 0):
    """Telemetry wrapper for a compiled eval battery: each call runs under a
    span with an explicit device sync (``jax.block_until_ready`` on the
    results — under async dispatch the un-synced call time is just the
    enqueue), and counts `batches` scan steps into ``eval/batches``.

    A zero-overhead passthrough while telemetry is off, so the standalone
    batteries keep deferring their sync to ``finalize_round`` and round
    pipelining is unaffected. With telemetry on, evals that run outside the
    fused round program (sequential_debug, overlap_eval, the degraded-round
    re-eval, the LOAN backdoor probe) report honest phase times at the cost
    of syncing where they are called. The fused round's own batteries are
    timed by their `phase/` scopes under a profiler trace instead."""
    return telemetry.instrument(fn, name, batches=batches)


def pick_eval_device(mesh, overlap: bool):
    """The device the overlap_eval batteries should run on, or None to
    share device 0. A SECOND local device (when present, and only without a
    clients mesh — sharded batteries stay on the mesh) gives true compute
    overlap: round N's eval executables compile against their own
    placement-cached copy of the test-set constants (JAX places
    closure-captured data per compiled executable), so they run while
    device 0 executes round N+1's train/aggregate. With one device the
    batteries still dispatch ahead but only the host-side fetch/record/
    checkpoint path is hidden."""
    if not overlap or mesh is not None:
        return None
    devs = jax.local_devices()
    return devs[1] if len(devs) > 1 else None


def place_eval_inputs(operands, device):
    """One-hop ``jax.device_put`` of the overlap path's eval operands onto
    the eval device (passthrough when placement is off). The operands are
    the superseded round's SNAPSHOTS (model, pre-fault deltas, task row) —
    transferring them here, at dispatch, is what lets the donated/overwritten
    device-0 buffers belong to round N+1 while N's batteries still read
    bit-identical inputs."""
    if device is None:
        return operands
    return jax.device_put(operands, device)


def make_eval_fn(model_def: ModelDef, data: DeviceData, poison: bool):
    """evaluate(model_vars, idx[S,B], slots[S,B], mask[S,B], adv_index)
    -> EvalResult. `poison` is static: True stamps every sample with trigger
    `adv_index` and swaps labels (test.py:95, evaluation=True)."""

    def evaluate(model_vars: ModelVars, idx, slots, mask,
                 adv_index) -> EvalResult:
        def body(carry, inp):
            loss_sum, correct, count = carry
            bidx, bslot, bmask = inp
            x, y = data.fetch_test(bslot, bidx)
            if poison:
                x, y, _ = data.stamp(x, y, adv_index, 0, poison_all=True)
            out = model_def.run_batch(model_vars, x, y, bmask, None,
                                      train=False)
            dl, dc, dn = batch_scores(out.logits, out.labels, bmask)
            return (loss_sum + dl, correct + dc, count + dn), None

        (loss_sum, correct, count), _ = jax.lax.scan(
            body, (jnp.float32(0), jnp.float32(0), jnp.float32(0)),
            (idx, slots, mask))
        safe = jnp.maximum(count, 1.0)
        return EvalResult(loss=loss_sum / safe, acc=100.0 * correct / safe,
                          correct=correct, count=count)

    return evaluate


def make_stacked_eval_fn(model_def: ModelDef, data: DeviceData):
    """evaluate_stacked(stacked_vars [C, ...], idx[S,B], slots[S,B],
    mask[S,B]) -> EvalResult with [C] leaves: the clean test of C client
    models over ONE shared eval plan.

    Fetching each test batch inside a per-client vmap (the naive formulation)
    gathers every batch C times. Here the fetch is hoisted out of the model
    vmap: one gather per batch, shared by all C models; only the forward
    passes are batched over clients. Numerics are bit-identical to vmapping
    :func:`make_eval_fn` — same ops, same per-client accumulation order
    (tests/test_eval_stacked.py).

    Called by fl/rounds.py::make_local_battery for the clean part where
    fl/rounds.py::lanes_as_jobs says no: a model of dense layers only (its
    stacked step is a batched matmul, 2.6 single-model steps at 10 lanes),
    and any model on a sharded clients axis (a lane a device). A model with a
    convolution on one device runs its clean tests, like every poisoned
    test, through :func:`make_eval_fn` one model at a time: ten models
    stacked cost 13-40 single-model steps there (PERF.md section 7)."""

    def evaluate_stacked(stacked_vars: ModelVars, idx, slots,
                         mask) -> EvalResult:
        def body(carry, inp):
            loss_sum, correct, count = carry         # [C] each
            bidx, bslot, bmask = inp
            x, y = data.fetch_test(bslot, bidx)      # ONE gather, shared
            bmaskf = bmask.astype(jnp.float32)

            def per_model(mv: ModelVars):
                logits, _ = model_def.apply(mv, x, train=False)
                loss = cross_entropy_sum(logits, y, bmask)
                preds = jnp.argmax(logits, axis=-1)
                return (loss, jnp.sum((preds == y) * bmaskf),
                        jnp.sum(bmaskf))

            dl, dc, dn = jax.vmap(per_model)(stacked_vars)
            return (loss_sum + dl, correct + dc, count + dn), None

        C = jax.tree_util.tree_leaves(stacked_vars)[0].shape[0]
        zeros = jnp.zeros((C,), jnp.float32)
        (loss_sum, correct, count), _ = jax.lax.scan(
            body, (zeros, zeros, zeros), (idx, slots, mask))
        safe = jnp.maximum(count, 1.0)
        return EvalResult(loss=loss_sum / safe, acc=100.0 * correct / safe,
                          correct=correct, count=count)

    return evaluate_stacked


def local_battery_jobs(poisoning_per_batch, adv_slot, num_epochs,
                       baseline: bool, forensics: bool = False):
    """Which poisoned tests of the local battery an attack run's recorder
    writes: (pre, post, trigger), each a [C] bool, from the [I, C] task rows
    of the segments the battery covers (numpy on the host, jax in the
    program: the one rule both read). As the reference, only a poisoning
    client's model is tested on poisoned data (image_train.py:150-164,
    :275-295):

    - `pre` (pre-scaling model, combined trigger) and `post` (submitted
      model, combined trigger) for a lane that poisoned in ANY of the
      segments; no `pre` under `baseline` (:148);
    - `trigger` (submitted model, the lane's own trigger) for a listed
      adversary, poisoning or not (:285-295);
    - with `forensics`, `post` for every real lane as well
      (`_record_forensics` reads its accuracy); a mesh's padding lanes
      (no epochs) get nothing."""
    poisoning = (poisoning_per_batch > 0).any(axis=0)
    real = (num_epochs > 0).any(axis=0)
    post = poisoning | real if forensics else poisoning
    return (poisoning & (not baseline), post, (adv_slot >= 0).any(axis=0))


def job_order(wanted):
    """wanted [N] bool -> (order [N], n_jobs): the ids of the wanted jobs
    first, in their original order (the rest follow), and how many they are:
    the trip count of the job loop, read inside the program."""
    return (jnp.argsort(~wanted, stable=True),
            jnp.sum(wanted, dtype=jnp.int32))


def battery_eval_counts(tasks_list, is_poison_run: bool, baseline: bool,
                        forensics: bool, clean_jobs: bool) -> Dict[str, int]:
    """What a round's tasks ask of the local batteries, counted on the host
    from the task rows the program reads (one ClientTask of [C] numpy leaves
    per segment; every segment runs a battery, the last one gating on the
    whole round's rows and alone serving `forensics`): `battery_evals_run`
    the single-model tests that run (the clean test of every lane plus
    :func:`local_battery_jobs`) over `battery_evals_plan`, all four parts
    (the clean one alone outside an attack run) for every lane. Of the
    `battery_clean_evals` clean tests, `battery_clean_jobs` run as
    single-model jobs, one model after another (all with the engine's
    `clean_jobs`, none where the stacked `vmap` runs them)."""
    rows = [np.stack([getattr(t, f) for t in tasks_list]) for f in
            ("poisoning_per_batch", "adv_slot", "num_epochs")]
    n_seg, lanes = rows[0].shape
    run = n_seg * lanes
    for s in range(n_seg if is_poison_run else 0):
        last = s == n_seg - 1
        run += sum(int(j.sum()) for j in local_battery_jobs(
            *(r if last else r[s:s + 1] for r in rows), baseline,
            forensics and last))
    return {"battery_evals_run": run,
            "battery_evals_plan": (4 if is_poison_run else 1) * n_seg * lanes,
            "battery_clean_evals": n_seg * lanes,
            "battery_clean_jobs": n_seg * lanes if clean_jobs else 0}
