"""The round engine: jitted train + aggregate computations, plus the jitted
local/global evaluation batteries.

Replaces main.py:135-231's sequential orchestration. A round is:

  train_fn   — for each `aggr_epoch_interval` segment (global epoch), the
               vmapped client step runs all clients in parallel, chaining each
               client's state across segments (the reference's local model
               trains continuously within a round, re-anchoring its distance
               loss and scaling at each global epoch — image_train.py:50-54,
               :306); emits Δ = w_end - w_global plus FoolsGold gradient
               accumulators and per-segment metrics.
  aggregate_fn — the configured rule over the stacked deltas.

Splitting the two lets the sequential debug mode (SURVEY §7.2.4) run clients
one at a time through the identical per-client program and still share the
aggregation path. Server→client broadcast and client→server upload are XLA
data flow, not host dict-copies (contrast image_train.py:32,
helper.py:223-227).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dba_mod_tpu import config as cfg
from dba_mod_tpu.models import ModelDef, ModelVars
from dba_mod_tpu.fl import faults as flt
from dba_mod_tpu.fl.client import ClientMetrics, make_client_step
from dba_mod_tpu.fl.device_data import DeviceData
from dba_mod_tpu.fl.evaluation import (EvalResult, job_order,
                                       local_battery_jobs, make_eval_fn,
                                       make_stacked_eval_fn)
from dba_mod_tpu.fl.state import ClientTask, RoundHyper
from dba_mod_tpu.ops import aggregation as agg
from dba_mod_tpu.ops.losses import tree_global_norm
from dba_mod_tpu.utils import telemetry


def count_bn_layers(batch_stats: Any) -> int:
    """Number of BatchNorm layers = number of `mean` running-stat leaves.

    Each BN layer in the reference's state_dict carries one
    `num_batches_tracked` scalar alongside running_mean/running_var; RFA's
    Weiszfeld distance sums squared differences over ALL state entries
    (helper.py:376-381), so the counter term enters the geometry once per BN
    layer."""
    paths = jax.tree_util.tree_flatten_with_path(batch_stats)[0]
    n = 0
    for path, _leaf in paths:
        last = path[-1]
        key = getattr(last, "key", getattr(last, "name", None))
        if key == "mean":
            n += 1
    return n


def nbt_client_deltas(mask_seq: jax.Array, scale_seq: jax.Array) -> jax.Array:
    """Per-client `num_batches_tracked` deltas for one round, [C] f32.

    torch BN increments the counter once per train-mode forward batch, so a
    client's counter delta is its number of REAL (non-padded) batch steps;
    the model-replacement epilogue scales the whole state_dict including the
    counter — `anchor + (v-anchor)·γ` copied into an int64 buffer truncates
    (image_train.py:166-171) — and with aggr_epoch_interval > 1 each segment
    re-anchors, so the round delta is Σ_seg trunc(steps_seg · γ_seg).

    mask_seq: [S, C, E, steps, B] validity mask; scale_seq: [S, C]."""
    steps = jnp.sum(jnp.any(mask_seq, axis=-1), axis=(2, 3))   # [S, C]
    return jnp.sum(jnp.trunc(steps.astype(jnp.float32) * scale_seq), axis=0)


class TrainResult(NamedTuple):
    deltas: ModelVars             # stacked [C, ...]: w_end - w_global
    fg_grads: Any                 # [C, ...] grads accumulated over the round
    fg_feature: jax.Array         # [C, L] similarity-layer grad, flattened
    metrics: ClientMetrics        # [I, C, E] per segment/client/epoch
    delta_norms: jax.Array        # [C] ‖Δ_params‖ — scale_result.csv distance
    batch_loss: jax.Array         # [I, C, E*S] per-batch loss ([I, C, 0]
                                  # when vis_train_batch_loss is off)
    batch_dist: jax.Array         # [I, C, E*S] per-batch post-step distance
                                  # ([I, C, 0] when batch_track_distance off)
    seg_deltas: Any               # list (len I-1) of full-state ModelVars
                                  # [C, ...] cumulative deltas at each
                                  # INTERMEDIATE segment end — feeds the
                                  # per-epoch local clean evals when
                                  # aggr_epoch_interval > 1
                                  # (image_train.py:268-271 runs inside the
                                  # global-epoch loop); empty list when I == 1


class RobustStats(NamedTuple):
    """Per-round fault-tolerance outcome, computed inside the jitted round
    program (None in the payload when the fault layer is off)."""
    n_dropped: jax.Array      # i32 — injected dropouts (never reported)
    n_quarantined: jax.Array  # i32 — reported but failed the screen
    n_surviving: jax.Array    # i32 — survivors among counted clients
    degraded: jax.Array       # bool — aggregation skipped (< min survivors)
    global_finite: jax.Array  # bool — post-aggregation model is all-finite
    survivor_mask: jax.Array  # [C] bool


class ForensicStats(NamedTuple):
    """Per-client defense-forensics diagnostics, computed inside the jitted
    round program when `forensics: true` (None in the payload otherwise).
    Rides the payload's single device_get at finalize — no host callbacks
    inside jit, no extra sync."""
    recv_norms: jax.Array     # [C] ‖Δ_params‖ as RECEIVED by the server
                              # (post fault injection; equals delta_norms
                              # when the fault layer is off — NaN/Inf for
                              # corrupted payloads, honestly)
    cosine_to_agg: jax.Array  # [C] cos(received Δ_c, applied global update)
    verdict: jax.Array        # [C] bool — client entered the aggregate
    reason: jax.Array         # [C] i32 quarantine reason (REASON_*)
    oracle_calls: jax.Array   # i32 — RFA Weiszfeld oracle count (1 else)


# quarantine-reason codes carried in ForensicStats.reason
REASON_OK = 0           # aggregated
REASON_DROPPED = 1      # never reported (injected dropout)
REASON_NONFINITE = 2    # failed the finite screen
REASON_NORM = 3         # exceeded the norm-screen threshold
REASON_NAMES = {REASON_OK: "ok", REASON_DROPPED: "dropped",
                REASON_NONFINITE: "nonfinite", REASON_NORM: "norm_exceeded"}


def forensic_stats(global_vars: ModelVars, new_vars: ModelVars,
                   recv_deltas: ModelVars, survivor_mask: jax.Array,
                   reason: jax.Array, oracle_calls) -> ForensicStats:
    """Assemble the per-client forensics pytree (jit-traced).

    `recv_deltas` are the deltas the SERVER received (post-fault); the
    cosine compares each against the update the server actually APPLIED
    (new - old params), which works uniformly across all three aggregation
    rules (and yields 0 for a degraded round, where the update is zero).
    A NaN-corrupted row produces a NaN norm/cosine for that client only —
    rows are independent, so nothing leaks across clients."""
    recv_norms = jax.vmap(
        lambda d: tree_global_norm(d.params))(recv_deltas)
    pts = agg.flatten_stacked(recv_deltas.params)              # [C, P]
    upd = agg.flatten_stacked(jax.tree_util.tree_map(
        lambda n, g: (n - g)[None], new_vars.params,
        global_vars.params))[0]                                # [P]
    unorm = jnp.sqrt(jnp.sum(upd * upd))
    denom = jnp.maximum(recv_norms * unorm, 1e-12)
    cos = (pts @ upd) / denom
    return ForensicStats(recv_norms, cos, survivor_mask,
                         reason.astype(jnp.int32),
                         jnp.asarray(oracle_calls, jnp.int32))


def _per_client_finite(tree: Any) -> jax.Array:
    """[C] bool — every leaf entry of each client's stacked row is finite."""
    flags = None
    for l in jax.tree_util.tree_leaves(tree):
        f = jnp.all(jnp.isfinite(l.astype(jnp.float32))
                    .reshape(l.shape[0], -1), axis=1)
        flags = f if flags is None else flags & f
    return flags


def screen_client_updates(deltas: ModelVars, reported: jax.Array,
                          counted: jax.Array, norm_mult: jax.Array,
                          extra_trees=()):
    """The server-side delta validation/quarantine pass (jit-traced).

    Two screens over the stacked client payloads:
      finite — every entry of the delta (and any `extra_trees`, e.g. the
               FoolsGold gradient accumulators) must be finite;
      norm   — ‖Δ_params‖ must not exceed `norm_mult` × the median norm of
               the reported-and-finite counted clients. `norm_mult` is a
               TRACED scalar so round-level retries can escalate it without
               recompiling; <= 0 disables the norm screen (threshold = ∞).

    Returns (survivor_mask [C] bool, norms [C]). A client that never
    reported (`reported` False) is excluded regardless of screens; inert
    padding lanes (`counted` False) never enter the median.
    """
    finite = _per_client_finite(deltas)
    for t in extra_trees:
        finite = finite & _per_client_finite(t)
    norms = jax.vmap(lambda d: tree_global_norm(d.params))(deltas)
    valid = reported & finite & counted
    med = jnp.nanmedian(jnp.where(valid, norms, jnp.nan))
    thresh = jnp.where(norm_mult > 0, norm_mult * med, jnp.inf)
    return reported & finite & (norms <= thresh), norms


def model_health_stats(old_vars: Any, new_vars: Any):
    """The jitted half of the post-merge model-health sentinel: (all leaves
    of the committed model finite, global L2 norm of the applied update).
    One reduction pass over the tree — cheap relative to a round; callers
    jit it once and pay one scalar host sync per checked merge."""
    new_leaves = jax.tree_util.tree_leaves(new_vars)
    finite = jnp.asarray(True)
    sq = jnp.float32(0.0)
    for o, n in zip(jax.tree_util.tree_leaves(old_vars), new_leaves):
        if not jnp.issubdtype(n.dtype, jnp.floating):
            continue
        finite = finite & jnp.all(jnp.isfinite(n))
        d = (n - o).astype(jnp.float32)
        sq = sq + jnp.sum(d * d)
    return finite, jnp.sqrt(sq)


class HealthSentinel:
    """Post-merge model-health gate shared by both engines
    (``model_health_check``): an unhealthy merge is one whose committed
    model has a non-finite leaf, or — once ``warmup`` healthy merges have
    seeded the trailing EMA — whose update norm exceeds ``band`` × that
    EMA (``health_norm_band``; 0 keeps only the finite check). Healthy
    commits feed the EMA and a last-good ring of up to ``ring_size``
    in-memory model versions; ``rollback_target`` hands back the newest
    ring entry (or the caller's pre-merge fallback when the ring is off or
    still empty). The ring is in-memory only — a resumed run restarts it
    from its first healthy merge, while (ema, merges) ride the async aux
    sidecar via state()/load_state() so the band re-arms deterministically."""

    def __init__(self, band: float, ema_alpha: float, warmup: int,
                 ring_size: int):
        self.band = float(band)
        self.alpha = float(ema_alpha)
        self.warmup = int(warmup)
        self.ring_size = int(ring_size)
        self.ema = 0.0
        self.merges = 0
        self.ring: List[Tuple[int, Any]] = []  # (version, model vars)
        self._fn = jax.jit(model_health_stats)

    def check(self, old_vars: Any, new_vars: Any) -> Tuple[bool, float]:
        """(healthy, update_norm) for one candidate merge — one host sync."""
        finite, norm = jax.device_get(self._fn(old_vars, new_vars))
        healthy = bool(finite)
        if (healthy and self.band > 0 and self.merges >= max(1, self.warmup)
                and self.ema > 0):
            healthy = float(norm) <= self.band * self.ema
        return healthy, float(norm)

    def commit(self, version: int, new_vars: Any, norm: float) -> None:
        """Record one healthy committed merge: advance the EMA and push the
        model onto the last-good ring."""
        self.merges += 1
        self.ema = (norm if self.merges == 1
                    else self.alpha * norm + (1.0 - self.alpha) * self.ema)
        if self.ring_size > 0:
            self.ring.append((int(version), new_vars))
            if len(self.ring) > self.ring_size:
                self.ring.pop(0)

    def rollback_target(self, fallback: Any) -> Any:
        return self.ring[-1][1] if self.ring else fallback

    def state(self) -> Dict[str, Any]:
        return {"ema": float(self.ema), "merges": int(self.merges)}

    def load_state(self, st: Optional[Dict[str, Any]]) -> None:
        if st:
            self.ema = float(st.get("ema", 0.0))
            self.merges = int(st.get("merges", 0))


class AggregateResult(NamedTuple):
    new_vars: ModelVars
    new_fg_state: agg.FoolsGoldState
    wv: jax.Array                 # [C] aggregation weights (RFA/FoolsGold)
    alpha: jax.Array              # [C] RFA distances / FoolsGold alphas
    num_oracle_calls: jax.Array   # RFA oracle counter (1 otherwise)
    is_updated: jax.Array         # bool — False iff RFA's max_update_norm
                                  # rejected the round (helper.py:360-369)


class LocalEvals(NamedTuple):
    """Per-client local-model eval rows (all [C]): reference CSV parity.
    clean/pre-scale rows evaluate the unscaled model (image_train.py:150-164
    runs Mytest/Mytest_poison BEFORE scaling); post rows the submitted one."""
    clean: EvalResult             # test_result rows (image_train.py:268-271)
    poison_pre: EvalResult        # posiontest_result pre-scale (:157-164)
    poison_post: EvalResult       # posiontest_result post-scale (:275-282)
    agent_trigger: EvalResult     # poisontriggertest_result (:291-295)


class GlobalEvals(NamedTuple):
    clean: EvalResult             # Mytest(global) (main.py:198-201)
    poison: EvalResult            # Mytest_poison(global) (main.py:207-215)
    per_trigger: EvalResult       # [T] rows (main.py:225-231)


@dataclasses.dataclass
class EvalPlans:
    """Device-resident eval index plans, built once per experiment."""
    clean_idx: jax.Array      # [S, B]
    clean_slots: jax.Array
    clean_mask: jax.Array
    poison_idx: jax.Array     # [S', B] — target-label samples dropped
    poison_slots: jax.Array
    poison_mask: jax.Array


def make_local_battery(model_def: ModelDef, data: DeviceData,
                       plans: EvalPlans, is_poison_run: bool, baseline: bool,
                       clean_jobs: bool):
    """battery(unscaled [C, ...], scaled [C, ...], tasks ([I, C] rows of the
    segments whose flags gate the poison parts), forensics) -> LocalEvals
    with [C] leaves: clean on the pre-scaling model (image_train.py:150-155,
    :268-271), poison pre on it (:157-164), poison post + per-agent trigger
    on the submitted one (:275-282, :291-295).

    Clean part: every client's row is recorded. With `clean_jobs` (the
    engine's `lanes_as_jobs`: an unsharded model with a convolution, where
    a C-model `vmap` step costs more than C single-model steps) the C models
    go one after another through the single-model test, a loop of static
    length C around `make_eval_fn`'s scan over the clean plan. Without it
    (dense layers, or a sharded clients axis) they share ONE eval plan under
    `make_stacked_eval_fn`'s `vmap`, the batch fetch hoisted out of it (one
    gather per batch instead of C). Either way one loop that reads the test
    set, beside the poison parts': the executable carries the test images
    once a loop, and a conditional between the two tests inside one job
    loop carried them four times more (PR 36, on the chip).

    Poison parts: only a poisoning client's model is tested on poisoned
    data, so they run as a list of single-model jobs, one per row the
    recorder writes (`local_battery_jobs`), read from the round's tasks
    inside the program: job id = part * C + lane, the loop's trip count the
    number of jobs (0 in a clean round), a slot with no job left at zeros
    (count 0). The eval inside a job is a scan of static length over the
    poison plan."""
    eval_clean_s = make_stacked_eval_fn(model_def, data)
    eval_clean = make_eval_fn(model_def, data, poison=False)
    eval_poison = make_eval_fn(model_def, data, poison=True)
    clean_plan = (plans.clean_idx, plans.clean_slots, plans.clean_mask)

    def poison_jobs(unscaled: ModelVars, scaled: ModelVars, adv_slots,
                    wanted):
        C = adv_slots.shape[0]
        order, n_jobs = job_order(jnp.stack(wanted).reshape(-1))

        def job(i, rows):
            j = order[i]
            part, lane = j // C, j % C
            model = jax.tree_util.tree_map(
                lambda u, s: jnp.where(part == 0, u[lane], s[lane]),
                unscaled, scaled)
            r = eval_poison(model, plans.poison_idx, plans.poison_slots,
                            plans.poison_mask,
                            jnp.where(part == 2, adv_slots[lane], -1))
            return jax.tree_util.tree_map(lambda row, v: row.at[j].set(v),
                                          rows, r)

        rows = jax.lax.fori_loop(
            0, n_jobs, job,
            EvalResult(*(jnp.zeros((3 * C,), jnp.float32),) * 4))
        return [jax.tree_util.tree_map(lambda l: l[k * C:(k + 1) * C], rows)
                for k in range(3)]

    def battery(unscaled: ModelVars, scaled: ModelVars, tasks: ClientTask,
                forensics: bool) -> LocalEvals:
        if clean_jobs:
            clean = jax.lax.map(
                lambda model: eval_clean(model, *clean_plan, jnp.int32(-1)),
                unscaled)
        else:
            clean = eval_clean_s(unscaled, *clean_plan)
        if is_poison_run:
            pre, post, agent = poison_jobs(
                unscaled, scaled, tasks.adv_slot[-1],
                local_battery_jobs(
                    tasks.poisoning_per_batch, tasks.adv_slot,
                    tasks.num_epochs, baseline, forensics))
        else:
            C = tasks.adv_slot.shape[1]
            pre = post = agent = EvalResult(
                *(jnp.zeros((C,), jnp.float32),) * 4)
        return LocalEvals(clean, pre, post, agent)

    return battery


def lanes_as_jobs(model_def: ModelDef, mesh) -> bool:
    """Whether the stacked round runs each lane's work as a single-model
    job and not under a `vmap` over the lanes: the one observation both the
    client step (`wide_from_of`) and the local battery's clean part
    (`make_local_battery`) adapt to. Read at build from the mesh and the
    model's parameter shapes alone: no knob, no model's name, nothing timed.

    - A sharded clients axis: no. A lane a device is the point there, and
      taking one lane out of a sharded stack is a collective nobody has
      priced.
    - Unsharded, a model with a convolution (some parameter leaf is a rank-4
      kernel): yes. `vmap` over the lanes turns each convolution into one
      with `lanes` sets of weights plus layout copies between the lanes and
      batch axes, and a full-width step costs more than `lanes` width-1
      steps, training (13.6-19.4 of them at 10 lanes) and evaluating (PERF.md
      section 7's tables).
    - Unsharded, dense layers only: no. Stacked lanes make a batched matmul
      the chip runs well (2.25 width-1 steps)."""
    if mesh is not None:
        return False
    shapes = jax.eval_shape(lambda: model_def.init_vars(jax.random.key(0)))
    return any(l.ndim == 4 for l in jax.tree_util.tree_leaves(shapes.params))


def wide_from_of(model_def: ModelDef, mesh, lanes: int) -> int:
    """The client step's `wide_from` (fl/client.py::split_steps): the number
    of live lanes from which a step runs at full width. Set here, at build,
    from the model's kind and `lanes_as_jobs` alone — no knob, and nothing
    timed: a job's steps and the full-width loop's are not bit-equal on the
    chip, so a program chosen by a stopwatch would move a run's numerics
    with the machine's noise.

    - A streamed model (`ModelDef.streamed`, fl/streamed.py): `lanes + 1`.
      Its round has no lanes to be wide over: the clients run one after
      another at width 1 by construction, and the number only tells the
      plan's counts that every real step is a client's own. The shapes are
      not asked: the rank-4 test never sees such a model (a short
      convolution's depthwise kernel is rank 2), and need not.
    - `lanes_as_jobs`: `lanes + 1`, every lane a job.
    - A sharded clients axis: 1, the full-width loop alone.
    - Unsharded, dense layers only: 2. Only one lane's tail leaves the
      full-width loop."""
    if model_def.streamed or lanes_as_jobs(model_def, mesh):
        return lanes + 1
    return 1 if mesh is not None else 2


class RoundEngine:
    """Holds the jitted round + eval computations for one experiment config.

    Two forms of the round program, chosen by the model's kind alone: the
    stacked round below (C clients at once on a clients axis), and for a
    model too large to stack (`ModelDef.streamed`) the streamed round of
    fl/streamed.py, whose feed adds the engine's `workspace` and the
    population (`round_workspace`).

    With a mesh, the stacked clients axis is sharded across devices (GSPMD via
    jit in_shardings): each device trains its clients locally and the
    aggregation reductions lower to ICI collectives (SURVEY §2.2)."""

    def __init__(self, params: cfg.Params, model_def: ModelDef,
                 data: DeviceData, plans: EvalPlans, mesh=None,
                 num_segments: int = 1,
                 local_plans: Optional[EvalPlans] = None):
        # one span around the whole host-side build (tracing the jit
        # wrappers is free — XLA compiles lazily on first call; those
        # compiles land in the xla/compiles counter via the monitoring
        # listener, not here)
        with telemetry.span("engine/build"):
            self._build(params, model_def, data, plans, mesh, num_segments,
                        local_plans or plans)

    def round_workspace(self, global_vars: ModelVars):
        """The streamed round's workspace: made at build, handed to every
        round and taken back from it (`self.workspace`); made again after
        `release_workspace`."""
        if self.workspace is None:
            from dba_mod_tpu.fl.streamed import make_workspace
            self.workspace = jax.jit(make_workspace)(global_vars)
        return self.workspace

    def release_workspace(self) -> None:
        """Give the workspace's device memory back (three copies of the
        model) while no round is in flight; the next round makes it anew."""
        if self.workspace is not None:
            jax.block_until_ready(self.workspace)
            for leaf in jax.tree_util.tree_leaves(self.workspace):
                leaf.delete()
            self.workspace = None

    def _build(self, params: cfg.Params, model_def: ModelDef,
               data: DeviceData, plans: EvalPlans, mesh,
               num_segments: int, local_plans: EvalPlans):
        self.params = params
        self.hyper = RoundHyper.from_params(params)
        self.model_def = model_def
        self.data = data
        self.plans = plans
        self.mesh = mesh
        self.num_segments = num_segments
        hyper = self.hyper
        fg_enabled = hyper.aggregation == cfg.AGGR_FOOLSGOLD
        # fault layer (fl/faults.py + the screening/quarantine pass below):
        # every flag is static, so with fault_injection off and screening
        # off the robust path is simply not traced
        self.fault_cfg = fcfg = flt.FaultConfig.from_params(params)
        screen = params.get("screen_updates", "auto")
        self.screening = fcfg.enabled if screen == "auto" else bool(screen)
        self.robust = fcfg.enabled or self.screening
        self.min_surviving = max(1, int(params.get("min_surviving_clients",
                                                   1)))
        self.base_norm_mult = float(params.get("screen_norm_mult", 0.0))
        screening, min_surv = self.screening, self.min_surviving
        # defense forensics (utils/forensics.py): static flag — when off,
        # nothing below is traced and the payload keeps a None in the
        # forensic slot, so the round program is bit-identical to pre-PR
        self.forensics = forensics_on = bool(params.get("forensics", False))
        # fused per-step updates: pallas multi-tensor kernels; sound only
        # when the clients axis is unsharded (GSPMD cannot partition a
        # custom call), so the mesh path keeps the per-leaf jnp form
        fu = params.get("fused_updates", "auto")
        fused_pallas = bool(fu) if fu != "auto" else (
            mesh is None and jax.default_backend() == "tpu")
        # a streamed model's round (fl/streamed.py) has no stacked lanes
        # for the kernel to fuse over
        self.streamed = bool(model_def.streamed)
        if self.streamed:
            from dba_mod_tpu.fl import streamed
            streamed.refuse(params, mesh, num_segments, self.robust,
                            forensics_on, hyper.track_batches)
            fused_pallas = False
        self.workspace = None
        self.fused_pallas = fused_pallas
        self.fused_interpret = bool(params.get("fused_interpret", False))
        # from how many live lanes a step runs at full width
        # (fl/client.py::split_steps); what runs below it is a lane's job
        self.wide_from = wide_from_of(model_def, mesh, hyper.no_models)
        # the same observation gives the local battery's clean part its
        # form (the streamed round has a battery of its own)
        self.clean_jobs = (not self.streamed
                           and lanes_as_jobs(model_def, mesh))
        segment_step = make_client_step(
            model_def, data, hyper, fg_enabled, fused_pallas=fused_pallas,
            fused_interpret=self.fused_interpret, wide_from=self.wide_from)
        eval_clean = make_eval_fn(model_def, data, poison=False)
        eval_poison = make_eval_fn(model_def, data, poison=True)
        is_poison_run = bool(params["is_poison"])

        def train_fn(global_vars: ModelVars, tasks_seq: ClientTask, idx_seq,
                     mask_seq, lane, rng) -> TrainResult:
            # tasks_seq leaves [I, C, ...]; idx/mask [I, C, E, S, B];
            # lane [C] — absolute lane index so per-client rng streams are
            # identical between the vmapped and sequential-debug paths
            n_seg, C = idx_seq.shape[0], idx_seq.shape[1]
            start = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(l, (C,) + l.shape), global_vars)
            benign_mom = jax.tree_util.tree_map(
                lambda l: jnp.zeros((C,) + l.shape), global_vars.params)
            fg_total = jax.tree_util.tree_map(
                lambda l: jnp.zeros((C,) + l.shape), global_vars.params)
            seg_metrics = []
            seg_bloss, seg_bdist = [], []
            seg_deltas = []
            for s in range(n_seg):  # static unroll; n_seg is 1 in practice
                seg_rng = jax.random.fold_in(rng, s)
                rngs = jax.vmap(
                    lambda i: jax.random.fold_in(seg_rng, i))(lane)
                tasks_s = jax.tree_util.tree_map(lambda l: l[s], tasks_seq)
                # the steps loops run only the steps some lane needs, below
                # `wide_from` live lanes as jobs at width 1: their trip
                # counts come from the mask, inside the program
                # (fl/client.py)
                res = segment_step(start, benign_mom, tasks_s, idx_seq[s],
                                   mask_seq[s], rngs)
                start = res.end_vars
                benign_mom = res.benign_mom
                if fg_enabled:
                    fg_total = jax.tree_util.tree_map(jnp.add, fg_total,
                                                      res.fg_grads)
                seg_metrics.append(res.metrics)
                seg_bloss.append(res.batch_loss)
                seg_bdist.append(res.batch_dist)
                if s < n_seg - 1:  # intermediate states feed per-epoch evals
                    seg_deltas.append(jax.tree_util.tree_map(
                        lambda e, g: e - g, start, global_vars))
            deltas = jax.tree_util.tree_map(lambda e, g: e - g, start,
                                            global_vars)
            fg_feature = jax.vmap(
                lambda t: model_def.similarity_param(t).reshape(-1))(fg_total)
            metrics = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *seg_metrics)
            delta_norms = jax.vmap(
                lambda d: tree_global_norm(d.params))(deltas)
            return TrainResult(deltas, fg_total, fg_feature, metrics,
                               delta_norms, jnp.stack(seg_bloss),
                               jnp.stack(seg_bdist), seg_deltas)

        def aggregate_fn(global_vars: ModelVars,
                         fg_state: agg.FoolsGoldState, deltas: ModelVars,
                         fg_grads, fg_feature, participant_ids, num_samples,
                         rng, nbt_deltas=None, mask=None) -> AggregateResult:
            # mask ([C], optional): survivor mask from the quarantine pass —
            # routes to the mask-aware rule variants; None is the dense path
            C = fg_feature.shape[0]
            wv = jnp.zeros((C,), jnp.float32)
            alpha = jnp.zeros((C,), jnp.float32)
            calls = jnp.int32(1)
            is_updated = jnp.asarray(True)
            new_fg = fg_state
            if hyper.aggregation == cfg.AGGR_MEAN:
                if mask is None:
                    new_vars = agg.fedavg_update(
                        global_vars, deltas, hyper.eta, hyper.no_models,
                        hyper.sigma if hyper.diff_privacy else 0.0, rng)
                else:
                    new_vars = agg.fedavg_update_masked(
                        global_vars, deltas, hyper.eta, hyper.no_models,
                        mask, num_samples > 0,
                        hyper.sigma if hyper.diff_privacy else 0.0, rng)
            elif hyper.aggregation == cfg.AGGR_GEO_MED:
                r = agg.geometric_median_update(
                    global_vars, deltas, num_samples, hyper.eta,
                    maxiter=hyper.geom_median_maxiter,
                    max_update_norm=hyper.max_update_norm,
                    dp_sigma=hyper.sigma if hyper.diff_privacy else 0.0,
                    rng=rng, nbt_deltas=nbt_deltas,
                    n_bn=count_bn_layers(global_vars.batch_stats),
                    mask=mask)
                new_vars, calls, wv, alpha = (r.new_state, r.num_oracle_calls,
                                              r.wv, r.distances)
                is_updated = r.is_updated
            elif hyper.aggregation == cfg.AGGR_KRUM:
                r = agg.krum_update(
                    global_vars, deltas, hyper.eta, hyper.krum_m,
                    hyper.krum_f, mask=mask,
                    dp_sigma=hyper.sigma if hyper.diff_privacy else 0.0,
                    rng=rng)
                # wv = applied selection weights; alpha records the Krum
                # scores (clipped into a plottable range — excluded
                # sentinels are ~1e35)
                new_vars = r.new_state
                wv = r.wv
                alpha = jnp.minimum(r.scores, jnp.float32(1e30))
            elif hyper.aggregation in (cfg.AGGR_TRIMMED_MEAN,
                                       cfg.AGGR_MEDIAN):
                if hyper.aggregation == cfg.AGGR_TRIMMED_MEAN:
                    r = agg.trimmed_mean_update(
                        global_vars, deltas, hyper.eta, hyper.trim_beta,
                        mask=mask,
                        dp_sigma=hyper.sigma if hyper.diff_privacy else 0.0,
                        rng=rng)
                else:
                    r = agg.coordinate_median_update(
                        global_vars, deltas, hyper.eta, mask=mask,
                        dp_sigma=hyper.sigma if hyper.diff_privacy else 0.0,
                        rng=rng)
                new_vars = r.new_state
                wv = r.wv  # uniform survivor weights (coordinate-wise
                # rules have no per-client scalar weight; alpha stays 0)
            else:  # foolsgold
                r = agg.foolsgold_update(
                    global_vars.params, fg_grads, fg_feature,
                    participant_ids, fg_state, hyper.eta, hyper.lr,
                    hyper.momentum, hyper.weight_decay,
                    use_memory=hyper.fg_use_memory, mask=mask)
                # BN stats are not aggregated by FoolsGold (the reference
                # steps an optimizer over named_parameters only,
                # helper.py:286-290)
                new_vars = ModelVars(r.new_params, global_vars.batch_stats)
                new_fg, wv, alpha = r.new_fg_state, r.wv, r.alpha
            return AggregateResult(new_vars, new_fg, wv, alpha, calls,
                                   is_updated)

        if mesh is not None:
            from dba_mod_tpu.parallel.mesh import (CLIENTS_AXIS,
                                                   client_sharding,
                                                   replicated_sharding)
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = replicated_sharding(mesh)
            cs = client_sharding(mesh)
            seg_cs = NamedSharding(mesh, P(None, CLIENTS_AXIS))
            # out_shardings must be pinned: without them XLA may return
            # constant-foldable outputs (e.g. the all-zero fg_grads tree when
            # FoolsGold is off) replicated, and aggregate_fn's P('clients')
            # in_shardings then reject them at the call boundary.
            out_shard = TrainResult(deltas=cs, fg_grads=cs, fg_feature=cs,
                                    metrics=seg_cs, delta_norms=cs,
                                    batch_loss=seg_cs, batch_dist=seg_cs,
                                    seg_deltas=[cs] * (num_segments - 1))
            self.train_fn = jax.jit(
                train_fn, in_shardings=(rep, seg_cs, seg_cs, seg_cs, cs,
                                        rep),
                out_shardings=out_shard)
            self.aggregate_fn = jax.jit(
                aggregate_fn,
                in_shardings=(rep, rep, cs, cs, cs, cs, cs, rep, cs))
        else:
            self.train_fn = jax.jit(train_fn)
            self.aggregate_fn = jax.jit(aggregate_fn)

        battery = make_local_battery(model_def, data, plans, is_poison_run,
                                     bool(params["baseline"]),
                                     self.clean_jobs)

        def _bc(s, leaf):
            """[C] → [C, 1, ...] for per-client scalars against [C, ...]."""
            return s.reshape((s.shape[0],) + (1,) * (leaf.ndim - 1))

        def local_evals(global_vars: ModelVars, deltas: ModelVars,
                        tasks_seq: ClientTask,
                        prev_deltas: ModelVars) -> LocalEvals:
            # `prev_deltas` anchors the final segment: the pre-scaling model
            # is (global + prev) + (Δ - prev)/scale — for interval=1 prev is
            # zero and this reduces to global + Δ/scale; for interval>1 it
            # divides only the FINAL segment's step by its scale (earlier
            # segments' contributions were already scaled when submitted).
            # The round-final poison rows gate on ALL the round's segments
            # (a client may poison epoch 3 of a (3,4) round), hence tasks_seq
            scale = tasks_seq.scale[-1]
            unscaled = jax.tree_util.tree_map(
                lambda g, p, d: g + p + (d - p) / _bc(scale, d),
                global_vars, prev_deltas, deltas)
            scaled = jax.tree_util.tree_map(lambda g, d: g + d, global_vars,
                                            deltas)
            return battery(unscaled, scaled, tasks_seq, forensics_on)

        if mesh is not None:
            from dba_mod_tpu.parallel.mesh import (client_sharding,
                                                   replicated_sharding,
                                                   segment_client_sharding)
            self.local_evals_fn = jax.jit(
                local_evals,
                in_shardings=(replicated_sharding(mesh),
                              client_sharding(mesh),
                              segment_client_sharding(mesh),
                              client_sharding(mesh)))
        else:
            self.local_evals_fn = jax.jit(local_evals)

        # Per-epoch local evals for aggr_epoch_interval > 1: the reference
        # runs the whole battery inside the per-global-epoch loop — clean +
        # pre-scaling poison in the poison branch (image_train.py:150-164),
        # clean for benign epochs (:268-271), post-scaling poison and the
        # per-agent trigger test (:273-295) — the final segment is covered by
        # local_evals above, intermediate segments here, with the same
        # LocalEvals battery per segment.
        def seg_local_evals(global_vars: ModelVars, seg_deltas,
                            tasks_seq: ClientTask):
            outs = []
            prev = None
            for s, cur in enumerate(seg_deltas):
                if prev is None:
                    prev = jax.tree_util.tree_map(jnp.zeros_like, cur)
                # live model of this segment: anchor (global + prev Δ) plus
                # this segment's step, unscaled for the pre rows
                unscaled = jax.tree_util.tree_map(
                    lambda g, p, c: g + p + (c - p) / _bc(
                        tasks_seq.scale[s], c),
                    global_vars, prev, cur)
                scaled = jax.tree_util.tree_map(
                    lambda g, c: g + c, global_vars, cur)
                # an intermediate segment's rows gate on its own flags
                outs.append(battery(
                    unscaled, scaled,
                    jax.tree_util.tree_map(lambda l: l[s:s + 1], tasks_seq),
                    False))
                prev = cur
            return outs

        if num_segments > 1:
            if mesh is not None:
                from dba_mod_tpu.parallel.mesh import (
                    client_sharding, replicated_sharding,
                    segment_client_sharding)
                self.seg_local_evals_fn = jax.jit(
                    seg_local_evals,
                    in_shardings=(replicated_sharding(mesh),
                                  [client_sharding(mesh)]
                                  * (num_segments - 1),
                                  segment_client_sharding(mesh)))
            else:
                self.seg_local_evals_fn = jax.jit(seg_local_evals)
        else:
            self.seg_local_evals_fn = None

        # Global per-trigger battery (main.py:225-231): centralized mode tests
        # each sub-pattern by index — only when `centralized_test_trigger` is
        # set (main.py:226) — distributed mode tests each adversary's pattern
        # (= its slot).
        if params.is_centralized_attack:
            n_triggers = (int(params["trigger_num"])
                          if bool(params["centralized_test_trigger"]) else 0)
        else:
            n_triggers = params.num_adversaries
        self.num_global_triggers = n_triggers
        trigger_ids = jnp.arange(max(n_triggers, 1), dtype=jnp.int32)

        def global_evals(model_vars: ModelVars) -> GlobalEvals:
            clean = eval_clean(model_vars, plans.clean_idx, plans.clean_slots,
                               plans.clean_mask, jnp.int32(-1))
            if is_poison_run:
                poison = eval_poison(model_vars, plans.poison_idx,
                                     plans.poison_slots, plans.poison_mask,
                                     jnp.int32(-1))
                if n_triggers > 0:
                    one = lambda t: eval_poison(
                        model_vars, plans.poison_idx, plans.poison_slots,
                        plans.poison_mask, t)
                    # a streamed model's triggers one after another: under
                    # `vmap` a `lax.cond` in the model (models/lfm2.py's
                    # expert layer) becomes a select that runs both paths
                    per_trigger = (jax.lax.map(one, trigger_ids)
                                   if model_def.streamed
                                   else jax.vmap(one)(trigger_ids))
                else:
                    zero = EvalResult(*(jnp.float32(0),) * 4)
                    per_trigger = jax.tree_util.tree_map(
                        lambda z: jnp.zeros((1,)), zero)
            else:
                zero = EvalResult(*(jnp.float32(0),) * 4)
                poison = zero
                per_trigger = jax.tree_util.tree_map(
                    lambda z: jnp.zeros((max(n_triggers, 1),)), zero)
            return GlobalEvals(clean, poison, per_trigger)

        self.global_evals_fn = jax.jit(global_evals)

        def backdoor_acc(model_vars: ModelVars) -> jax.Array:
            """Combined-trigger backdoor accuracy of the global model — feeds
            the LOAN adaptive poison LR (loan_train.py:67-75)."""
            r = eval_poison(model_vars, plans.poison_idx, plans.poison_slots,
                            plans.poison_mask, jnp.int32(-1))
            return r.acc

        self.backdoor_acc_fn = jax.jit(backdoor_acc)

        # Standalone batteries get telemetry spans with honest device-sync
        # points (fl/evaluation.py:instrument_eval) — a passthrough while
        # telemetry is off, so the fused/pipelined paths keep their deferred
        # sync. `batches` counts the eval-plan scan steps known when the
        # engine is built: for the local battery the clean plan's (stacked,
        # the C client models share each step's gather; as jobs every lane's
        # job walks them again). How many single-model jobs a round's local
        # battery runs varies with the round: the `round/plan` span counts
        # them (`battery_evals_run`, `battery_clean_jobs`).
        from dba_mod_tpu.fl.evaluation import instrument_eval
        clean_steps = int(plans.clean_idx.shape[0])
        poison_steps = int(plans.poison_idx.shape[0])
        local_batches = clean_steps
        global_batches = clean_steps + ((1 + n_triggers) * poison_steps
                                        if is_poison_run else 0)
        self.local_evals_fn = instrument_eval(
            self.local_evals_fn, "eval/local", batches=local_batches)
        if self.seg_local_evals_fn is not None:
            self.seg_local_evals_fn = instrument_eval(
                self.seg_local_evals_fn, "eval/seg_local",
                batches=(num_segments - 1) * local_batches)
        self.global_evals_fn = instrument_eval(
            self.global_evals_fn, "eval/global", batches=global_batches)
        self.backdoor_acc_fn = instrument_eval(
            self.backdoor_acc_fn, "eval/backdoor_probe",
            batches=poison_steps)

        # The whole round as ONE program: train → [inject faults → screen] →
        # aggregate → local evals → global evals. One dispatch, no
        # cross-program buffer boundaries (the separate fns above stay for
        # sequential_debug and for bench phase diagnostics). Returns
        # (new_vars, new_fg_state, payload) — payload ordered exactly as
        # Experiment.finalize_round unpacks it, with a RobustStats (or None)
        # in slot 9 and a ForensicStats (or None) in the last slot — the
        # robust dispatch's degraded-path payload surgery slices around
        # slot 1, so new slots must only ever be APPENDED. The robust
        # variant additionally takes
        # (rng_f, prev_deltas, norm_mult) and returns the submitted deltas
        # as a 4th output so the next round can replay them for the stale
        # fault lane (an empty tuple when staleness is off).
        do_local_eval = bool(params.get("local_eval", True))

        def _round(global_vars: ModelVars, fg_state, tasks_seq, idx_seq,
                   mask_seq, lane, num_samples, rng_t, rng_a,
                   rng_f=None, prev_deltas=(), norm_mult=None,
                   with_evals=True):
            robust = norm_mult is not None  # trace-time switch
            # the four `phase/` scopes name the round's device operations
            # in a profiler trace (metadata only: same compiled code)
            with jax.named_scope("phase/train"):
                train = train_fn(global_vars, tasks_seq, idx_seq, mask_seq,
                                 lane, rng_t)
            deltas, fg_grads = train.deltas, train.fg_grads
            fg_feature = train.fg_feature
            tasks_first = jax.tree_util.tree_map(lambda l: l[0], tasks_seq)
            with jax.named_scope("phase/aggregate"):
                nbt = nbt_client_deltas(mask_seq, tasks_seq.scale)
                stats = None
                fstats = None
                deltas_out = ()
                if robust:
                    counted = num_samples > 0
                    reported = jnp.ones_like(counted)
                    n_dropped = jnp.int32(0)
                    if fcfg.enabled:
                        plan = flt.make_fault_plan(fcfg, rng_f, counted)
                        stale = prev_deltas if fcfg.stale_enabled else None
                        deltas = flt.perturb_tree(deltas, plan, fcfg, stale)
                        if fg_enabled:
                            # FoolsGold aggregates the gradient accumulators,
                            # not the deltas — corrupt that payload too (stale
                            # replay stays delta-only; see faults.py docstring)
                            fg_grads = flt.perturb_tree(fg_grads, plan, fcfg)
                            fg_feature = flt.perturb_tree(fg_feature, plan,
                                                          fcfg)
                        reported = ~plan.dropped
                        n_dropped = jnp.sum(
                            plan.dropped & counted).astype(jnp.int32)
                    if fcfg.stale_enabled:
                        deltas_out = deltas  # what the server RECEIVED
                    if screening:
                        extra = (fg_grads,) if fg_enabled else ()
                        smask, _norms = screen_client_updates(
                            deltas, reported, counted, norm_mult, extra)
                    else:
                        # dropout is server-visible without any screening: a
                        # client that never reported cannot be aggregated
                        smask = reported
                    n_quar = jnp.sum(reported & ~smask
                                     & counted).astype(jnp.int32)
                    n_surv = jnp.sum(smask & counted).astype(jnp.int32)
                    degraded = n_surv < min_surv
                    res = aggregate_fn(global_vars, fg_state, deltas, fg_grads,
                                       fg_feature, tasks_first.participant_id,
                                       num_samples, rng_a, nbt,
                                       mask=smask.astype(jnp.float32))
                    # graceful degradation: too few survivors → skip the
                    # aggregate, carry the global model and defense state
                    new_vars = jax.tree_util.tree_map(
                        lambda g, a: jnp.where(degraded, g, a),
                        global_vars, res.new_vars)
                    new_fg = jax.tree_util.tree_map(
                        lambda o, n: jnp.where(degraded, o, n),
                        fg_state, res.new_fg_state)
                    gfin = jnp.asarray(True)
                    for l in jax.tree_util.tree_leaves(new_vars):
                        gfin = gfin & jnp.all(
                            jnp.isfinite(l.astype(jnp.float32)))
                    stats = RobustStats(n_dropped, n_quar, n_surv, degraded,
                                        gfin, smask)
                    res = res._replace(new_vars=new_vars, new_fg_state=new_fg)
                    if forensics_on:
                        # quarantine reason, consistent with the mask actually
                        # applied: never-reported → dropped; reported but
                        # screened out → nonfinite or norm_exceeded (screening
                        # off means smask == reported, so the middle branch is
                        # unreachable and `finite` is never consulted)
                        if screening:
                            finite = _per_client_finite(deltas)
                            for t in ((fg_grads,) if fg_enabled else ()):
                                finite = finite & _per_client_finite(t)
                        else:
                            finite = jnp.ones_like(smask)
                        reason = jnp.where(
                            ~reported, jnp.int32(REASON_DROPPED),
                            jnp.where(reported & ~smask,
                                      jnp.where(finite, jnp.int32(REASON_NORM),
                                                jnp.int32(REASON_NONFINITE)),
                                      jnp.int32(REASON_OK)))
                        fstats = forensic_stats(global_vars, new_vars, deltas,
                                                smask, reason,
                                                res.num_oracle_calls)
                else:
                    res = aggregate_fn(global_vars, fg_state, deltas, fg_grads,
                                       fg_feature, tasks_first.participant_id,
                                       num_samples, rng_a, nbt)
                    if forensics_on:
                        C = fg_feature.shape[0]
                        fstats = forensic_stats(
                            global_vars, res.new_vars, deltas,
                            jnp.ones((C,), bool), jnp.zeros((C,), jnp.int32),
                            res.num_oracle_calls)
            prev = (train.seg_deltas[-1] if num_segments > 1 else
                    jax.tree_util.tree_map(jnp.zeros_like, train.deltas))
            if with_evals:
                # the local battery evaluates what each client TRAINED
                # (faults model the uplink, not local training) — pre-fault
                # deltas
                with jax.named_scope("phase/local_battery"):
                    locals_ = (local_evals(global_vars, train.deltas,
                                           tasks_seq, prev)
                               if do_local_eval else None)
                    seg_l = (seg_local_evals(
                        global_vars, train.seg_deltas, tasks_seq)
                        if do_local_eval and num_segments > 1 else None)
                with jax.named_scope("phase/global_battery"):
                    globals_ = global_evals(res.new_vars)
            else:
                # overlap_eval's round CORE: the eval tail is stripped —
                # the dispatcher runs the SAME jitted batteries as separate
                # programs against the returned eval inputs, after the model
                # commit, so they overlap the next round's train dispatch
                locals_ = seg_l = globals_ = None
            track_pair = ((train.batch_loss, train.batch_dist)
                          if hyper.track_batches else None)
            payload = (locals_, globals_, train.metrics, train.delta_norms,
                       res.wv, res.alpha, track_pair, res.is_updated, seg_l,
                       stats, fstats)
            if not with_evals:
                # everything the stripped batteries need that only exists
                # inside the program: the PRE-fault deltas (the local
                # battery's input even on the robust path), the final
                # segment's anchor, and the per-segment deltas
                eval_in = (train.deltas, prev, tuple(train.seg_deltas))
                if robust:
                    return (res.new_vars, res.new_fg_state, payload,
                            deltas_out, eval_in)
                return res.new_vars, res.new_fg_state, payload, eval_in
            if robust:
                return res.new_vars, res.new_fg_state, payload, deltas_out
            return res.new_vars, res.new_fg_state, payload

        def round_fn(global_vars: ModelVars, fg_state, tasks_seq, idx_seq,
                     mask_seq, lane, num_samples, rng_t, rng_a):
            return _round(global_vars, fg_state, tasks_seq, idx_seq,
                          mask_seq, lane, num_samples, rng_t, rng_a)

        def round_fn_robust(global_vars: ModelVars, fg_state, tasks_seq,
                            idx_seq, mask_seq, lane, num_samples, rng_t,
                            rng_a, rng_f, prev_deltas, norm_mult):
            return _round(global_vars, fg_state, tasks_seq, idx_seq,
                          mask_seq, lane, num_samples, rng_t, rng_a,
                          rng_f, prev_deltas, norm_mult)

        # The round CORE for the overlap_eval scheduler: train → [faults →
        # screen] → aggregate, with the eval tail stripped and the eval
        # inputs returned instead. Snapshot contract: the core must NOT
        # donate (or otherwise alias) its input buffers — the overlapped
        # eval batteries read the RETAINED pre-round global_vars and the
        # returned delta snapshots after round N+1's core has already been
        # enqueued against the new model.
        def core_fn(global_vars: ModelVars, fg_state, tasks_seq, idx_seq,
                    mask_seq, lane, num_samples, rng_t, rng_a):
            return _round(global_vars, fg_state, tasks_seq, idx_seq,
                          mask_seq, lane, num_samples, rng_t, rng_a,
                          with_evals=False)

        def core_fn_robust(global_vars: ModelVars, fg_state, tasks_seq,
                           idx_seq, mask_seq, lane, num_samples, rng_t,
                           rng_a, rng_f, prev_deltas, norm_mult):
            return _round(global_vars, fg_state, tasks_seq, idx_seq,
                          mask_seq, lane, num_samples, rng_t, rng_a,
                          rng_f, prev_deltas, norm_mult, with_evals=False)

        if self.streamed:
            # the same round for a model that cannot be stacked: the
            # clients one after another inside one program, the workspace
            # (argument 2) donated with the state
            round_fn = streamed.make_streamed_round(
                model_def, data, hyper, plans, local_plans, global_evals,
                is_poison_run, bool(params["baseline"]), do_local_eval)
            self.round_fn = jax.jit(round_fn)
            self.core_fn = None
            self.round_fn_donated = (
                jax.jit(round_fn, donate_argnums=(0, 1, 2))
                if jax.default_backend() != "cpu" else None)
            self.forensic_fn = None
            return

        if mesh is not None:
            from dba_mod_tpu.parallel.mesh import (client_sharding,
                                                   replicated_sharding,
                                                   segment_client_sharding)
            rep2 = replicated_sharding(mesh)
            cs2 = client_sharding(mesh)
            seg_cs2 = segment_client_sharding(mesh)
            # out_shardings: the new global/defense state stays replicated
            # (it feeds the next round's rep in_shardings), and the small
            # metrics payload is replicated so finalize_round's device_get
            # is host-local on EVERY process of a multi-host run
            base_in = (rep2, rep2, seg_cs2, seg_cs2, seg_cs2, cs2, cs2,
                       rep2, rep2)
            # the eval-input snapshot trio (deltas, prev anchor, seg deltas)
            # keeps the client sharding the eval batteries expect
            eval_out = (cs2, cs2, cs2)
            if self.robust:
                self.round_fn = jax.jit(
                    round_fn_robust,
                    in_shardings=base_in + (rep2, cs2, rep2),
                    out_shardings=(rep2, rep2, rep2, cs2))
                self.core_fn = jax.jit(
                    core_fn_robust,
                    in_shardings=base_in + (rep2, cs2, rep2),
                    out_shardings=(rep2, rep2, rep2, cs2, eval_out))
            else:
                self.round_fn = jax.jit(
                    round_fn, in_shardings=base_in,
                    out_shardings=(rep2, rep2, rep2))
                self.core_fn = jax.jit(
                    core_fn, in_shardings=base_in,
                    out_shardings=(rep2, rep2, rep2, eval_out))
        else:
            self.round_fn = jax.jit(round_fn_robust if self.robust
                                    else round_fn)
            self.core_fn = jax.jit(core_fn_robust if self.robust
                                   else core_fn)

        # Donation gate (snapshot/donation contract): the fused round is the
        # LAST reader of its (global_vars, fg_state) buffers on the
        # steady-state non-robust path, so on non-CPU backends a donated
        # twin lets XLA reuse those buffers in place — model-sized headroom
        # per round. Three exclusions, each load-bearing:
        #   * CPU: buffers are host RAM — aliasing saves nothing and XLA:CPU
        #     donation is the one backend where it has historically been
        #     fragile, so the gate stays off (tier-1 runs are CPU);
        #   * robust: the retry loop re-runs the program with the SAME
        #     captured inputs, which donation would have invalidated;
        #   * core_fn/overlap: the overlapped eval batteries read the
        #     retained pre-round buffers AFTER the next core is enqueued —
        #     the core never donates (see core_fn above).
        # Experiment-side contract: route through round_fn_donated only when
        # no health sentinel is armed (its check/rollback re-reads the
        # pre-round model), and warm calls must pass copies.
        self.round_fn_donated = None
        if not self.robust and jax.default_backend() != "cpu":
            if mesh is not None:
                self.round_fn_donated = jax.jit(
                    round_fn, in_shardings=base_in,
                    out_shardings=(rep2, rep2, rep2),
                    donate_argnums=(0, 1))
            else:
                self.round_fn_donated = jax.jit(round_fn,
                                                donate_argnums=(0, 1))

        # Split-path forensics (sequential_debug — the robust path is never
        # split): the same ForensicStats
        # as its own tiny jitted program, called by _finish_split_round with
        # an all-ones mask (no screening on the split path). None when
        # forensics is off so the split payload keeps its None slot.
        def forensic_fn(global_vars: ModelVars, new_vars: ModelVars,
                        deltas: ModelVars, oracle_calls) -> ForensicStats:
            C = jax.tree_util.tree_leaves(deltas)[0].shape[0]
            return forensic_stats(global_vars, new_vars, deltas,
                                  jnp.ones((C,), bool),
                                  jnp.zeros((C,), jnp.int32), oracle_calls)

        if not forensics_on:
            self.forensic_fn = None
        elif mesh is not None:
            from dba_mod_tpu.parallel.mesh import (client_sharding,
                                                   replicated_sharding)
            rep3 = replicated_sharding(mesh)
            self.forensic_fn = jax.jit(
                forensic_fn,
                in_shardings=(rep3, rep3, client_sharding(mesh), rep3))
        else:
            self.forensic_fn = jax.jit(forensic_fn)
