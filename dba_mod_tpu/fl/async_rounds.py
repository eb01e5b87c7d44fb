"""Buffered-asynchronous federation: the FedBuff-style streaming engine.

The synchronous engine (fl/experiment.py) is a barrier per round: select C
clients, train them in one vmapped program, aggregate, evaluate. The ROADMAP
north star is a service absorbing updates as they arrive; this module is the
buffered-asynchronous middle point of Nguyen et al., *Federated Learning
with Buffered Asynchronous Aggregation* (AISTATS 2022): the server admits
client updates continuously, buffers them, and merges every K arrivals with
a staleness-weighted partial-participation rule.

Shape of the simulation (single-controller, deterministic per seed):

  - Client work is dispatched in *cohorts* ("waves") through the SAME
    jitted ``engine.train_fn`` program the lockstep rounds run — one wave
    per selection epoch, trained against the global model current at
    dispatch. A wave's lanes then become individual *arrivals*, each with a
    service delay drawn from the arrival process below; a new wave is
    dispatched whenever the arrival queue drains, so stragglers from
    earlier cohorts interleave with later cohorts and accumulate staleness.
  - The arrival process is a pure function of ``(random_seed, wave)``:
    Exp(1/arrival_rate) service times, optional lognormal jitter
    (``arrival_jitter``), and a straggler tail (``straggler_tail`` fraction
    delayed by ``straggler_factor``). Virtual time — merge ORDER is what
    matters; no wall-clock sleeps.
  - Every K arrivals (``buffer_k``; 0 ⇒ no_models) the buffer is merged by
    a jitted partial-participation rule reusing the survivor-mask contract
    of ops/aggregation.py: occupancy is a mask, the buffer is padded with
    inert zero-delta lanes to the static K, so occupancy < K (the final
    flush of a gracefully stopped run) compiles to the same program shape.
  - Staleness of a buffered update = merges applied since its wave was
    dispatched. ``staleness_weighting``: "none" (static no-op branch — the
    weight multiply is not even traced, keeping the sync reduction
    bit-exact), "polynomial" w(s) = (1+s)^-staleness_alpha (the FedBuff
    paper's choice), or "exponential" w(s) = staleness_alpha^s.
  - Faults (fl/faults.py) become arrival-process events: the same
    deterministic per-epoch plan f(fault_seed, wave_epoch) is drawn, but a
    *dropped* client never arrives, a *stale* client becomes a straggler
    (its arrival is delayed by ``straggler_factor`` — the streaming
    generalization of the lockstep lane's replay-last-round model), and
    *corrupt*/*blowup* perturb the payload in transit; when
    ``screen_updates`` is on, the merge screens the buffer and quarantines
    via the mask. Host-loss lanes are a lockstep/multi-process concept and
    are ignored here (the driver is single-controller).

Sync-reduction guarantee (the keystone parity artifact,
tests/test_async_rounds.py): with ``buffer_k == no_models`` a merge fires
exactly when a full wave has arrived and the next wave is dispatched only
after the merge — the cadence, RNG stream consumption, train program,
masked-FedAvg divisor, and eval batteries all reduce to the synchronous
round, and the recorded metrics.jsonl rows are bit-identical (modulo wall
times and the async-only keys). This holds for ANY arrival knobs: arrival
order within a wave cannot matter because the merge sorts its buffer by
(wave, lane).

Known deviations from the lockstep engine (documented, not silent):
  - DP noise draws use the newest merged wave's aggregation key — merges
    are not 1:1 with waves in general, so the sync noise stream cannot be
    reproduced for K != C (it IS reproduced at K == C).
  - The LOAN adaptive poison-LR probe never blocks the stream: it always
    uses the last *finalized* backdoor accuracy (the ``stale_poison_probe``
    behavior), one merge stale.
  - Per-batch visualization channels (vis_train_batch_loss /
    batch_track_distance) are not recorded in async mode.
  - Leftover buffered updates at the end of a run are discarded (counted
    in telemetry as ``async/unmerged_leftovers``); a graceful stop flushes
    the partial buffer as one final padded merge instead.

Checkpoint/resume: the full streaming state (version, wave counter, virtual
clock, arrival heap, buffer, and the delta payloads of every wave still
referenced) rides the PR-4 aux sidecar under the ``async_state`` key —
``kill -9`` between merges resumes bit-exactly from the last committed
merge (tests/test_async_rounds.py).

Self-healing layer (README "Self-healing federation"; every knob a strict
bit-identical no-op at its default):

  - ``merge_timeout_v`` + ``merge_min_k``: a merge fires on K arrivals OR
    when the oldest buffered update has waited past the virtual-time
    deadline with at least ``merge_min_k`` buffered — the padded partial
    merge is the same compiled program shape.
  - ``starvation_policy``: what 200 consecutive empty cohorts means —
    "abort" (the pre-existing RuntimeError), "carry" (record a degraded
    no-op step and keep going), "wait" (keep drawing cohorts; the
    watchdog is the backstop). Starved cohorts are counted either way.
  - ``max_outstanding_waves``: admission control — with the watermark hit
    and mergeable updates buffered, the driver flushes a partial merge
    instead of dispatching another cohort. ``arrival_ttl_v`` expires heap
    entries whose service delay exceeded the TTL; they never reach the
    buffer.
  - ``model_health_check``: the shared HealthSentinel (fl/rounds.py) gates
    every commit — an unhealthy merge re-merges the SAME buffer with
    escalated screening up to ``max_round_retries`` (the async analog of
    the sync retry loop; the escalation never recompiles because
    norm_mult is a traced scalar), then rolls back to the last-good ring
    (``rollback_ring``) and records the step degraded.
  - ``min_surviving_clients``: a merge whose screen leaves fewer
    survivors skips aggregation inside the jitted merge (the same
    jnp.where carry as the sync round) and records the step degraded.
"""
from __future__ import annotations

import dataclasses
import heapq
import logging
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dba_mod_tpu import config as cfg
from dba_mod_tpu.data import build_batch_plan
from dba_mod_tpu.fl import faults as flt
from dba_mod_tpu.fl.rounds import (count_bn_layers, nbt_client_deltas,
                                   screen_client_updates)
from dba_mod_tpu.fl.selection import select_agents
from dba_mod_tpu.fl.state import build_client_tasks
from dba_mod_tpu.ops import aggregation as agg

logger = logging.getLogger("async_rounds")

# consecutive empty cohorts before the stream counts as starved and
# starvation_policy decides (abort / wait / carry). Module-level so tests
# can starve cheaply; the production value is deliberately generous — a
# fault plan has to zero out 200 cohorts in a row before we give up
STARVATION_LIMIT = 200


def staleness_weights(staleness: np.ndarray, weighting: str,
                      alpha: float) -> np.ndarray:
    """w(s) per buffered update, f32. "none" ⇒ ones (the caller's static
    branch skips the multiply entirely; this exists for unit tests and the
    recorded histogram), "polynomial" ⇒ (1+s)^-alpha (FedBuff §5),
    "exponential" ⇒ alpha^s."""
    s = np.asarray(staleness, np.float32)
    if weighting == "none":
        return np.ones_like(s)
    if weighting == "polynomial":
        return (1.0 + s) ** np.float32(-alpha)
    if weighting == "exponential":
        return np.float32(alpha) ** s
    raise ValueError(f"unknown staleness_weighting {weighting!r}")


class ArrivalProcess:
    """Deterministic per-(seed, wave) service delays for a cohort's lanes.

    Draws are a pure function of ``SeedSequence((seed, wave))`` — a resumed
    run (or a re-run on another host) replays the identical arrival plan,
    which the determinism test pins."""

    def __init__(self, seed: int, rate: float, jitter: float,
                 straggler_tail: float, straggler_factor: float):
        if rate <= 0:
            raise ValueError(f"arrival_rate must be > 0, got {rate}")
        self.seed = int(seed)
        self.rate = float(rate)
        self.jitter = float(jitter)
        self.straggler_tail = float(straggler_tail)
        self.straggler_factor = float(straggler_factor)

    def delays(self, wave: int, n: int) -> np.ndarray:
        rng = np.random.default_rng(
            np.random.SeedSequence((self.seed, int(wave))))
        d = rng.exponential(1.0 / self.rate, size=n)
        if self.jitter > 0:
            d = d * rng.lognormal(0.0, self.jitter, size=n)
        if self.straggler_tail > 0:
            tail = rng.random(n) < self.straggler_tail
            d = np.where(tail, d * self.straggler_factor, d)
        return d.astype(np.float64)


@dataclasses.dataclass
class _Wave:
    """One dispatched cohort: device-resident payloads + host metadata kept
    until every lane is consumed (merged or dropped) and its per-client
    rows are recorded."""
    wave: int                    # 0-based cohort counter
    epoch: int                   # wave+1 — selection/poison-schedule epoch
    base_version: int            # merge count at dispatch (staleness base)
    names: List[Any]
    adv_names: List[Any]
    tasks: Any                   # host-side ClientTask (np leaves)
    deltas: Any                  # [C] stacked ModelVars tree (post-fault)
    nbt: jax.Array               # [C] num_batches_tracked deltas
    num_samples: np.ndarray      # [C] f32
    pids: np.ndarray             # [C] i32
    rng_agg: jax.Array           # this wave's aggregation key
    metrics_dev: Any             # TrainResult.metrics handles (or np, post-resume)
    locals_dev: Any              # LocalEvals handles or None
    delta_norms: Any             # [C] device/np
    outstanding: int             # lanes not yet consumed
    recorded: bool = False
    t_dispatch: float = 0.0      # virtual clock at dispatch (arrival_ttl_v)


@dataclasses.dataclass
class _MergeInFlight:
    """One dispatched-but-unfinalized merge (overlap_eval's async analog of
    experiment.RoundInFlight): device handles of the merge outputs + every
    host value finalize needs, captured at dispatch time — by finalize time
    the live driver state (version, clock, heap, RNG streams, global model)
    already belongs to the NEXT step's fill."""
    step: int
    t0: float                    # perf_counter at dispatch start
    globals_dev: Any
    wv: Any
    alpha: Any
    is_updated: Any
    n_quar: Any
    degr: Any
    names: List[Any]
    adversaries: List[Any]
    staleness: np.ndarray
    occupancy: int
    retries: int
    rolled_back: bool
    n_dropped: int
    dispatch_wall: float
    extras: Dict[str, Any]
    entries: List[Tuple[int, int]]
    rows: List[_Wave]            # cohorts resolved since the previous merge,
    # in resolution order — finalize replays them before the merge rows
    t_dispatch_end: float = 0.0
    # checkpoint capture (run() only): the streaming sidecar + model/RNG
    # state at dispatch — what save_model must persist for THIS step
    snapshot: Optional[Dict[str, Any]] = None
    vars_after: Any = None
    fg_after: Any = None
    rng_after: Optional[Dict[str, Any]] = None


class AsyncDriver:
    """The persistent buffered-async server loop over one Experiment."""

    def __init__(self, exp):
        p = exp.params
        if jax.process_count() > 1:
            raise ValueError("mode: async is single-controller only")
        if exp.mesh is not None:
            raise ValueError(
                "mode: async does not support a sharded clients mesh yet "
                "(set num_devices: 0); the wave train program is "
                "single-device in this version")
        if exp.sequential_debug:
            raise ValueError("mode: async is incompatible with "
                             "sequential_debug")
        self.exp = exp
        self.C = int(p["no_models"])
        self.K = int(p.get("buffer_k", 0) or 0) or self.C
        self.weighting = str(p.get("staleness_weighting", "none"))
        self.alpha = float(p.get("staleness_alpha", 0.5))
        self.arrivals = ArrivalProcess(
            seed=int(p.get("random_seed") or 0),
            rate=float(p.get("arrival_rate", 1.0)),
            jitter=float(p.get("arrival_jitter", 0.0)),
            straggler_tail=float(p.get("straggler_tail", 0.0)),
            straggler_factor=float(p.get("straggler_factor", 10.0)))
        if bool(p.get("vis_train_batch_loss")) or bool(
                p.get("batch_track_distance")):
            logger.warning("async mode does not record per-batch channels; "
                           "vis_train_batch_loss/batch_track_distance rows "
                           "will be absent")
        # self-healing knobs (README "Self-healing federation") — every
        # default is a strict bit-identical no-op
        self.merge_timeout_v = float(p.get("merge_timeout_v", 0.0))
        self.merge_min_k = int(p.get("merge_min_k", 1))
        self.starvation_policy = str(p.get("starvation_policy", "abort"))
        self.max_outstanding = int(p.get("max_outstanding_waves", 0))
        self.arrival_ttl_v = float(p.get("arrival_ttl_v", 0.0))
        self._sentinel = exp._sentinel  # shared HealthSentinel or None
        # streaming state
        self.version = 0          # merges applied
        self.wave = 0             # cohorts dispatched
        self.clock = 0.0          # virtual time of the last consumed arrival
        self._seq = 0             # heap tie-break
        self._heap: List[Tuple[float, int, int, int]] = []  # (t, seq, wid, lane)
        self._buffer: List[Tuple[int, int]] = []            # (wid, lane)
        self._arrival_t: Dict[Tuple[int, int], float] = {}  # buffered → t
        self._waves: Dict[int, _Wave] = {}
        self._pending_dropped = 0
        self._dispatch_wall = 0.0
        self._total_arrivals = 0
        # self-healing observability (stats() — bench.py's --async lane)
        self._starved_cohorts = 0
        self._expired_arrivals = 0
        self._deadline_merges = 0
        self._backpressure_hits = 0
        self._rollbacks = 0
        self._waves_highwater = 0
        self._merge_latencies: List[float] = []
        # cohorts fully resolved (merged/dropped/expired) whose per-client
        # rows have not been written yet — drained into the next merge's
        # handle and replayed, in resolution order, at its finalize
        self._pending_rows: List[_Wave] = []
        # overlap_eval: pipeline each merge's host finalize (device fetch +
        # row recording + checkpoint) behind the NEXT step's fill/merge
        # compute. Gated off under telemetry (per-step span/epoch
        # attribution stays honest) and for poisoned LOAN runs (the
        # adaptive-LR probe reads last_backdoor_acc at wave dispatch, which
        # pipelining would make one more merge stale than the documented
        # deviation). Off ⇒ this module is a strict bit-identical no-op of
        # the serial driver; on, the recorded stream is byte-identical by
        # construction — finalize replays the deferred rows in resolution
        # order before anything later records.
        self._pipeline = (bool(p.get("overlap_eval", False))
                          and not exp.telemetry.enabled
                          and not (p.type == cfg.TYPE_LOAN
                                   and exp.is_poison_run))
        self._overlap_merges = 0
        self._overlap_hidden_s = 0.0
        self._merge_fn = self._build_merge_fn()
        fcfg = exp.engine.fault_cfg
        self._perturb_fn = (jax.jit(
            lambda tree, plan: flt.perturb_tree(tree, plan, fcfg))
            if fcfg.enabled else None)
        self._restore(exp._resume_aux)

    # ------------------------------------------------------------ merge rule
    def _build_merge_fn(self):
        """The jitted staleness-weighted partial-participation merge over
        the padded [K] buffer. Mirrors engine.aggregate_fn's rule dispatch
        but with the BUFFER as the participation unit: the masked-FedAvg
        divisor counts occupied surviving lanes out of K (so a full,
        unscreened buffer at K == no_models is bitwise the dense sync
        FedAvg — ops/aggregation.py's scale-rewrite), and every rule gets
        the occupancy/survivor mask. The staleness multiply is a STATIC
        branch: "none" traces no weighting ops at all."""
        exp = self.exp
        hyper = exp.engine.hyper
        screening = exp.engine.screening
        min_surv = int(exp.params.get("min_surviving_clients", 1))
        weighting = self.weighting
        K = self.K
        if hyper.aggregation == cfg.AGGR_FOOLSGOLD:  # config.py rejects too
            raise ValueError("foolsgold is stateful per-round and has no "
                             "buffered-async form; pick another rule")

        def merge(global_vars, deltas, nbt, ns, occ, w, rng, norm_mult):
            # deltas: [K] stacked tree; occ [K] bool occupancy; w [K] f32;
            # norm_mult a TRACED scalar so health re-merges escalate the
            # screen without recompiling (the sync retry-loop contract)
            if weighting != "none":
                deltas = jax.tree_util.tree_map(
                    lambda l: (l * agg._bc_mask(w, l)
                               if jnp.issubdtype(l.dtype, jnp.floating)
                               else l), deltas)
            mask = occ
            n_quar = jnp.int32(0)
            if screening:
                surv, _ = screen_client_updates(deltas, occ, occ, norm_mult)
                mask = occ & surv
                n_quar = jnp.sum((occ & ~surv).astype(jnp.int32))
            sigma = hyper.sigma if hyper.diff_privacy else 0.0
            wv = jnp.zeros((K,), jnp.float32)
            alpha = jnp.zeros((K,), jnp.float32)
            calls = jnp.int32(1)
            is_updated = jnp.asarray(True)
            if hyper.aggregation == cfg.AGGR_MEAN:
                # counted=ones ⇒ divisor = #surviving occupied lanes: the
                # partial flush is a true mean over present updates, and a
                # full unscreened buffer keeps the dense eta/K scale bitwise
                new_vars = agg.fedavg_update_masked(
                    global_vars, deltas, hyper.eta, K, mask,
                    jnp.ones((K,), bool), sigma, rng)
            elif hyper.aggregation == cfg.AGGR_GEO_MED:
                r = agg.geometric_median_update(
                    global_vars, deltas, ns, hyper.eta,
                    maxiter=hyper.geom_median_maxiter,
                    max_update_norm=hyper.max_update_norm,
                    dp_sigma=sigma, rng=rng, nbt_deltas=nbt,
                    n_bn=count_bn_layers(global_vars.batch_stats),
                    mask=mask)
                new_vars, calls, wv, alpha = (r.new_state,
                                              r.num_oracle_calls, r.wv,
                                              r.distances)
                is_updated = r.is_updated
            elif hyper.aggregation == cfg.AGGR_KRUM:
                r = agg.krum_update(global_vars, deltas, hyper.eta,
                                    hyper.krum_m, hyper.krum_f, mask=mask,
                                    dp_sigma=sigma, rng=rng)
                new_vars, wv = r.new_state, r.wv
                alpha = jnp.minimum(r.scores, jnp.float32(1e30))
            elif hyper.aggregation == cfg.AGGR_TRIMMED_MEAN:
                r = agg.trimmed_mean_update(global_vars, deltas, hyper.eta,
                                            hyper.trim_beta, mask=mask,
                                            dp_sigma=sigma, rng=rng)
                new_vars, wv = r.new_state, r.wv
            else:  # cfg.AGGR_MEDIAN
                r = agg.coordinate_median_update(global_vars, deltas,
                                                 hyper.eta, mask=mask,
                                                 dp_sigma=sigma, rng=rng)
                new_vars, wv = r.new_state, r.wv
            # min_surviving_clients skip-and-carry, the sync round's
            # degradation ported to the buffered merge: too few surviving
            # occupied lanes ⇒ the global model is carried unchanged
            # (jnp.where with a False scalar is a bitwise passthrough, so
            # the default min_surv=1 path stays bit-identical)
            n_surv = jnp.sum(mask.astype(jnp.int32))
            degraded = n_surv < jnp.int32(min_surv)
            new_vars = jax.tree_util.tree_map(
                lambda g, a: jnp.where(degraded, g, a), global_vars,
                new_vars)
            return (new_vars, wv, alpha, calls, is_updated, n_quar, n_surv,
                    degraded)

        return jax.jit(merge)

    # --------------------------------------------------------------- running
    def run(self, epochs: Optional[int] = None) -> Dict[str, Any]:
        """The persistent server loop: fill the buffer from the arrival
        queue (dispatching cohorts on demand), merge, record, checkpoint —
        until the merge budget is spent or a graceful stop lands."""
        exp = self.exp
        p = exp.params
        eps = int(epochs if epochs is not None else p["epochs"])
        total = int(p.get("async_steps", 0) or 0)
        if total <= 0:
            # same client-update budget as `epochs` sync rounds — at
            # K == C this is exactly `epochs` merges
            total = max(1, eps * self.C // self.K)
        last: Dict[str, Any] = {}
        # overlap_eval: hold ONE dispatched-but-unfinalized merge, so step
        # S's device fetch + row recording + checkpoint drain behind step
        # S+1's fill (wave training) and merge compute — the async analog
        # of the sync engine's depth-1 pipelined loop
        pending: Optional[_MergeInFlight] = None

        def _drain(p: Optional[_MergeInFlight]) -> Optional[Dict[str, Any]]:
            if p is None:
                return None
            r = self._finalize_merge(p)
            self._save_pending(p)
            exp.telemetry.mark_warm()
            logger.info(
                "merge %d/%d done acc=%.2f staleness_mean=%.2f "
                "occupancy=%d/%d", p.step, total, r["global_acc"],
                r["staleness_mean"], r["buffer_occupancy"], self.K)
            return r

        while self.version < total:
            if exp.guard.stop_requested:
                last = _drain(pending) or last
                pending = None
                if self._buffer:
                    # graceful stop: flush the partial buffer as one final
                    # padded merge (occupancy < K — same compiled shape)
                    last = self._merge_and_record()
                    self._save()
                exp.interrupted = True
                logger.warning(
                    "graceful stop honored at the merge boundary after "
                    "step %d (resume with --resume auto)", self.version)
                break
            if self._fill_buffer():
                if self._pipeline:
                    nxt = self._dispatch_merge(capture_save=True)
                    last = _drain(pending) or last
                    pending = nxt
                    continue
                last = self._merge_and_record()
            else:
                last = _drain(pending) or last
                pending = None
                last = self._carry_starved_step()
            self._save()
            exp.telemetry.mark_warm()
            logger.info(
                "merge %d/%d done acc=%.2f staleness_mean=%.2f "
                "occupancy=%d/%d", self.version, total, last["global_acc"],
                last["staleness_mean"], last["buffer_occupancy"], self.K)
        last = _drain(pending) or last
        leftovers = len(self._buffer) + len(self._heap)
        if leftovers and not exp.interrupted:
            exp.telemetry.counter("async/unmerged_leftovers").inc(leftovers)
            logger.info("run end: %d buffered/in-flight updates discarded "
                        "(budget of %d merges spent)", leftovers, total)
        return last

    def run_steps(self, n: int) -> Dict[str, Any]:
        """Run exactly n merges (bench.py's --async lane), no checkpoints.
        Under overlap_eval the merges are pipelined depth-1 exactly like
        run(); the trailing merge is drained before returning, so n calls
        leave no in-flight state behind."""
        last: Dict[str, Any] = {}
        pending: Optional[_MergeInFlight] = None
        for _ in range(n):
            if self._fill_buffer():
                if self._pipeline:
                    nxt = self._dispatch_merge()
                    if pending is not None:
                        last = self._finalize_merge(pending)
                    pending = nxt
                    continue
                last = self._merge_and_record()
            else:
                if pending is not None:
                    last = self._finalize_merge(pending)
                    pending = None
                last = self._carry_starved_step()
        if pending is not None:
            last = self._finalize_merge(pending)
        return last

    def stats(self) -> Dict[str, Any]:
        """Self-healing observability for bench.py's --async lane: p95
        virtual merge latency (arrival → merge, virtual seconds) plus the
        backpressure/starvation counters and the outstanding-waves
        high-water mark."""
        lat = sorted(self._merge_latencies)
        p95 = float(lat[int(0.95 * (len(lat) - 1))]) if lat else 0.0
        return {"merge_latency_v_p95": p95,
                "outstanding_waves_highwater": self._waves_highwater,
                "starved_cohorts": self._starved_cohorts,
                "expired_arrivals": self._expired_arrivals,
                "deadline_merges": self._deadline_merges,
                "backpressure_hits": self._backpressure_hits,
                "health_rollbacks": self._rollbacks,
                # overlap_eval: merges finalized one step late + host
                # seconds that ran behind the next step's compute
                "pipelined_merges": self._overlap_merges,
                "hidden_finalize_s": round(self._overlap_hidden_s, 6)}

    def _save(self):
        self.exp.save_model(self.version,
                            extra_aux={"async_state": self._snapshot()})

    # ------------------------------------------------------ arrivals / waves
    def _deadline_due(self) -> bool:
        """True when a merge_timeout_v deadline merge should fire: the
        oldest buffered update has waited past the deadline (>= merge_min_k
        buffered) and the next known arrival — if any — lands after it.
        Firing advances the virtual clock to the deadline instant."""
        if self.merge_timeout_v <= 0 or len(self._buffer) < self.merge_min_k:
            return False
        oldest = self._arrival_t.get(tuple(self._buffer[0]), self.clock)
        deadline = oldest + self.merge_timeout_v
        if self._heap and self._heap[0][0] < deadline:
            return False
        self.clock = max(self.clock, deadline)
        return True

    def _expire_arrival(self, t: float, wid: int) -> bool:
        """arrival_ttl_v: an update whose service delay exceeded the TTL is
        expired at pop time — it never reaches the buffer, its lane is
        freed, and a fully-resolved cohort is recorded immediately."""
        w = self._waves[wid]
        if t - w.t_dispatch <= self.arrival_ttl_v:
            return False
        self._expired_arrivals += 1
        self.exp.telemetry.counter("async/expired_arrivals").inc()
        w.outstanding -= 1
        if w.outstanding == 0 and not w.recorded:
            self._resolve_wave(w)
            del self._waves[wid]
        return True

    def _fill_buffer(self) -> bool:
        """Pop arrivals into the buffer until it holds K — or until a
        merge_timeout_v deadline or max_outstanding_waves backpressure
        flush fires a partial merge. Dispatches a new cohort whenever the
        queue drains; virtual time advances to each consumed arrival.
        Returns True when the buffer should be merged, False when the
        stream is starved and starvation_policy says to carry a no-op
        step."""
        exp = self.exp
        empty_waves = 0
        while len(self._buffer) < self.K:
            if self._deadline_due():
                self._deadline_merges += 1
                exp.telemetry.counter("async/deadline_merges").inc()
                return True
            while not self._heap:
                if (self.max_outstanding > 0 and self._buffer
                        and len(self._waves) >= self.max_outstanding):
                    # admission control: the watermark is hit and we hold
                    # mergeable updates — flush instead of dispatching
                    self._backpressure_hits += 1
                    exp.telemetry.counter("async/backpressure_hits").inc()
                    return True
                before = len(self._heap)
                self._dispatch_wave()
                if len(self._heap) == before:
                    empty_waves += 1
                    self._starved_cohorts += 1
                    exp.telemetry.counter("async/starved_cohorts").inc()
                    if empty_waves > STARVATION_LIMIT:
                        if self.starvation_policy == "carry":
                            if self._buffer:
                                return True  # flush what we hold
                            return False     # carry a degraded no-op step
                        if self.starvation_policy == "wait":
                            # keep drawing cohorts indefinitely; the
                            # watchdog (watchdog_hard_s) is the backstop
                            empty_waves = 0
                            continue
                        raise RuntimeError(
                            "async arrival queue starved: "
                            f"{STARVATION_LIMIT} consecutive cohorts "
                            "produced no arrivals (fault dropout too "
                            "aggressive?)")
                else:
                    empty_waves = 0
            t, _seq, wid, lane = heapq.heappop(self._heap)
            if self.arrival_ttl_v > 0 and self._expire_arrival(t, wid):
                continue
            self.clock = max(self.clock, t)
            self._buffer.append((wid, lane))
            self._arrival_t[(wid, lane)] = self.clock
            self._total_arrivals += 1
            exp.telemetry.counter("async/arrivals").inc()
            exp.telemetry.gauge("async/buffer_occupancy").set(
                len(self._buffer))
        return True

    def _dispatch_wave(self):
        """Select + train one cohort through the lockstep train program and
        enqueue its lanes as future arrivals. Consumes the selection/plan/
        train RNG streams exactly like a sync round dispatch — the parity
        anchor."""
        exp = self.exp
        p = exp.params
        wid = self.wave
        self.wave += 1
        epoch = wid + 1
        t0 = time.perf_counter()
        with exp.telemetry.span("async/dispatch_wave"):
            agent_names, adv_names = select_agents(
                p, epoch, exp.participants, exp.benign_names, exp.select_rng)
            backdoor_acc = None
            if (p.type == cfg.TYPE_LOAN and exp.is_poison_run
                    and any(p.adversary_slot_of(n) >= 0 and
                            epoch in p.poison_epochs_for(
                                p.adversary_slot_of(n))
                            for n in agent_names)):
                # never block the stream on a probe: one merge stale
                backdoor_acc = exp.last_backdoor_acc
            slots = np.array([exp.client_slots[n] for n in agent_names],
                             np.int64)
            tasks = build_client_tasks(p, agent_names, epoch, slots,
                                       exp.epochs_max, backdoor_acc)
            plan = build_batch_plan(
                [exp.client_indices[n] for n in agent_names],
                [int(e) for e in tasks.num_epochs], int(p["batch_size"]),
                exp.plan_rng, min_steps=exp.steps_per_epoch,
                min_epochs=exp.epochs_max)
            tasks_seq = jax.tree_util.tree_map(
                lambda l: jnp.asarray(l[None]), tasks)
            idx_seq = jnp.asarray(plan.idx[None])
            mask_seq = jnp.asarray(plan.mask[None])
            exp.rng_key, round_key = jax.random.split(exp.rng_key)
            rng_train, rng_agg = jax.random.split(round_key)
            lane = jnp.arange(len(agent_names), dtype=jnp.int32)
            train = exp.engine.train_fn(exp.global_vars, tasks_seq, idx_seq,
                                        mask_seq, lane, rng_train)
            nbt = nbt_client_deltas(mask_seq, tasks_seq.scale)
            locals_dev = None
            if exp.local_eval:
                prev = jax.tree_util.tree_map(jnp.zeros_like, train.deltas)
                locals_dev = exp.engine.local_evals_fn(
                    exp.global_vars, train.deltas, tasks_seq, prev)
            deltas = train.deltas
            dropped = np.zeros(len(agent_names), bool)
            delay_mult = np.ones(len(agent_names))
            fcfg = exp.engine.fault_cfg
            if fcfg.enabled:
                # faults as arrival events: same deterministic per-epoch
                # plan as the lockstep lanes — dropped never arrives, stale
                # straggles, corrupt/blowup perturb the payload in transit
                rng_f = jax.random.fold_in(exp._fault_key, epoch)
                fplan = flt.make_fault_plan(
                    fcfg, rng_f, jnp.ones((len(agent_names),), bool))
                fhost = jax.device_get(fplan)
                dropped = np.asarray(fhost.dropped)
                delay_mult = np.where(np.asarray(fhost.stale),
                                      self.arrivals.straggler_factor, 1.0)
                deltas = self._perturb_fn(deltas, fplan)
            self._pending_dropped += int(dropped.sum())
            delays = self.arrivals.delays(wid, len(agent_names)) * delay_mult
            for c in range(len(agent_names)):
                if dropped[c]:
                    continue
                heapq.heappush(self._heap,
                               (self.clock + float(delays[c]), self._seq,
                                wid, c))
                self._seq += 1
            self._waves[wid] = _Wave(
                wave=wid, epoch=epoch, base_version=self.version,
                names=list(agent_names), adv_names=list(adv_names),
                tasks=tasks, deltas=deltas, nbt=nbt,
                num_samples=plan.num_samples.astype(np.float32),
                pids=np.asarray(tasks.participant_id),
                rng_agg=rng_agg, metrics_dev=train.metrics,
                locals_dev=locals_dev, delta_norms=train.delta_norms,
                outstanding=int(len(agent_names) - dropped.sum()),
                t_dispatch=self.clock)
            if self._waves[wid].outstanding == 0:
                # fully dropped cohort: resolve its train rows and free it
                self._resolve_wave(self._waves[wid])
                del self._waves[wid]
        if len(self._waves) > self._waves_highwater:
            self._waves_highwater = len(self._waves)
            exp.telemetry.gauge("async/outstanding_waves_highwater").set(
                self._waves_highwater)
        exp.telemetry.counter("async/waves").inc()
        self._dispatch_wall += time.perf_counter() - t0

    # ----------------------------------------------------------------- merge
    def _merge_and_record(self) -> Dict[str, Any]:
        """Merge the buffer (padded to K), advance the version, run the
        global battery, and record one metrics.jsonl row keyed by the
        aggregation step. Serial composition of the two merge phases; the
        pipelined run() loop holds the dispatched handle across one fill
        instead."""
        return self._finalize_merge(self._dispatch_merge())

    def _dispatch_merge(self, capture_save: bool = False) -> _MergeInFlight:
        """Phase 1 of a merge: consume the buffer, run the jitted merge
        (with the sentinel retry loop), dispatch the global battery, and
        COMMIT the new model/version — returning without blocking on the
        eval transfer. Every host value the deferred finalize needs is
        captured in the handle, because by finalize time the live driver
        state may already belong to the next step's fill. With
        ``capture_save`` the checkpoint payload (streaming snapshot +
        model/RNG state) is captured too, at exactly the state a serial
        post-merge save would see."""
        exp = self.exp
        t0 = time.perf_counter()
        step = self.version + 1
        exp.telemetry.set_epoch(step)
        entries = sorted(self._buffer)     # (wave, lane) — deterministic
        self._buffer = []
        B = len(entries)
        # per-client rows for cohorts that fully resolved with this batch:
        # resolution is deferred into the handle and replayed at finalize —
        # the serial path finalizes immediately, so the recorded stream is
        # order-identical in both modes
        for wid, _lane in entries:
            self._waves[wid].outstanding -= 1
        for wid in sorted({w for w, _ in entries}):
            w = self._waves[wid]
            if w.outstanding == 0 and not w.recorded:
                self._resolve_wave(w)
        names = [self._waves[w].names[lane] for w, lane in entries]
        merged_by_wave: Dict[int, set] = {}
        for (wid, lane) in entries:
            merged_by_wave.setdefault(wid, set()).add(lane)
        adversaries: List[Any] = []
        for wid in sorted(merged_by_wave):
            w = self._waves[wid]
            present = {w.names[ln] for ln in merged_by_wave[wid]}
            adversaries.extend(n for n in w.adv_names if n in present)
        for e in entries:
            lat = max(0.0, self.clock - self._arrival_t.pop(e, self.clock))
            self._merge_latencies.append(lat)
            exp.telemetry.histogram("async/merge_latency_v").observe(lat)
        if len(self._merge_latencies) > 100_000:
            del self._merge_latencies[:-50_000]
        rolled_back = False
        with exp.telemetry.span("async/merge"):
            deltas, nbt, ns, pids = self._gather(entries)
            staleness = np.array(
                [self.version - self._waves[w].base_version
                 for w, _ in entries], np.float32)
            for s in staleness:
                exp.telemetry.histogram("staleness").observe(float(s))
            w_full = np.zeros((self.K,), np.float32)
            w_full[:B] = staleness_weights(staleness, self.weighting,
                                           self.alpha)
            occ = np.zeros((self.K,), bool)
            occ[:B] = True
            rng = self._waves[max(w for w, _ in entries)].rng_agg
            vars_before = exp.global_vars
            # health sentinel loop (async analog of the sync retry loop):
            # an unhealthy candidate re-merges the SAME buffer with an
            # escalated norm screen; norm_mult is traced, so no recompile
            norm_mult: Optional[float] = None
            retries = 0
            healthy, unorm = True, 0.0
            while True:
                nm = (exp.engine.base_norm_mult if norm_mult is None
                      else norm_mult)
                (new_vars, wv, alpha, calls, is_updated, n_quar, n_surv,
                 degr) = self._merge_fn(
                    vars_before, deltas, nbt, jnp.asarray(ns),
                    jnp.asarray(occ), jnp.asarray(w_full), rng,
                    jnp.float32(nm))
                if self._sentinel is None:
                    break
                healthy, unorm = self._sentinel.check(vars_before, new_vars)
                if (healthy or not exp.engine.screening
                        or retries >= exp.max_round_retries):
                    break
                retries += 1
                norm_mult = exp._escalate_norm_mult(nm)
                logger.warning(
                    "merge %d: unhealthy aggregate; re-merge %d/%d with "
                    "norm screen at %.2fx median", step, retries,
                    exp.max_round_retries, norm_mult)
            if self._sentinel is not None and not healthy:
                # retries exhausted (or unscreened): roll back to the
                # last-good ring and record the step degraded
                rolled_back = True
                self._rollbacks += 1
                exp.telemetry.counter("async/health_rollbacks").inc()
                new_vars = self._sentinel.rollback_target(vars_before)
                logger.warning(
                    "merge %d: unhealthy aggregate after %d re-merges "
                    "(update norm %.3g vs EMA %.3g); rolled back to "
                    "last-good model", step, retries, unorm,
                    self._sentinel.ema)
            globals_dev = exp.engine.global_evals_fn(new_vars)
        exp.global_vars = new_vars
        self.version = step
        # free fully-consumed cohorts (their payloads are merged + resolved)
        for wid in [w for w, v in self._waves.items()
                    if v.outstanding == 0 and v.recorded]:
            del self._waves[wid]
        if self._sentinel is not None and not rolled_back:
            # commit the ring at DISPATCH so the sentinel observes merge S
            # before merge S+1's candidate is checked against it — the same
            # observation order as the serial path. The degradation scalar
            # is already synced (sentinel.check device_gets the norms), so
            # this fetch does not stall the pipeline.
            degr_host = bool(jax.device_get(degr))
            if not degr_host:
                self._sentinel.commit(step, new_vars, unorm)
        extras = {"mode": "async", "buffer_occupancy": B,
                  "staleness_mean": float(staleness.mean()) if B else 0.0,
                  "staleness_max": float(staleness.max()) if B else 0.0,
                  "waves_dispatched": self.wave,
                  "arrivals_total": self._total_arrivals,
                  "virtual_time": self.clock}
        h = _MergeInFlight(
            step=step, t0=t0, globals_dev=globals_dev, wv=wv, alpha=alpha,
            is_updated=is_updated, n_quar=n_quar, degr=degr, names=names,
            adversaries=adversaries, staleness=staleness, occupancy=B,
            retries=retries, rolled_back=rolled_back,
            n_dropped=self._pending_dropped,
            dispatch_wall=self._dispatch_wall, extras=extras,
            entries=entries, rows=self._pending_rows)
        self._pending_rows = []
        self._pending_dropped = 0
        self._dispatch_wall = 0.0
        if capture_save:
            h.snapshot = self._snapshot()
            h.vars_after = new_vars
            h.fg_after = exp.fg_state
            h.rng_after = exp._snapshot_rng()
        h.t_dispatch_end = time.perf_counter()
        return h

    def _finalize_merge(self, h: _MergeInFlight) -> Dict[str, Any]:
        """Phase 2 of a merge: block on the eval transfer, replay the
        deferred per-client rows (in resolution order), and record the
        merge. Under overlap_eval this runs one step late — everything it
        touches rides the handle, so the recorded stream is byte-identical
        to the serial composition."""
        exp = self.exp
        with exp.telemetry.span("async/finalize"):
            t_fin = time.perf_counter()
            (globals_, wv_h, alpha_h, is_upd_h, n_quar_h,
             degr_h) = jax.device_get(
                (h.globals_dev, h.wv, h.alpha, h.is_updated, h.n_quar,
                 h.degr))
        finalize_time = time.perf_counter() - t_fin
        if self._pipeline:
            self._overlap_merges += 1
            self._overlap_hidden_s += max(0.0, t_fin - h.t_dispatch_end)
        for w in h.rows:
            self._record_wave_rows(w)
        degraded = bool(degr_h) or h.rolled_back
        exp.last_is_updated = bool(is_upd_h)
        exp.last_global_loss = float(globals_.clean.loss)
        if exp.is_poison_run:
            exp.last_backdoor_acc = float(globals_.poison.acc)
        times = {"round_time": time.perf_counter() - h.t0,
                 "dispatch_time": h.dispatch_wall,
                 "finalize_time": finalize_time}
        robust = {"n_quarantined": int(n_quar_h), "n_dropped": h.n_dropped,
                  "n_retries": h.retries, "degraded": degraded}
        self._record_merge(h.step, h.entries, h.names, h.adversaries,
                           globals_, wv_h, alpha_h, times, robust, h.extras)
        exp.telemetry.counter("async/merges").inc()
        exp.telemetry.counter("async/updates_merged").inc(h.occupancy)
        self._flush_merge_telemetry(h.step, robust, times)
        return {"epoch": h.step, "agents": h.names,
                "global_acc": float(globals_.clean.acc),
                "backdoor_acc": (float(globals_.poison.acc)
                                 if exp.is_poison_run else None),
                **times, **robust, **h.extras}

    def _save_pending(self, h: _MergeInFlight):
        """Checkpoint a finalized pipelined merge from its dispatch-time
        capture. Runs AFTER _finalize_merge(h): save_model reads
        last_global_loss (best-val) and last_backdoor_acc, which finalize
        just set from this merge's battery — the same values a serial save
        would see."""
        if h.snapshot is None:
            return
        from dba_mod_tpu.fl.experiment import RoundInFlight
        fl = RoundInFlight(
            epoch=h.step, t0=h.t0, seg_epochs=[], agent_names=[],
            adv_names=[], tasks_list=[], mask_list=[], payload=None,
            vars_after=h.vars_after, fg_after=h.fg_after,
            rng_after=h.rng_after)
        self.exp.save_model(h.step, fl=fl,
                            extra_aux={"async_state": h.snapshot})

    def _carry_starved_step(self) -> Dict[str, Any]:
        """starvation_policy "carry": the stream produced no arrivals for
        200 consecutive cohorts and the buffer is empty — consume one merge
        step as a recorded no-op (model unchanged, row degraded) so a
        starved run terminates inside its budget instead of aborting."""
        exp = self.exp
        t0 = time.perf_counter()
        step = self.version + 1
        exp.telemetry.set_epoch(step)
        self._flush_pending_rows()  # cohorts expired during the starved fill
        globals_dev = exp.engine.global_evals_fn(exp.global_vars)
        self.version = step
        globals_ = jax.device_get(globals_dev)
        exp.last_is_updated = False
        exp.last_global_loss = float(globals_.clean.loss)
        if exp.is_poison_run:
            exp.last_backdoor_acc = float(globals_.poison.acc)
        times = {"round_time": time.perf_counter() - t0,
                 "dispatch_time": self._dispatch_wall, "finalize_time": 0.0}
        self._dispatch_wall = 0.0
        robust = {"n_quarantined": 0, "n_dropped": self._pending_dropped,
                  "n_retries": 0, "degraded": True}
        self._pending_dropped = 0
        extras = {"mode": "async", "buffer_occupancy": 0,
                  "staleness_mean": 0.0, "staleness_max": 0.0,
                  "waves_dispatched": self.wave,
                  "arrivals_total": self._total_arrivals,
                  "virtual_time": self.clock}
        zeros = np.zeros((self.K,), np.float32)
        self._record_merge(step, [], [], [], globals_, zeros, zeros, times,
                           robust, extras)
        exp.telemetry.counter("async/starved_steps").inc()
        self._flush_merge_telemetry(step, robust, times)
        logger.warning("merge %d: starved stream carried as a degraded "
                       "no-op step (starvation_policy: carry)", step)
        return {"epoch": step, "agents": [],
                "global_acc": float(globals_.clean.acc),
                "backdoor_acc": (float(globals_.poison.acc)
                                 if exp.is_poison_run else None),
                **times, **robust, **extras}

    def _gather(self, entries):
        """Assemble the padded [K] merge batch from the per-wave stacked
        payloads, grouped per wave (one gather per cohort, not per lane).
        Inert padding lanes are zero-delta and masked out by occupancy —
        the same contract as the lockstep mesh padding."""
        groups: List[Tuple[_Wave, List[int]]] = []
        for wid, lane in entries:  # entries sorted ⇒ groups contiguous
            w = self._waves[wid]
            if groups and groups[-1][0] is w:
                groups[-1][1].append(lane)
            else:
                groups.append((w, [lane]))
        d_parts, n_parts, ns_parts, pid_parts = [], [], [], []
        for w, lanes in groups:
            if lanes == list(range(len(w.names))):
                d_parts.append(w.deltas)   # whole-cohort fast path — and
                n_parts.append(w.nbt)      # the K == C parity path: the
                # buffer IS the wave, untouched by any gather op
            else:
                idx = jnp.asarray(lanes, jnp.int32)
                d_parts.append(jax.tree_util.tree_map(
                    lambda l: jnp.take(l, idx, axis=0), w.deltas))
                n_parts.append(jnp.take(w.nbt, idx, axis=0))
            ns_parts.append(w.num_samples[lanes])
            pid_parts.append(w.pids[lanes])
        pad = self.K - len(entries)
        if pad:
            zero = jax.tree_util.tree_map(
                lambda l: jnp.zeros((pad,) + l.shape[1:], l.dtype),
                d_parts[0])
            d_parts.append(zero)
            n_parts.append(jnp.zeros((pad,), jnp.float32))
            ns_parts.append(np.zeros((pad,), np.float32))
            pid_parts.append(np.zeros((pad,), np.int32))
        if len(d_parts) == 1:
            deltas, nbt = d_parts[0], n_parts[0]
        else:
            deltas = jax.tree_util.tree_map(
                lambda *ls: jnp.concatenate(ls, axis=0), *d_parts)
            nbt = jnp.concatenate(n_parts, axis=0)
        return (deltas, nbt, np.concatenate(ns_parts).astype(np.float32),
                np.concatenate(pid_parts).astype(np.int32))

    # ------------------------------------------------------------- recording
    def _resolve_wave(self, w: _Wave):
        """Mark a fully-consumed cohort resolved and queue its per-client
        rows. Rows are ALWAYS deferred (both modes) and replayed in
        resolution order by the next finalize — identical in-memory stream
        to recording inline, but under overlap_eval the device_get of the
        cohort's train metrics rides the hidden finalize instead of
        stalling the dispatch path."""
        w.recorded = True
        self._pending_rows.append(w)

    def _flush_pending_rows(self):
        """Record any resolved-but-unrecorded cohorts now — the non-merge
        recording paths (starved carry steps) must flush before they write
        their own rows to keep the stream ordered."""
        rows, self._pending_rows = self._pending_rows, []
        for w in rows:
            self._record_wave_rows(w)

    def _record_wave_rows(self, w: _Wave):
        """Per-client rows for one fully-resolved cohort: train metrics and
        (when local_eval) the local battery — the same row semantics as the
        lockstep recorder block for an interval-1 round, keyed by the
        cohort's selection epoch."""
        exp = self.exp
        rec = exp.recorder
        params = exp.params
        w.recorded = True
        metrics, locals_, delta_norms = jax.device_get(
            (w.metrics_dev, w.locals_dev, w.delta_norms))
        w.metrics_dev, w.locals_dev = None, None
        baseline = bool(params["baseline"])
        ppb = np.asarray(w.tasks.poisoning_per_batch)
        adv_slot = np.asarray(w.tasks.adv_slot)
        for c, name in enumerate(w.names):
            n_e = int(w.tasks.num_epochs[c])
            for e in range(n_e):
                count = max(float(metrics.count[0, c, e]), 1.0)
                rec.add_train(name, (w.epoch - 1) * n_e + e + 1, w.epoch,
                              e + 1,
                              float(metrics.loss_sum[0, c, e]) / count,
                              100.0 * float(metrics.correct[0, c, e])
                              / count,
                              int(metrics.correct[0, c, e]), int(count))
            poisoning = bool(ppb[c] > 0)
            if locals_ is not None:
                lr = locals_
                if not (poisoning and baseline):
                    rec.add_test(name, w.epoch, float(lr.clean.loss[c]),
                                 float(lr.clean.acc[c]),
                                 int(lr.clean.correct[c]),
                                 int(lr.clean.count[c]))
                if poisoning and exp.is_poison_run:
                    if not baseline:
                        rec.add_poisontest(name, w.epoch,
                                           float(lr.poison_pre.loss[c]),
                                           float(lr.poison_pre.acc[c]),
                                           int(lr.poison_pre.correct[c]),
                                           int(lr.poison_pre.count[c]))
                    rec.add_poisontest(name, w.epoch,
                                       float(lr.poison_post.loss[c]),
                                       float(lr.poison_post.acc[c]),
                                       int(lr.poison_post.correct[c]),
                                       int(lr.poison_post.count[c]))
                if exp.is_poison_run and int(adv_slot[c]) >= 0:
                    rec.add_triggertest(
                        name, f"{name}_trigger", "", w.epoch,
                        float(lr.agent_trigger.loss[c]),
                        float(lr.agent_trigger.acc[c]),
                        int(lr.agent_trigger.correct[c]),
                        int(lr.agent_trigger.count[c]))
            if poisoning and not baseline:
                rec.scale_temp_one_row.extend(
                    [w.epoch, round(float(delta_norms[c]), 4)])

    def _record_merge(self, step, entries, names, adversaries, globals_,
                      wv, alpha, times, robust, extras):
        """Global battery rows + the metrics.jsonl row for one merge —
        keyed by the aggregation step, same semantic keys as a sync round
        plus the async extras."""
        exp = self.exp
        rec = exp.recorder
        params = exp.params
        rec.add_test("global", step, float(globals_.clean.loss),
                     float(globals_.clean.acc), int(globals_.clean.correct),
                     int(globals_.clean.count))
        if exp.is_poison_run:
            g = globals_
            rec.add_poisontest("global", step, float(g.poison.loss),
                               float(g.poison.acc), int(g.poison.correct),
                               int(g.poison.count))
            rec.add_triggertest("global", "combine", "", step,
                                float(g.poison.loss), float(g.poison.acc),
                                int(g.poison.correct), int(g.poison.count))
            if params.is_centralized_attack:
                tnames = [f"global_in_index_{j}_trigger"
                          for j in range(exp.engine.num_global_triggers)]
            else:
                tnames = [f"global_in_{a}_trigger"
                          for a in params.adversary_list]
            for j, tname in enumerate(tnames):
                rec.add_triggertest(
                    "global", tname, "", step,
                    float(g.per_trigger.loss[j]),
                    float(g.per_trigger.acc[j]),
                    int(g.per_trigger.correct[j]),
                    int(g.per_trigger.count[j]))
        if rec.scale_temp_one_row:
            rec.scale_temp_one_row.append(
                round(float(globals_.clean.acc), 4))
        if params.aggregation != cfg.AGGR_MEAN:
            rec.add_weight_result([str(n) for n in names],
                                  np.asarray(wv)[:len(names)].tolist(),
                                  np.asarray(alpha)[:len(names)].tolist(),
                                  epoch=step)
        rec.add_round_json(
            epoch=step, agents=[str(n) for n in names],
            adversaries=[str(a) for a in adversaries],
            is_updated=exp.last_is_updated,
            global_acc=float(globals_.clean.acc),
            global_loss=float(globals_.clean.loss),
            backdoor_acc=(float(globals_.poison.acc)
                          if exp.is_poison_run else None),
            **times, **robust, **extras)
        rec.save(exp.is_poison_run)

    def _flush_merge_telemetry(self, step, robust, times):
        t = self.exp.telemetry
        if not t.enabled:
            return
        t.counter("rounds").inc()
        if robust.get("n_quarantined"):
            t.counter("clients_quarantined").inc(robust["n_quarantined"])
        if robust.get("n_dropped"):
            t.counter("clients_dropped").inc(robust["n_dropped"])
        if robust.get("n_retries"):
            t.counter("round_retries").inc(robust["n_retries"])
        if robust.get("degraded"):
            t.counter("degraded_rounds").inc()
        t.histogram("round_seconds").observe(times["round_time"])
        t.flush_round(step)

    # ------------------------------------------------------ checkpoint state
    def _snapshot(self) -> Dict[str, Any]:
        """Host-picklable streaming state for the aux sidecar: everything
        needed to resume the arrival queue and buffer bit-exactly. Wave
        payloads are np trees; device handles for unrecorded rows are
        fetched here (they must survive the process dying)."""
        waves = {}
        live = ({e[2] for e in self._heap} | {w for w, _ in self._buffer})
        for wid in live:
            w = self._waves[wid]
            metrics, locals_, norms = jax.device_get(
                (w.metrics_dev, w.locals_dev, w.delta_norms))
            waves[wid] = {
                "wave": w.wave, "epoch": w.epoch,
                "base_version": w.base_version, "names": w.names,
                "adv_names": w.adv_names,
                "tasks": jax.tree_util.tree_map(np.asarray, w.tasks),
                "deltas": jax.tree_util.tree_map(np.asarray, w.deltas),
                "nbt": np.asarray(w.nbt),
                "num_samples": w.num_samples, "pids": w.pids,
                "rng_agg": np.asarray(jax.random.key_data(w.rng_agg)),
                "metrics": metrics, "locals": locals_,
                "delta_norms": np.asarray(norms),
                "outstanding": w.outstanding, "recorded": w.recorded,
                "t_dispatch": w.t_dispatch}
        return {"version": self.version, "wave": self.wave,
                "clock": self.clock, "seq": self._seq,
                "heap": list(self._heap), "buffer": list(self._buffer),
                "arrival_t": [[wid, lane, t] for (wid, lane), t
                              in self._arrival_t.items()],
                "health": (self._sentinel.state()
                           if self._sentinel is not None else None),
                "pending_dropped": self._pending_dropped,
                "total_arrivals": self._total_arrivals, "waves": waves}

    def _restore(self, aux: Optional[Dict[str, Any]]):
        st = (aux or {}).get("async_state")
        if st is None:
            if self.exp.start_epoch > 1:
                # model-only resume (no/discarded sidecar): restart the
                # stream at the committed version with an empty buffer —
                # the arrival queue is rebuilt from fresh cohorts
                self.version = self.exp.start_epoch - 1
                self.wave = self.version * self.K // max(self.C, 1)
                logger.warning(
                    "async resume without a streaming sidecar: restarting "
                    "the arrival queue at merge %d (buffer state lost)",
                    self.version)
            return
        self.version = int(st["version"])
        self.wave = int(st["wave"])
        self.clock = float(st["clock"])
        self._seq = int(st["seq"])
        self._heap = [tuple(e) for e in st["heap"]]
        heapq.heapify(self._heap)
        self._buffer = [tuple(e) for e in st["buffer"]]
        # pre-PR sidecars carry no arrival times: buffered entries then get
        # no deadline credit (t defaults to the restored clock)
        self._arrival_t = {(int(a), int(b)): float(t)
                           for a, b, t in st.get("arrival_t", [])}
        if self._sentinel is not None:
            self._sentinel.load_state(st.get("health"))
        self._pending_dropped = int(st["pending_dropped"])
        self._total_arrivals = int(st["total_arrivals"])
        for wid, d in st["waves"].items():
            self._waves[int(wid)] = _Wave(
                wave=int(d["wave"]), epoch=int(d["epoch"]),
                base_version=int(d["base_version"]), names=d["names"],
                adv_names=d["adv_names"], tasks=d["tasks"],
                deltas=jax.tree_util.tree_map(jnp.asarray, d["deltas"]),
                nbt=jnp.asarray(d["nbt"]),
                num_samples=d["num_samples"], pids=d["pids"],
                rng_agg=jax.random.wrap_key_data(jnp.asarray(d["rng_agg"])),
                metrics_dev=d["metrics"], locals_dev=d["locals"],
                delta_norms=d["delta_norms"],
                outstanding=int(d["outstanding"]),
                recorded=bool(d["recorded"]),
                t_dispatch=float(d.get("t_dispatch", 0.0)))
        logger.info("async resume: merge %d, %d cohorts live, %d buffered, "
                    "%d in flight", self.version, len(self._waves),
                    len(self._buffer), len(self._heap))
