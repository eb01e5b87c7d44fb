"""The end-to-end FL experiment driver — the TPU-native main.py.

Replaces the reference's __main__ round loop (main.py:84-244) with a class:
data loading + partitioning once at startup, then per round: host-side agent
selection and plan building, one jitted round computation (train all clients →
aggregate), jitted local/global evaluation batteries, and recording. No import
cycles, no global mutable state (SURVEY §1 layer-crossing notes, §7.3).
"""
from __future__ import annotations

import dataclasses
import logging
import os
import random
import signal
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dba_mod_tpu import config as cfg
from dba_mod_tpu import checkpoint as ckpt
from dba_mod_tpu.data import (build_batch_plan, build_eval_plan,
                              load_image_dataset, load_loan_dataset,
                              plan_step_counts)
from dba_mod_tpu.data.partition import (equal_split_indices,
                                        poison_test_indices,
                                        sample_dirichlet_indices)
from dba_mod_tpu.fl import evaluation
from dba_mod_tpu.fl.client import STEP_CHUNK
from dba_mod_tpu.fl.device_data import (make_image_device_data,
                                        make_loan_device_data,
                                        make_token_device_data)
from dba_mod_tpu.fl.rounds import EvalPlans, RoundEngine
from dba_mod_tpu.fl.selection import select_agents
from dba_mod_tpu.fl.state import build_client_tasks
from dba_mod_tpu.models import ModelVars, build_model, compute_dtype_of
from dba_mod_tpu.ops.aggregation import foolsgold_init
from dba_mod_tpu.utils import run_guard, telemetry
from dba_mod_tpu.utils.recorder import Recorder

logger = logging.getLogger("dba_mod_tpu")


def _pad_tasks(tasks, pad: int, aggregation: str):
    """Append `pad` inert clients (fully-masked plans → zero deltas) so the
    stacked axis tiles the mesh. Sound only for FedAvg, whose divisor is the
    static no_models — a zero delta shifts RFA's geometric median and
    FoolsGold's similarity geometry. Enforced here, not by caller
    convention."""
    if aggregation != cfg.AGGR_MEAN:
        raise ValueError(
            f"inert-client padding is only sound for FedAvg (aggregation="
            f"{cfg.AGGR_MEAN!r}); got {aggregation!r} — pick a no_models "
            "that tiles the mesh instead")
    from dba_mod_tpu.fl.state import ClientTask
    return ClientTask(
        slot=np.pad(tasks.slot, (0, pad)),
        participant_id=np.pad(tasks.participant_id, (0, pad)),
        adv_index=np.pad(tasks.adv_index, (0, pad), constant_values=-1),
        adv_slot=np.pad(tasks.adv_slot, (0, pad), constant_values=-1),
        poisoning_per_batch=np.pad(tasks.poisoning_per_batch, (0, pad)),
        alpha=np.pad(tasks.alpha, (0, pad), constant_values=1.0),
        scale=np.pad(tasks.scale, (0, pad), constant_values=1.0),
        lr_row=np.pad(tasks.lr_row, ((0, pad), (0, 0))),
        num_epochs=np.pad(tasks.num_epochs, (0, pad)))


@dataclasses.dataclass
class RoundInFlight:
    """Device handles + host context of a dispatched round, awaiting its one
    blocking transfer. Produced by `dispatch_round`, consumed by
    `finalize_round`; holding two of these pipelines round N+1's compute
    behind round N's host fetch."""
    epoch: int
    t0: float                    # perf_counter at dispatch start
    seg_epochs: List[int]
    agent_names: List[Any]
    adv_names: List[Any]
    tasks_list: List[Any]
    mask_list: List[Any]
    payload: Any                 # device trees handed to jax.device_get
    # host planning + enqueue seconds (perf_counter), set by dispatch_round;
    # finalize_round records it next to its own fetch time so
    # round_result.csv splits round_time into honest phases
    dispatch_time: float = 0.0
    # fault-tolerance outcome of the dispatch (fl/faults.py + the screening
    # pass in fl/rounds.py): retries consumed re-running the round after a
    # non-finite aggregate, and whether the host forced a degraded round
    # (restored the pre-round state) because retries ran out
    n_retries: int = 0
    forced_degraded: bool = False
    # Post-round state handles + host RNG snapshots, captured at dispatch
    # time: under pipelining, by the time round N finalizes the experiment's
    # live attributes already belong to round N+1, so checkpoints must save
    # these captured values, not the live ones.
    vars_after: Any = None       # global ModelVars after this round
    fg_after: Any = None         # FoolsGoldState after this round
    rng_after: Optional[Dict[str, Any]] = None
    # the deltas the server RECEIVED this round — the stale fault lane's
    # replay source for the NEXT round, captured per-round for the resume
    # sidecar (under pipelining the live _prev_deltas may already belong
    # to round N+1 when round N checkpoints). None unless the stale lane
    # is on.
    deltas_after: Any = None
    # overlap_eval bookkeeping: the round ran the split core + overlapped
    # eval batteries, and eval_dispatch_t is the perf_counter when the last
    # battery was enqueued — finalize_round turns (fetch wall time vs time
    # since enqueue) into the hidden-eval clock
    overlapped: bool = False
    eval_dispatch_t: float = 0.0


class Experiment:
    def __init__(self, params: cfg.Params, save_results: bool = True):
        from dba_mod_tpu.parallel.distributed import initialize_distributed
        initialize_distributed()  # env-triggered; no-op single-host
        self.params = params
        # crash/preemption guard (utils/run_guard.py): stop flag checked at
        # round boundaries + watchdog around host sync points. Construction
        # is side-effect free; run() installs/uninstalls the handlers.
        # Strict no-op (no threads, no handlers) with the default knobs.
        self.guard = run_guard.RunGuard.from_params(params)
        self.interrupted = False
        self._ckpt_mgr: Optional[ckpt.CheckpointManager] = None
        # resumed_model: auto — discover the newest VERIFIED checkpoint
        # across run_dir's run folders BEFORE creating a new folder: the
        # resumed run re-enters the killed run's folder and continues its
        # recorder stream, instead of scattering each retry into a fresh
        # timestamped dir
        self._auto_resume_path: Optional[Path] = None
        resumed_folder: Optional[Path] = None
        # one results writer per multi-process run: every process shares
        # the run folder path (orbax checkpoint saves are collective — all
        # processes must call with the same path), but only process 0
        # writes run metadata, logs, and the recorder streams
        is_writer = jax.process_index() == 0
        if (save_results and jax.process_count() > 1
                and not params.run_name):
            raise ValueError(
                "multi-process runs that save results require run_name: "
                "every process — and every elastic relaunch of the "
                "survivors — must agree on ONE run folder, which "
                "per-process timestamped folders cannot guarantee")
        if params.resume_mode == "auto":
            hit = ckpt.find_auto_resume(Path(str(params["run_dir"])),
                                        params.type, params.run_name)
            if hit is not None:
                resumed_folder, self._auto_resume_path = hit
        if not save_results:
            self.folder: Optional[Path] = None
        elif resumed_folder is not None:
            self.folder = resumed_folder
            if is_writer:  # exclusive-owner mutations: one process only
                ckpt.sweep_stale(self.folder)  # debris: *.tmp, orbax tmp
                params.write_yaml(self.folder)
        elif is_writer:
            self.folder = params.make_run_folder()
        else:
            self.folder = Path(str(params["run_dir"])) / params.run_name
            self.folder.mkdir(parents=True, exist_ok=True)
        # idempotent logger setup (telemetry.py): one stream handler, one
        # run-folder file handler that FOLLOWS the active experiment —
        # replaces the old basicConfig + per-instance FileHandler stacking
        # (two experiments in one process each logged every line twice)
        telemetry.setup_logging(self.folder if is_writer else None)
        if self.folder and is_writer:
            from dba_mod_tpu.utils.html import dict_html
            (self.folder / "params.html").write_text(
                dict_html(params.raw, params.current_time))
        self.recorder = Recorder(self.folder if is_writer else None,
                                 tensorboard=bool(params.get("tensorboard")))
        # telemetry (utils/telemetry.py): spans + metrics + XLA compile and
        # memory instrumentation. Files land in telemetry_dir (default: the
        # run folder; in-memory when neither exists); one writer per
        # multi-process run. The instance is process-wide current, so spans
        # in shared code paths (checkpoint.py, rounds.py) resolve to it.
        tdir = str(params.get("telemetry_dir", "") or "")
        tfolder: Optional[Path] = Path(tdir) if tdir else self.folder
        if tfolder is not None and jax.process_index() != 0:
            tfolder = None
        self.telemetry = telemetry.configure(
            enabled=bool(params.get("telemetry", False)), folder=tfolder,
            tb_sink=(self.recorder._scalar
                     if self.recorder._tb is not None else None))
        # defense forensics (utils/forensics.py): per-client aggregation
        # introspection streamed from the jitted round's ForensicStats
        # payload slot. Opt-in and strictly inert when off: no writer, no
        # files, no extra device work anywhere in the round path.
        self.forensics_writer = None
        if bool(params.get("forensics", False)):
            from dba_mod_tpu.utils.forensics import ForensicsWriter
            self.forensics_writer = ForensicsWriter(
                self.folder if is_writer else None,
                tb_sink=(self.recorder._scalar
                         if self.recorder._tb is not None else None))
        self.model_def = build_model(params)
        seed = int(params.get("random_seed", 1))
        self.select_rng = random.Random(seed)
        self.plan_rng = np.random.RandomState(seed)
        self.rng_key = jax.random.key(seed)

        self.local_eval_plans = None  # a workload may give the local
        self._load_data_and_partition(seed)  # batteries a shorter plan

        # Fixed plan shape across rounds → the jitted round compiles once.
        max_client = max((len(v) for v in self.client_indices.values()),
                         default=1)
        b = int(params["batch_size"])
        self.steps_per_epoch = max(1, int(np.ceil(max_client / b)))
        self.is_poison_run = bool(params["is_poison"])
        self.epochs_max = (max(int(params["internal_epochs"]),
                               int(params["internal_poison_epochs"]))
                           if self.is_poison_run
                           else int(params["internal_epochs"]))

        # Global model: fresh init or resume (image_helper.py:56-67)
        init_rng = jax.random.key(seed)
        self.global_vars = self.model_def.init_vars(init_rng)
        self.start_epoch = 1
        self._resume_aux: Optional[Dict[str, Any]] = None
        resume_path: Optional[Path] = None
        if params.resume_mode == "auto":
            resume_path = self._auto_resume_path
            if resume_path is None:
                logger.warning(
                    "resume auto: no verified checkpoint under %s — "
                    "starting a fresh run", params["run_dir"])
        elif params.resume_mode == "named":
            path = (Path(str(params.get("checkpoint_dir", "saved_models")))
                    / str(params["resumed_model_name"]))
            # integrity gate: verified → load; manifest-less (pretrain/
            # legacy) → load unverified, the reference behavior; corrupt →
            # fall back to the newest verified SAME-NAME sibling. No sweep
            # and no quarantine here: checkpoint_dir is a shared library
            # (another process may be mid-commit into it), unlike the
            # exclusively owned run folder swept in __init__
            resume_path = ckpt.resolve_verified(path)
        if resume_path is not None:
            self.global_vars, saved_epoch, saved_lr = ckpt.load_checkpoint(
                resume_path, self.global_vars)
            if params.resume_mode == "auto":
                # the checkpoint records the completed round's BASE epoch;
                # with aggr_epoch_interval > 1 that round also trained the
                # interval-1 following epochs, and the killed run's round
                # grid steps by the interval — continuing the exact
                # trajectory means the next base, not base+1 (which would
                # re-train epoch base+1 and shift the whole grid)
                self.start_epoch = (saved_epoch
                                    + int(params["aggr_epoch_interval"]))
            else:
                # named resume keeps the reference's +1 semantics
                self.start_epoch = saved_epoch + 1
            self.params.raw["lr"] = saved_lr
            # full-state sidecar, when the checkpoint has one (save_model
            # runs write it; pretrain checkpoints don't — model-only resume
            # is the reference behavior, image_helper.py:56-67). A corrupt
            # sidecar also degrades to model-only (checkpoint.py).
            self._resume_aux = ckpt.load_aux_state(resume_path)
            if (self._resume_aux is not None
                    and int(self._resume_aux["epoch"]) != saved_epoch):
                # a crash between the (synchronous) sidecar write and the
                # async orbax commit can leave the sidecar one round ahead
                # of the model — restoring it would replay round N with
                # round N+1's RNG/memory. Fall back to model-only resume.
                logger.warning(
                    "resume sidecar is for epoch %d but the model "
                    "checkpoint is epoch %d — discarding the sidecar "
                    "(model-only resume; FoolsGold memory and RNG streams "
                    "restart)", int(self._resume_aux["epoch"]), saved_epoch)
                self._resume_aux = None
            logger.info("resumed %s: lr=%s start_epoch=%d aux=%s",
                        resume_path, saved_lr, self.start_epoch,
                        self._resume_aux is not None)
            if params.resume_mode == "auto" and self.folder is not None:
                # continue the killed run's recorder stream: reload rows
                # through the resume round's FINAL global epoch and drop
                # the rest — a kill can land after round N recorded but
                # before its checkpoint verified, and the replayed round N
                # must not appear twice in metrics.jsonl/round_result.csv
                cut = saved_epoch + int(params["aggr_epoch_interval"]) - 1
                kept = self.recorder.load_from_folder(cut)
                logger.info(
                    "resume auto: continuing recorder stream in %s "
                    "(%d metrics rows kept through epoch %d)",
                    self.folder, kept, cut)
                if self.forensics_writer is not None:
                    # same truncate-and-continue contract for the forensic
                    # streams — a replayed round must not appear twice
                    self.forensics_writer.load_from_folder(cut)

        # clients mesh: 0 → single-device; -1 → all visible devices; n → n
        nd = int(params.get("num_devices", 0))
        self.mesh = None
        if nd == -1 or nd > 1:
            from dba_mod_tpu.parallel.mesh import make_mesh
            self.mesh = make_mesh(0 if nd == -1 else nd)

        # elastic peer-health layer (parallel/distributed.py::PeerHealth):
        # per-host heartbeats, round-boundary staleness checks, and the
        # peer-lost watchdog verdict (exit 77). Active only in
        # multi-process runs with heartbeat_interval_s > 0 — single-host
        # the knobs are strict no-ops: no thread, no files, no per-round
        # work (run() never touches a None peers).
        self.peers = None
        self.heartbeat_barrier_s = float(
            params.get("heartbeat_barrier_s", 0.0))
        hb = float(params.get("heartbeat_interval_s", 0.0))
        if hb > 0 and jax.process_count() > 1:
            from dba_mod_tpu.parallel.distributed import PeerHealth
            # default under THIS run's folder: concurrent runs sharing a
            # run_dir must not read each other's heartbeats (a same-gen
            # twin world would mask a real loss); folder-less runs
            # (save_results=False) fall back to run_dir/_peers
            hb_dir = (str(params.get("heartbeat_dir", "") or "")
                      or str((self.folder if self.folder is not None
                              else Path(str(params["run_dir"])))
                             / "_peers"))
            self.peers = PeerHealth(
                hb_dir, jax.process_index(), jax.process_count(),
                interval_s=hb,
                timeout_s=float(params.get("heartbeat_timeout_s", 0.0)))
        self.telemetry.gauge("mesh/world_size").set(jax.process_count())

        self.interval = int(params["aggr_epoch_interval"])
        self.sequential_debug = bool(params.get("sequential_debug", False))
        if self.sequential_debug and self.mesh is not None:
            # width-1 client slices cannot tile a sharded clients axis
            logger.warning("sequential_debug forces single-device execution; "
                           "ignoring num_devices")
            self.mesh = None
        self.engine = RoundEngine(params, self.model_def, self.device_data,
                                  self.eval_plans, mesh=self.mesh,
                                  num_segments=self.interval,
                                  local_plans=self.local_eval_plans)
        # fault-tolerance layer (fl/faults.py; README "Fault model"): the
        # robust round program screens payloads into a survivor mask and the
        # host retries/degrades rounds below. Sequential-debug runs the
        # split train/aggregate path which bypasses the fault layer — refuse
        # the combination rather than silently not injecting.
        if self.engine.robust and self.sequential_debug:
            raise ValueError("fault_injection/screen_updates are not "
                             "supported with sequential_debug")
        if (self.engine.fault_cfg.stale_enabled
                and jax.process_count() > 1):
            raise ValueError("fault_stale_prob > 0 is single-controller "
                             "only (the replayed-delta carry cannot be "
                             "placed across processes)")
        self.max_round_retries = int(params.get("max_round_retries", 2))
        self.retry_backoff_s = float(params.get("retry_backoff_s", 0.0))
        # post-merge model-health sentinel (README "Self-healing
        # federation"): None when off — no program traced, no host sync,
        # strict no-op. Shared with the async driver so both engines gate
        # commits through the same EMA band + last-good ring.
        self._sentinel = None
        if bool(params.get("model_health_check", False)):
            from dba_mod_tpu.fl.rounds import HealthSentinel
            self._sentinel = HealthSentinel(
                band=float(params.get("health_norm_band", 0.0)),
                ema_alpha=float(params.get("health_ema_alpha", 0.1)),
                warmup=int(params.get("health_warmup_merges", 3)),
                ring_size=int(params.get("rollback_ring", 0)))
        # overlap_eval (README "Round pipelining"): dispatch round N's eval
        # batteries + host record/checkpoint concurrently with round N+1's
        # train/aggregate. The scheduler lives in _dispatch_overlap; here we
        # only pick the eval placement: with >1 local device and no clients
        # mesh the batteries run on a SECOND device (true compute overlap —
        # the eval executables get their own placement-cached data
        # constants), otherwise they share device 0 and the overlap hides
        # the host-side fetch/record/checkpoint path only. sequential_debug
        # takes precedence (see _dispatch); with telemetry on the split
        # program still runs but the loop stays SEQUENTIAL (_run_rounds) so
        # span attribution is honest. Off is a strict no-op — no core
        # program is ever compiled.
        self._overlap = bool(params.get("overlap_eval", False))
        self._eval_device = evaluation.pick_eval_device(self.mesh,
                                                        self._overlap)
        self._overlap_rounds = 0
        self._overlap_hidden_s = 0.0  # cumulative eval+fetch seconds hidden
        self._overlap_wait_s = 0.0    # cumulative finalize blocking seconds
        self._fault_key = jax.random.key(self.engine.fault_cfg.seed)
        # last round's submitted deltas (the stale lane's replay source).
        # Checkpointed in the aux sidecar when the lane is on (save_model
        # captures each round's deltas_after), so a resumed run's first
        # stale replay is faithful; only sidecar-less resumes (pretrain /
        # model-only checkpoints) fall back to the zero delta here.
        self._prev_deltas = None
        # (a streamed model's round refuses FoolsGold: no memory to keep)
        grad_len = 0 if self.engine.streamed else int(np.prod(
            self.model_def.similarity_param(self.global_vars.params).shape))
        self.fg_state = foolsgold_init(self.num_participants, grad_len)
        if self.mesh is not None:
            # replicate host-initialized state onto the mesh explicitly —
            # required on multi-host (device_put cannot span processes), a
            # no-op-cost placement single-host
            from dba_mod_tpu.parallel.mesh import replicate_for_mesh
            self.global_vars = replicate_for_mesh(self.mesh,
                                                  self.global_vars)
            self.fg_state = replicate_for_mesh(self.mesh, self.fg_state)
        if self.engine.streamed:
            # allocated once, here; every round takes it and hands it back
            jax.block_until_ready(
                self.engine.round_workspace(self.global_vars))
        self.local_eval = bool(params.get("local_eval", True))
        self.last_is_updated = True  # set per-round in finalize_round
        self.last_global_loss = float("inf")  # feeds the best-val checkpoint
        self.best_loss = float("inf")         # helper.py:433, main.py:120
        # stale_poison_probe (flag-gated deviation): the LOAN adaptive
        # poison-LR probe reads the CURRENT global model's backdoor accuracy
        # (loan_train.py:67-75), which forces a host sync that serializes
        # round pipelining on every poison round. With this flag the probe
        # uses the most recently FINALIZED round's backdoor accuracy
        # instead — one round stale in sequential runs, two rounds stale
        # under pipeline_rounds (dispatch of round N precedes finalize of
        # N-1) — for a quantity the reference itself recomputes mid-loop.
        self.stale_poison_probe = bool(params.get("stale_poison_probe",
                                                  False))
        self.last_backdoor_acc: Optional[float] = None
        self._apply_resume_aux()
        # host counters and the slow-round history, from here on: the first
        # round's counts are those since the build ended
        self._boundary = telemetry.RoundBoundary()

    def _apply_resume_aux(self):
        """Restore the full-state sidecar loaded during resume: FoolsGold
        memory, best-val loss, and every RNG stream — so a killed-and-resumed
        run continues the uninterrupted trajectory exactly (the reference
        cannot: helper.py:545-549 is RAM-only)."""
        aux = self._resume_aux
        if not aux:
            return
        self.select_rng.setstate(aux["select_rng"])
        self.plan_rng.set_state(aux["plan_rng"])
        self.rng_key = jax.random.wrap_key_data(jnp.asarray(aux["rng_key"]))
        self.best_loss = float(aux["best_loss"])
        self.last_backdoor_acc = aux.get("last_backdoor_acc")
        mem = jnp.asarray(aux["fg_memory"])
        if mem.shape != self.fg_state.memory.shape:
            raise ValueError(
                f"resume sidecar FoolsGold memory shape {mem.shape} does not "
                f"match this run's {self.fg_state.memory.shape} — the "
                "checkpoint belongs to a different participant set or model")
        self.fg_state = self.fg_state._replace(memory=mem)
        if self.mesh is not None:
            from dba_mod_tpu.parallel.mesh import replicate_for_mesh
            self.fg_state = replicate_for_mesh(self.mesh, self.fg_state)
        pd = aux.get("prev_deltas")
        if pd is not None and self.engine.fault_cfg.stale_enabled:
            # faithful first post-resume stale replay (the lane is
            # single-process-only, so plain placement suffices)
            tree = jax.tree_util.tree_map(jnp.asarray, pd)
            if self.mesh is not None:
                from dba_mod_tpu.parallel.mesh import client_sharding
                tree = jax.device_put(tree, client_sharding(self.mesh))
            self._prev_deltas = tree

    # ------------------------------------------------------------------ data
    def _load_data_and_partition(self, seed: int):
        params = self.params
        cdtype = compute_dtype_of(params)
        # eval batch size only shapes the eval scans; the recorded sums are
        # batch-size invariant (test.py:21-22's reduction='sum')
        eb = int(params.get("eval_batch_size", 0) or
                 params["test_batch_size"])
        if params.is_image:
            with telemetry.span("setup/data"):
                data = self.image_data = load_image_dataset(params)
            self.device_data = make_image_device_data(data, params,
                                                      compute_dtype=cdtype)
            with telemetry.span("setup/partition"):
                self._partition_images(data, seed, eb)
        elif params.is_tokens:
            from dba_mod_tpu.data.tokens import load_token_dataset
            with telemetry.span("setup/data"):
                # the ids a model keeps for itself (a diffusion model's
                # MASK) are at the top of its vocabulary: no row holds one
                data = self.token_data = load_token_dataset(
                    params, self.model_def.vocab_size
                    - self.model_def.reserved_ids)
            self.device_data = make_token_device_data(
                data, params, compute_dtype=cdtype,
                block_length=self.model_def.block_length)
            with telemetry.span("setup/partition"):
                self._partition_tokens(data, eb)
        else:
            with telemetry.span("setup/data"):
                data = self.loan_data = load_loan_dataset(params)
            self.device_data = make_loan_device_data(data, params,
                                                     compute_dtype=cdtype)
            with telemetry.span("setup/partition"):
                self._partition_loan(data, eb)

    def _partition_images(self, data, seed: int, eb: int):
        params = self.params
        if params["sampling_dirichlet"]:
            indices = sample_dirichlet_indices(
                data.train_labels,
                int(params["number_of_total_participants"]),
                float(params["dirichlet_alpha"]),
                py_rng=random.Random(seed),
                np_rng=np.random.RandomState(seed))
        else:
            indices = equal_split_indices(
                len(data.train_labels),
                int(params["number_of_total_participants"]),
                py_rng=random.Random(seed))
        self.client_indices = indices
        self.client_slots = {name: 0 for name in indices}
        if params["is_random_namelist"]:
            self.participants = list(
                range(int(params["number_of_total_participants"])))
        else:
            self.participants = list(params["participants_namelist"])
        self.benign_names = sorted(
            set(self.participants) - set(params.adversary_list))
        self.num_participants = int(
            params["number_of_total_participants"])

        clean = build_eval_plan(np.arange(len(data.test_labels)), eb)
        poison = build_eval_plan(
            poison_test_indices(data.test_labels,
                                int(params["poison_label_swap"])), eb)
        self.eval_plans = EvalPlans(
            clean_idx=jnp.asarray(clean.idx),
            clean_slots=jnp.zeros_like(jnp.asarray(clean.idx)),
            clean_mask=jnp.asarray(clean.mask),
            poison_idx=jnp.asarray(poison.idx),
            poison_slots=jnp.zeros_like(jnp.asarray(poison.idx)),
            poison_mask=jnp.asarray(poison.mask))

    def _partition_tokens(self, data, eb: int):
        """A participant holds its own packed rows (data/tokens.py drew them
        from its topic mixture). Both global tests read every held-out row
        (a backdoor test scores the target continuation, so no row is
        dropped for its label); a local battery reads the first
        `local_test_sequences` of them."""
        params = self.params
        self.client_indices = data.client_rows
        self.client_slots = {name: 0 for name in data.client_rows}
        self.num_participants = int(params["number_of_total_participants"])
        self.participants = (list(range(self.num_participants))
                             if params["is_random_namelist"]
                             else list(params["participants_namelist"]))
        self.benign_names = sorted(
            set(self.participants) - set(params.adversary_list))

        def plans(rows: int) -> EvalPlans:
            plan = build_eval_plan(np.arange(rows), eb)
            idx, mask = jnp.asarray(plan.idx), jnp.asarray(plan.mask)
            return EvalPlans(idx, jnp.zeros_like(idx), mask,
                             idx, jnp.zeros_like(idx), mask)

        self.eval_plans = plans(len(data.test_tokens))
        self.local_eval_plans = plans(
            min(int(params["local_test_sequences"]), len(data.test_tokens)))

    def _partition_loan(self, data, eb: int):
        params = self.params
        state_of = {n: i for i, n in enumerate(data.state_names)}
        # benign list: first `number_of_total_participants` shards that
        # are not adversaries (loan_helper.py:134-141)
        benign = []
        for j, name in enumerate(data.state_names):
            if j >= int(params["number_of_total_participants"]):
                break
            if name not in params.adversary_list:
                benign.append(name)
        self.benign_names = benign
        if params["is_random_namelist"]:
            self.participants = benign + params.adversary_list
        else:
            self.participants = list(params["participants_namelist"])
        self.client_indices = {
            name: list(range(len(data.train_y[state_of[name]])))
            for name in data.state_names}
        self.client_slots = state_of
        self.num_participants = len(data.state_names)

        # eval plans concatenate every state shard (test.py:13-24)
        b = eb
        pairs = [(s, i) for s, ys in enumerate(data.test_y)
                 for i in range(len(ys))]
        slots = np.array([p[0] for p in pairs], np.int64)
        rows = np.array([p[1] for p in pairs], np.int64)
        plan = build_eval_plan(np.arange(len(pairs)), b)
        # map flat eval positions back to (slot, row)
        idx = rows[plan.idx.reshape(-1)].reshape(plan.idx.shape)
        slt = slots[plan.idx.reshape(-1)].reshape(plan.idx.shape)
        self.eval_plans = EvalPlans(
            clean_idx=jnp.asarray(idx.astype(np.int32)),
            clean_slots=jnp.asarray(slt.astype(np.int32)),
            clean_mask=jnp.asarray(plan.mask),
            poison_idx=jnp.asarray(idx.astype(np.int32)),
            poison_slots=jnp.asarray(slt.astype(np.int32)),
            poison_mask=jnp.asarray(plan.mask))

    # ----------------------------------------------------------------- round
    def build_static_round_inputs(self, epoch: int):
        """Device-ready train_fn inputs at the STATIC plan shape — for
        diagnostics that call the engine directly (bench.py's phase probe).
        Consumes the experiment's selection/plan RNG streams. Returns
        (tasks_seq, idx_seq, mask_seq, num_samples, lane)."""
        params = self.params
        agent_names, _ = select_agents(params, epoch, self.participants,
                                       self.benign_names, self.select_rng)
        slots = np.array([self.client_slots[n] for n in agent_names],
                         np.int64)
        tasks = build_client_tasks(params, agent_names, epoch, slots,
                                   self.epochs_max, None)
        plan = build_batch_plan(
            [self.client_indices[n] for n in agent_names],
            [int(e) for e in tasks.num_epochs], int(params["batch_size"]),
            self.plan_rng, min_steps=self.steps_per_epoch,
            min_epochs=self.epochs_max)
        tasks_seq = jax.tree_util.tree_map(lambda l: jnp.asarray(l[None]),
                                           tasks)
        return (tasks_seq, jnp.asarray(plan.idx[None]),
                jnp.asarray(plan.mask[None]),
                jnp.asarray(plan.num_samples.astype(np.float32)),
                jnp.arange(len(agent_names), dtype=jnp.int32))

    def run_round(self, epoch: int) -> Dict[str, Any]:
        return self.finalize_round(self.dispatch_round(epoch))

    @property
    def _use_donated_round(self) -> bool:
        """Route through the fused round's donated twin (non-CPU, non-robust
        — see the gate in rounds.py) only when nothing re-reads the consumed
        buffers after dispatch: the health sentinel's check/rollback path
        does (it compares against the pre-round model), the overlap
        scheduler never runs the fused program at all, and the pipelined
        loop checkpoints round N's captured state (RoundInFlight.vars_after)
        AFTER round N+1's dispatch has consumed those very buffers."""
        pipelined_save = (bool(self.params.get("pipeline_rounds", False))
                          and bool(self.params["save_model"])
                          and self.folder is not None)
        return (self.engine.round_fn_donated is not None
                and self._sentinel is None and not self._overlap
                and not pipelined_save)

    def dispatch_round(self, epoch: int) -> RoundInFlight:
        """Telemetry/timing shell around :meth:`_dispatch`: the whole host
        planning + enqueue runs under the ``round/dispatch`` span (leaves
        ``round/plan``, ``round/stage``, ``round/enqueue``; a LOAN poison
        round's ``round/poison_probe`` inside the plan, a robust round's
        ``round/compute`` and ``round/screen_sync`` inside the enqueue) inside
        a profiler step annotation, so a device trace's step line carries the
        round; its perf_counter duration lands in ``round_result.csv`` as
        ``dispatch_time``."""
        t0 = time.perf_counter()
        self.telemetry.set_epoch(epoch)
        with jax.profiler.StepTraceAnnotation("round", step_num=epoch), \
                telemetry.span("round/dispatch", round=epoch):
            fl = self._dispatch(epoch, t0)
        fl.dispatch_time = time.perf_counter() - t0
        return fl

    def _dispatch(self, epoch: int, t0: float) -> RoundInFlight:
        """Host-side planning + every device dispatch for one round; no host
        sync — EXCEPT the LOAN adaptive-poison probe below, which must read
        the current global model's backdoor accuracy (loan_train.py:67-75)
        and therefore blocks on all previously dispatched work (pipelining
        degrades to sequential for those rounds, by necessity). The
        returned handle feeds `finalize_round`, which performs the round's
        single blocking transfer and the CSV/JSONL recording."""
        params = self.params
        with telemetry.span("round/plan", round=epoch) as plan_span:
            agent_names, adv_names = select_agents(
                params, epoch, self.participants, self.benign_names,
                self.select_rng)
            logger.info("Server Epoch:%d choose agents: %s", epoch,
                        agent_names)

            backdoor_acc = None
            if (params.type == cfg.TYPE_LOAN and self.is_poison_run
                    and any(params.adversary_slot_of(n) >= 0 and
                            epoch in params.poison_epochs_for(
                                params.adversary_slot_of(n))
                            for n in agent_names)):
                if (self.stale_poison_probe
                        and self.last_backdoor_acc is not None):
                    backdoor_acc = self.last_backdoor_acc  # N-1's battery
                else:
                    with self.guard.watch("round/poison_probe"), \
                            telemetry.span("round/poison_probe", round=epoch):
                        backdoor_acc = float(self.engine.backdoor_acc_fn(
                            self.global_vars))

            slots = np.array([self.client_slots[n] for n in agent_names],
                             np.int64)
            # one segment per global epoch in the aggregation interval
            # (image_train.py:50: the local model trains continuously across
            # the interval; the server applies the summed update once)
            seg_epochs = list(range(epoch, epoch + self.interval))
            tasks_list, idx_list, mask_list = [], [], []
            num_samples_np = None
            for ep in seg_epochs:
                tasks_s = build_client_tasks(params, agent_names, ep, slots,
                                             self.epochs_max, backdoor_acc)
                plan = build_batch_plan(
                    [self.client_indices[n] for n in agent_names],
                    [int(e) for e in tasks_s.num_epochs],
                    int(params["batch_size"]), self.plan_rng,
                    min_steps=self.steps_per_epoch,
                    min_epochs=self.epochs_max)
                if num_samples_np is None:
                    num_samples_np = plan.num_samples.astype(np.float32)
                tasks_list.append(tasks_s)
                idx_list.append(plan.idx)
                mask_list.append(plan.mask)

            if self.mesh is not None:
                from dba_mod_tpu.parallel.mesh import pad_clients
                c_pad = pad_clients(len(agent_names), self.mesh)
                if c_pad != len(agent_names):
                    if params.aggregation != cfg.AGGR_MEAN:
                        raise ValueError(
                            f"no_models={len(agent_names)} does not tile "
                            f"the {self.mesh.devices.size}-device mesh; pick "
                            "a multiple (inert-client padding is only sound "
                            "for FedAvg, whose divisor is the static "
                            "no_models)")
                    pad = c_pad - len(agent_names)
                    tasks_list = [_pad_tasks(t, pad, params.aggregation)
                                  for t in tasks_list]
                    idx_list = [np.pad(i, ((0, pad),) + ((0, 0),) * 3)
                                for i in idx_list]
                    mask_list = [np.pad(m, ((0, pad),) + ((0, 0),) * 3)
                                 for m in mask_list]
                    num_samples_np = np.pad(num_samples_np, (0, pad))
            if self.engine.streamed:
                # what a streamed step is made of: the positions it sends
                # through the layers (every stream of its rows), and the
                # client-steps the round's one-after-another loop runs
                steps = int(sum(m.any(axis=-1).sum() for m in mask_list))
                plan_span.count(
                    tokens_step=int(np.prod(self.model_def.input_shape)
                                    * int(params["batch_size"])
                                    * self.model_def.streams),
                    client_steps=steps,
                    **({"block_length": self.model_def.block_length}
                       if self.model_def.block_length else {}),
                    # a model with a blocked attention kernel: the tiles one
                    # forward pass over a row visits, and what else its
                    # ModelDef says of them
                    **(dict(zip(
                        ("attention_tiles_run", "attention_tiles_all"),
                        self.model_def.attention_tiles))
                       if (self.model_def.block_length
                           or self.model_def.attention_counts) else {}),
                    **dict(self.model_def.attention_counts))
            plan_span.count(
                **plan_step_counts(
                    mask_list, STEP_CHUNK,
                    1 if self.sequential_debug else self.engine.wide_from),
                **(evaluation.battery_eval_counts(
                    tasks_list, self.is_poison_run, bool(params["baseline"]),
                    self.engine.forensics, self.engine.clean_jobs)
                   if self.local_eval else {}))

        with telemetry.span("round/stage", round=epoch):
            tasks_seq = jax.tree_util.tree_map(
                lambda *ls: jnp.asarray(np.stack(ls)), *tasks_list)
            idx_seq = jnp.asarray(np.stack(idx_list))
            mask_seq = jnp.asarray(np.stack(mask_list))
            ns_dev = jnp.asarray(num_samples_np)
            if self.mesh is not None:
                from dba_mod_tpu.parallel.mesh import shard_round_inputs
                tasks_seq, idx_seq, mask_seq, ns_dev = shard_round_inputs(
                    self.mesh, tasks_seq, idx_seq, mask_seq, ns_dev)

            self.rng_key, round_key = jax.random.split(self.rng_key)
            rng_train, rng_agg = jax.random.split(round_key)
            lane = jnp.arange(idx_seq.shape[1], dtype=jnp.int32)
        # Three dispatch shapes: the fused round (one program, one dispatch —
        # the perf path, whatever the `telemetry` knob says), the robust
        # fused round (adds the screening sync + host retry loop), and the
        # SPLIT path of sequential_debug (clients one by one, then the same
        # aggregate/eval programs the fused round inlines). Per-phase device
        # time of the fused round is read from its `phase/` scopes under a
        # profiler trace (rounds.py::_round), not by splitting it.
        with telemetry.span("round/enqueue", round=epoch):
            if self.sequential_debug:
                train = self._train_sequential(tasks_seq, idx_seq, mask_seq,
                                               rng_train)
                return self._finish_split_round(
                    epoch, t0, seg_epochs, agent_names, adv_names,
                    tasks_list, mask_list, tasks_seq, mask_seq, ns_dev,
                    rng_agg, train)
            if self._overlap:
                return self._dispatch_overlap(
                    epoch, t0, seg_epochs, agent_names, adv_names,
                    tasks_list, mask_list, tasks_seq, idx_seq, mask_seq,
                    lane, ns_dev, rng_train, rng_agg)
            if self.engine.robust:
                return self._dispatch_robust(
                    epoch, t0, seg_epochs, agent_names, adv_names,
                    tasks_list, mask_list, tasks_seq, idx_seq, mask_seq,
                    lane, ns_dev, rng_train, rng_agg)
            # one program, one dispatch: train → aggregate → evals (the
            # donated twin when the gate allows — same program, XLA may
            # reuse the consumed state buffers in place)
            rf = (self.engine.round_fn_donated if self._use_donated_round
                  else self.engine.round_fn)
            if self.engine.streamed:
                (new_vars, new_fg, self.engine.workspace,
                 payload) = rf(
                    self.global_vars, self.fg_state,
                    self.engine.round_workspace(self.global_vars), tasks_seq,
                    idx_seq, mask_seq, lane, ns_dev, rng_train, rng_agg,
                    self.device_data.train_source)
            else:
                new_vars, new_fg, payload = rf(
                    self.global_vars, self.fg_state, tasks_seq, idx_seq,
                    mask_seq, lane, ns_dev, rng_train, rng_agg)
            rolled = False
            if self._sentinel is not None:
                new_vars, payload, rolled = self._health_gate(
                    epoch, self.global_vars, new_vars, payload)
                if rolled:
                    new_fg = self.fg_state
            self.global_vars = new_vars
            self.fg_state = new_fg
            return RoundInFlight(
                epoch=epoch, t0=t0, seg_epochs=seg_epochs,
                agent_names=agent_names, adv_names=adv_names,
                tasks_list=tasks_list, mask_list=mask_list, payload=payload,
                forced_degraded=rolled,
                vars_after=new_vars, fg_after=new_fg,
                rng_after=self._snapshot_rng())

    def _finish_split_round(self, epoch, t0, seg_epochs, agent_names,
                            adv_names, tasks_list, mask_list, tasks_seq,
                            mask_seq, ns_dev, rng_agg,
                            train) -> RoundInFlight:
        """Aggregate + eval batteries + payload assembly for
        sequential_debug's split dispatch — the same tail the fused round
        program runs on device."""
        params = self.params
        tasks_first = jax.tree_util.tree_map(lambda l: l[0], tasks_seq)
        from dba_mod_tpu.fl.rounds import nbt_client_deltas
        with self.guard.watch("round/aggregate"), \
                telemetry.span("round/aggregate", round=epoch):
            result = self.engine.aggregate_fn(
                self.global_vars, self.fg_state, train.deltas,
                train.fg_grads, train.fg_feature,
                tasks_first.participant_id, ns_dev, rng_agg,
                nbt_client_deltas(mask_seq, tasks_seq.scale))
            self.telemetry.sync(result.new_vars)

        # dispatch every eval before any host sync — one blocking transfer,
        # deferred to finalize_round so a caller can overlap the next round.
        # (With telemetry on, the instrumented standalone batteries sync
        # here instead: eval/local + eval/global span times in exchange for
        # the pipeline overlap — sequential_debug only.)
        prev_deltas = (train.seg_deltas[-1] if train.seg_deltas else
                       jax.tree_util.tree_map(jnp.zeros_like, train.deltas))
        locals_dev = (self.engine.local_evals_fn(
            self.global_vars, train.deltas, tasks_seq, prev_deltas)
            if self.local_eval else None)
        seg_locals_dev = None
        if self.local_eval and self.engine.seg_local_evals_fn is not None:
            seg_locals_dev = self.engine.seg_local_evals_fn(
                self.global_vars, train.seg_deltas, tasks_seq)
        globals_dev = self.engine.global_evals_fn(result.new_vars)
        fstats_dev = None
        if self.engine.forensic_fn is not None:
            # must see the PRE-aggregation globals (the cosine baseline is
            # "applied update" = new - old), so compute before reassignment
            fstats_dev = self.engine.forensic_fn(
                self.global_vars, result.new_vars, train.deltas,
                result.num_oracle_calls)
        track = (bool(params.get("vis_train_batch_loss"))
                 or bool(params.get("batch_track_distance")))
        batch_dev = (train.batch_loss, train.batch_dist) if track else None
        payload = (locals_dev, globals_dev, train.metrics, train.delta_norms,
                   result.wv, result.alpha, batch_dev, result.is_updated,
                   seg_locals_dev, None, fstats_dev)
        new_vars, new_fg = result.new_vars, result.new_fg_state
        rolled = False
        if self._sentinel is not None:
            new_vars, payload, rolled = self._health_gate(
                epoch, self.global_vars, new_vars, payload)
            if rolled:
                new_fg = self.fg_state
        self.global_vars = new_vars
        self.fg_state = new_fg
        return RoundInFlight(epoch=epoch, t0=t0, seg_epochs=seg_epochs,
                             agent_names=agent_names, adv_names=adv_names,
                             tasks_list=tasks_list, mask_list=mask_list,
                             payload=payload, forced_degraded=rolled,
                             vars_after=self.global_vars,
                             fg_after=self.fg_state,
                             rng_after=self._snapshot_rng())

    def _zero_deltas(self, n_clients: int):
        """A [C]-stacked all-zero delta tree — the stale lane's replay
        source before any round has been submitted."""
        tree = jax.tree_util.tree_map(
            lambda l: jnp.zeros((n_clients,) + l.shape, l.dtype),
            self.global_vars)
        if self.mesh is not None:
            from dba_mod_tpu.parallel.mesh import client_sharding
            tree = jax.device_put(tree, client_sharding(self.mesh))
        return tree

    def _robust_round_args(self, epoch: int, n_clients: int,
                           norm_mult: Optional[float] = None,
                           use_carry: bool = False):
        """The extra (rng_f, prev_deltas, norm_mult) inputs of the robust
        round program; () when the fault layer is off. The fault key is a
        pure function of (fault_seed, epoch) — independent of every other
        RNG stream, so fault schedules reproduce across runs and retries."""
        if not self.engine.robust:
            return ()
        rng_f = jax.random.fold_in(self._fault_key, epoch)
        if self.engine.fault_cfg.stale_enabled:
            prev = (self._prev_deltas
                    if use_carry and self._prev_deltas is not None
                    else self._zero_deltas(n_clients))
        else:
            prev = ()
        nm = self.engine.base_norm_mult if norm_mult is None else norm_mult
        return (rng_f, prev, jnp.float32(nm))

    def _health_check(self, epoch, vars_before, new_vars):
        """The sentinel decision alone — check the merged model BEFORE
        anything of round N+1 commits (the overlap scheduler calls this
        between the core program and the eval dispatch; the serial paths
        via _health_gate below). Returns (vars_to_commit, rolled_back);
        on a healthy merge the sentinel's EMA/ring commit happens here."""
        healthy, unorm = self._sentinel.check(vars_before, new_vars)
        if healthy:
            self._sentinel.commit(epoch, new_vars, unorm)
            return new_vars, False
        self.telemetry.counter("health_rollbacks").inc()
        target = self._sentinel.rollback_target(vars_before)
        logger.warning(
            "epoch %d: unhealthy aggregate (update norm %.3g vs EMA %.3g, "
            "band %.1fx); rolled back to last-good model", epoch, unorm,
            self._sentinel.ema, self._sentinel.band)
        return target, True

    def _health_gate(self, epoch, vars_before, new_vars, payload):
        """Post-merge sentinel for the non-retrying SERIAL dispatch paths:
        _health_check, plus — because those paths already ran the global
        battery on the pre-rollback model — a re-run on the restored model
        spliced into the payload so the recorded round stays finite.
        Returns (vars, payload, rolled_back)."""
        target, rolled = self._health_check(epoch, vars_before, new_vars)
        if not rolled:
            return target, payload, False
        globals_dev = self.engine.global_evals_fn(target)
        return target, payload[:1] + (globals_dev,) + payload[2:], True

    @staticmethod
    def _escalate_norm_mult(cur: float) -> float:
        """Retry-k screening escalation: switch the norm screen on if it was
        off (10× the survivor median catches any blowup that slipped a
        finite-only screen), then halve it each further retry, floored at
        1× the median — tighter than that would quarantine the majority."""
        return 10.0 if cur <= 0 else max(cur / 2.0, 1.0)

    def _dispatch_robust(self, epoch, t0, seg_epochs, agent_names,
                         adv_names, tasks_list, mask_list, tasks_seq,
                         idx_seq, mask_seq, lane, ns_dev, rng_train,
                         rng_agg) -> RoundInFlight:
        """The robust round dispatch: run the fused round program, then —
        only when screening is on — check the post-aggregation model is
        finite (ONE host sync; this is what pipeline depth costs under the
        fault layer) and re-run the round from the captured pre-round state
        with escalated screening up to max_round_retries. If retries run
        out, force a degraded round: restore the pre-round state, re-run
        the global battery on it, and record the degradation."""
        vars_before, fg_before = self.global_vars, self.fg_state
        C = int(idx_seq.shape[1])
        norm_mult: Optional[float] = None
        retries = 0
        healthy, unorm = True, 0.0
        while True:
            extra = self._robust_round_args(epoch, C, norm_mult=norm_mult,
                                            use_carry=True)
            # the robust round stays ONE fused program (the screening sync
            # below is the pipeline cost it already pays) — telemetry times
            # it as a single round/compute span per attempt
            with telemetry.span("round/compute", round=epoch):
                new_vars, new_fg, payload, deltas_out = self.engine.round_fn(
                    vars_before, fg_before, tasks_seq, idx_seq, mask_seq,
                    lane, ns_dev, rng_train, rng_agg, *extra)
            if not self.engine.screening:
                finite = True  # unscreened injection: faults flow through
                if self._sentinel is not None:
                    # no norm screen to escalate — unhealthy goes straight
                    # to the rollback path below
                    healthy, unorm = self._sentinel.check(vars_before,
                                                          new_vars)
                break
            with self.guard.watch("round/screen_sync"), \
                    telemetry.span("round/screen_sync", round=epoch):
                finite = bool(payload[9].global_finite)  # the one host sync
            healthy, unorm = True, 0.0
            if finite and self._sentinel is not None:
                healthy, unorm = self._sentinel.check(vars_before, new_vars)
            if (finite and healthy) or retries >= self.max_round_retries:
                break
            retries += 1
            cur = (self.engine.base_norm_mult if norm_mult is None
                   else norm_mult)
            norm_mult = self._escalate_norm_mult(cur)
            if self.retry_backoff_s > 0:
                time.sleep(min(self.retry_backoff_s * 2 ** (retries - 1),
                               30.0))
            logger.warning(
                "epoch %d: aggregated model %s; retry %d/%d with "
                "norm screen at %.2f× median", epoch,
                "non-finite" if not finite else "outside the health band",
                retries, self.max_round_retries, norm_mult)
        forced = (self.engine.screening and not finite) or not healthy
        if forced:
            # retries exhausted and the aggregate is still non-finite (or
            # outside the health band): degrade — restore the last-good
            # model (the pre-round state when no ring is armed) and re-run
            # the global battery on it so the record stays finite
            logger.warning(
                "epoch %d: aggregated model %s after %d retries; degraded "
                "round (last-good model carried forward)", epoch,
                "non-finite" if not finite else "outside the health band",
                retries)
            new_vars = (self._sentinel.rollback_target(vars_before)
                        if self._sentinel is not None else vars_before)
            new_fg = fg_before
            if self._sentinel is not None and not healthy:
                self.telemetry.counter("health_rollbacks").inc()
            globals_dev = self.engine.global_evals_fn(new_vars)
            payload = payload[:1] + (globals_dev,) + payload[2:]
        elif self._sentinel is not None:
            self._sentinel.commit(epoch, new_vars, unorm)
        self.global_vars = new_vars
        self.fg_state = new_fg
        stale_on = self.engine.fault_cfg.stale_enabled
        if stale_on:
            self._prev_deltas = deltas_out
        return RoundInFlight(
            epoch=epoch, t0=t0, seg_epochs=seg_epochs,
            agent_names=agent_names, adv_names=adv_names,
            tasks_list=tasks_list, mask_list=mask_list, payload=payload,
            n_retries=retries, forced_degraded=forced,
            vars_after=new_vars, fg_after=new_fg,
            rng_after=self._snapshot_rng(),
            deltas_after=deltas_out if stale_on else None)

    def _dispatch_overlap(self, epoch, t0, seg_epochs, agent_names,
                          adv_names, tasks_list, mask_list, tasks_seq,
                          idx_seq, mask_seq, lane, ns_dev, rng_train,
                          rng_agg) -> RoundInFlight:
        """The overlap scheduler (overlap_eval): run the round CORE — the
        fused program minus its eval tail (train → [faults → screen] →
        aggregate) — commit the model update, THEN dispatch round N's eval
        batteries as separate programs against the retained pre-round
        buffers. The pipelined loop in _run_rounds dispatches round N+1's
        core immediately after this returns, so the batteries (pure
        functions of the superseded model) and the host fetch/record/
        checkpoint path run concurrently with N+1's train. Contracts:

        * bit-identity — the batteries are the same jitted programs the
          fused round inlines, on the same inputs (pre-fault deltas,
          pre-round globals, post-commit model); fused ≡ core+batteries is
          A/B-verified by tests/test_overlap.py;
        * sentinel-before-commit — _health_check gates the merged model
          between the core and the eval dispatch, so the sentinel observes
          round N before anything of N+1 is enqueued, exactly as on the
          serial path;
        * retry cancellation — a rejected robust attempt never had evals in
          flight (the core returns only train/aggregate state); the
          batteries dispatch once, for the accepted (or force-degraded)
          attempt, whose train deltas are identical across attempts
          (rng_train and the fault key are fixed per epoch)."""
        engine = self.engine
        vars_before, fg_before = self.global_vars, self.fg_state
        retries = 0
        forced = False
        deltas_out = ()
        if not engine.robust:
            new_vars, new_fg, payload, eval_in = engine.core_fn(
                vars_before, fg_before, tasks_seq, idx_seq, mask_seq, lane,
                ns_dev, rng_train, rng_agg)
            if self._sentinel is not None:
                new_vars, forced = self._health_check(epoch, vars_before,
                                                      new_vars)
                if forced:
                    new_fg = fg_before
        else:
            C = int(idx_seq.shape[1])
            norm_mult: Optional[float] = None
            healthy, unorm = True, 0.0
            while True:
                extra = self._robust_round_args(epoch, C,
                                                norm_mult=norm_mult,
                                                use_carry=True)
                with telemetry.span("round/compute", round=epoch):
                    (new_vars, new_fg, payload, deltas_out,
                     eval_in) = engine.core_fn(
                        vars_before, fg_before, tasks_seq, idx_seq,
                        mask_seq, lane, ns_dev, rng_train, rng_agg, *extra)
                if not engine.screening:
                    finite = True
                    if self._sentinel is not None:
                        healthy, unorm = self._sentinel.check(vars_before,
                                                              new_vars)
                    break
                with self.guard.watch("round/screen_sync"), \
                        telemetry.span("round/screen_sync", round=epoch):
                    finite = bool(payload[9].global_finite)
                healthy, unorm = True, 0.0
                if finite and self._sentinel is not None:
                    healthy, unorm = self._sentinel.check(vars_before,
                                                          new_vars)
                if (finite and healthy) or retries >= self.max_round_retries:
                    break
                retries += 1
                cur = (engine.base_norm_mult if norm_mult is None
                       else norm_mult)
                norm_mult = self._escalate_norm_mult(cur)
                if self.retry_backoff_s > 0:
                    time.sleep(min(
                        self.retry_backoff_s * 2 ** (retries - 1), 30.0))
                logger.warning(
                    "epoch %d: aggregated model %s; retry %d/%d with "
                    "norm screen at %.2f× median", epoch,
                    "non-finite" if not finite
                    else "outside the health band",
                    retries, self.max_round_retries, norm_mult)
            forced = (engine.screening and not finite) or not healthy
            if forced:
                logger.warning(
                    "epoch %d: aggregated model %s after %d retries; "
                    "degraded round (last-good model carried forward)",
                    epoch, "non-finite" if not finite
                    else "outside the health band", retries)
                new_vars = (self._sentinel.rollback_target(vars_before)
                            if self._sentinel is not None else vars_before)
                new_fg = fg_before
                if self._sentinel is not None and not healthy:
                    self.telemetry.counter("health_rollbacks").inc()
            elif self._sentinel is not None:
                self._sentinel.commit(epoch, new_vars, unorm)
        # the model update is decided — commit, so the caller can enqueue
        # round N+1's core before the batteries below have drained
        self.global_vars = new_vars
        self.fg_state = new_fg
        stale_on = engine.fault_cfg.stale_enabled
        if stale_on:
            self._prev_deltas = deltas_out
        # eval dispatch against snapshots of the superseded buffers. With a
        # second local device the inputs are copied there and the same
        # jitted batteries compile a per-device executable (their
        # closure-captured eval data is placed per executable and cached),
        # so N's eval compute itself overlaps N+1's train — otherwise the
        # batteries share device 0 behind N+1's enqueue and the overlap
        # hides the host-side fetch/record/checkpoint path.
        deltas_pre, prev_dev, seg_deltas = eval_in
        vars_old, vars_new = vars_before, new_vars
        (vars_old, vars_new, deltas_pre, prev_dev, seg_deltas,
         tasks_ev) = evaluation.place_eval_inputs(
            (vars_old, vars_new, deltas_pre, prev_dev, seg_deltas,
             tasks_seq), self._eval_device)
        locals_dev = (engine.local_evals_fn(vars_old, deltas_pre,
                                            tasks_ev, prev_dev)
                      if self.local_eval else None)
        seg_locals_dev = None
        if self.local_eval and engine.seg_local_evals_fn is not None:
            seg_locals_dev = engine.seg_local_evals_fn(
                vars_old, list(seg_deltas), tasks_ev)
        globals_dev = engine.global_evals_fn(vars_new)
        payload = ((locals_dev, globals_dev) + payload[2:8]
                   + (seg_locals_dev,) + payload[9:])
        fl = RoundInFlight(
            epoch=epoch, t0=t0, seg_epochs=seg_epochs,
            agent_names=agent_names, adv_names=adv_names,
            tasks_list=tasks_list, mask_list=mask_list, payload=payload,
            n_retries=retries, forced_degraded=forced,
            vars_after=new_vars, fg_after=new_fg,
            rng_after=self._snapshot_rng(),
            deltas_after=deltas_out if stale_on else None,
            overlapped=True)
        fl.eval_dispatch_t = time.perf_counter()
        return fl

    def _snapshot_rng(self) -> Dict[str, Any]:
        """Host snapshot of every RNG stream a round consumes, taken right
        after dispatch consumed them — the state a resumed run needs to
        replay round N+1 onward exactly (tests/test_full_state_resume.py)."""
        return {"select_rng": self.select_rng.getstate(),
                "plan_rng": self.plan_rng.get_state(),
                "rng_key": np.asarray(jax.random.key_data(self.rng_key))}

    def finalize_round(self, fl: RoundInFlight) -> Dict[str, Any]:
        """The round's wait for the device (``round/wait``), its one
        transfer (``round/fetch``) and its recording (``round/record``), the
        three leaves of ``round/finalize``, whose record also carries the
        host counters since the previous round's finalize ended
        (telemetry.py::RoundBoundary). Then, outside the span: the round's
        account, the ``slow round`` line if it stands out, and the
        exporters' flush."""
        self.telemetry.set_epoch(fl.epoch)
        with telemetry.span("round/finalize", round=fl.epoch) as fin_span:
            result = self._finalize(fl)
            fin_span.count(**self._boundary.counts())
        self.telemetry.flush_round(fl.epoch, self._boundary.close(fl.epoch))
        return result

    def _finalize(self, fl: RoundInFlight) -> Dict[str, Any]:
        t_fin = time.perf_counter()
        with self.guard.watch("round/finalize"):
            # the sync point where a wedged runtime stalls, hence the
            # watchdog zone (run_guard.py); `device_get` would wait as well:
            # apart, a late device and a slow transfer are two numbers
            with telemetry.span("round/wait", round=fl.epoch):
                jax.block_until_ready(fl.payload)
            with telemetry.span("round/fetch", round=fl.epoch):
                payload = jax.device_get(fl.payload)
            (locals_, globals_, metrics, delta_norms, wv, alpha,
             batches, is_updated, seg_locals, rstats, fstats) = payload[:11]
            # a streamed round appends what its model counted of its own work
            counts = payload[11] if len(payload) > 11 else None
        finalize_time = time.perf_counter() - t_fin
        # perf_counter durations (the old time.time() delta could jump under
        # clock adjustments); under pipeline_rounds round_time spans the
        # overlap with the next round's dispatch — dispatch_time and
        # finalize_time are the honest per-phase components
        times = {"round_time": time.perf_counter() - fl.t0,
                 "dispatch_time": fl.dispatch_time,
                 "finalize_time": finalize_time}
        if fl.overlapped:
            # honest attribution of the overlapped eval+sync work: of the
            # wall time since the batteries were enqueued, finalize only
            # BLOCKED for finalize_time — the rest drained behind whatever
            # the caller dispatched in between (round N+1's core under the
            # pipelined loop). Mirrored to the overlap/ telemetry family
            # when telemetry is wired (bench reads the experiment counters
            # directly — the pipelined loop runs with telemetry off).
            since_enqueue = time.perf_counter() - fl.eval_dispatch_t
            hidden = max(0.0, since_enqueue - finalize_time)
            self._overlap_rounds += 1
            self._overlap_hidden_s += hidden
            self._overlap_wait_s += finalize_time
            t = self.telemetry
            if t.enabled:
                t.counter("overlap/rounds").inc()
                t.gauge("overlap/hidden_eval_s").set(self._overlap_hidden_s)
                t.gauge("overlap/dispatch_ahead_depth").set(1.0)
                t.histogram("overlap/eval_wait_s").observe(finalize_time)
        self.last_is_updated = bool(is_updated)
        self.last_global_loss = float(globals_.clean.loss)
        if self.is_poison_run:
            self.last_backdoor_acc = float(globals_.poison.acc)
        # robust counters: from the jitted screen plus the host retry path
        # (a forced degradation restored the pre-round state host-side)
        robust = {"n_quarantined": 0, "n_dropped": 0,
                  "n_retries": int(fl.n_retries),
                  "degraded": bool(fl.forced_degraded)}
        if rstats is not None:
            robust["n_quarantined"] = int(rstats.n_quarantined)
            robust["n_dropped"] = int(rstats.n_dropped)
            robust["degraded"] = (bool(rstats.degraded)
                                  or bool(fl.forced_degraded))
        with telemetry.span("round/record", round=fl.epoch) as record_span:
            if counts is not None and int(counts.cells):
                record_span.count(
                    expert_tokens_held=int(counts.held),
                    expert_tokens_max=int(counts.max),
                    expert_tokens_mean=float(counts.held)
                    / int(counts.cells))
            if counts is not None and int(counts.rows[1]):
                record_span.count(expert_rows_run=int(counts.rows[0]),
                                  expert_rows_all=int(counts.rows[1]))
            if counts is not None and counts.tallies:
                # what the model's objective tallied over the real steps
                record_span.count(**{name: int(v) for name, v
                                     in counts.tallies.items()})
            written = (self.recorder.files_written,
                       self.recorder.bytes_written)
            self._record(fl.epoch, fl.seg_epochs, fl.agent_names,
                         fl.adv_names, fl.tasks_list, metrics, locals_,
                         globals_, delta_norms, wv, alpha, times, batches,
                         fl.mask_list, seg_locals, robust)
            # the recorder rewrites every file whole each round
            record_span.count(
                files=self.recorder.files_written - written[0],
                bytes=self.recorder.bytes_written - written[1])
            if self.forensics_writer is not None and fstats is not None:
                self._record_forensics(fl, locals_, delta_norms, wv, alpha,
                                       fstats, robust)
            self._update_round_registry(fl, robust, delta_norms, times)
        return {"epoch": fl.epoch, "agents": fl.agent_names,
                "global_acc": float(globals_.clean.acc),
                "backdoor_acc": (float(globals_.poison.acc)
                                 if self.is_poison_run else None),
                **times, **robust}

    def _update_round_registry(self, fl: RoundInFlight, robust: Dict[str,
                               Any], delta_norms, times) -> None:
        """Per-round metrics-registry update: the round's counters and its
        delta-norm and round-time observations. `finalize_round` flushes
        them, with the span-duration windows, once the round's last span has
        ended: one telemetry.jsonl line (mirrored to TB when wired)."""
        t = self.telemetry
        if not t.enabled:
            return
        t.counter("rounds").inc()
        if fl.n_retries:
            t.counter("round_retries").inc(fl.n_retries)
        if robust.get("n_quarantined"):
            t.counter("clients_quarantined").inc(robust["n_quarantined"])
        if robust.get("n_dropped"):
            t.counter("clients_dropped").inc(robust["n_dropped"])
        if robust.get("degraded"):
            t.counter("degraded_rounds").inc()
        for n in np.asarray(delta_norms).reshape(-1):
            t.histogram("delta_norm").observe(float(n))
        t.histogram("round_seconds").observe(times["round_time"])

    def _record_forensics(self, fl: RoundInFlight, locals_, delta_norms,
                          wv, alpha, fstats, robust) -> None:
        """One forensic record per round: host-side assembly of the jitted
        ForensicStats slot plus the identity/defense context only the
        experiment knows (names, adversary membership, defense weights,
        poison battery). Arrays are sliced to the real client count —
        trailing mesh-padding lanes carry no client."""
        from dba_mod_tpu.fl.rounds import REASON_NAMES
        params = self.params
        names = list(fl.agent_names)
        C = len(names)
        adv = set(params.adversary_list)
        pids = np.asarray(fl.tasks_list[0].participant_id)[:C]
        poison_acc = None
        if self.is_poison_run and locals_ is not None:
            poison_acc = np.asarray(locals_.poison_post.acc)[:C]
        robust_agg = params.aggregation != cfg.AGGR_MEAN
        self.forensics_writer.add_round(
            epoch=fl.epoch, aggregation=params.aggregation, names=names,
            participant_ids=pids,
            adversary_flags=[int(n in adv) for n in names],
            delta_norms=np.asarray(delta_norms)[:C],
            recv_norms=np.asarray(fstats.recv_norms)[:C],
            cosine=np.asarray(fstats.cosine_to_agg)[:C],
            verdict=np.asarray(fstats.verdict)[:C],
            reason_codes=np.asarray(fstats.reason)[:C],
            reason_names=REASON_NAMES,
            weights=np.asarray(wv)[:C] if robust_agg else None,
            alpha=np.asarray(alpha)[:C] if robust_agg else None,
            poison_acc=poison_acc,
            oracle_calls=int(fstats.oracle_calls),
            n_retries=int(robust.get("n_retries", 0)),
            degraded=bool(robust.get("degraded", False)))
        self.forensics_writer.save()

    def _train_sequential(self, tasks_seq, idx_seq, mask_seq, rng):
        """Sequential debug mode (SURVEY §7.2.4): run clients one at a time
        through the SAME per-client program (width-1 train_fn calls with the
        true lane index, so rng streams match the vmapped path), then stitch
        the stacked results back together for the shared aggregation path."""
        from dba_mod_tpu.fl.rounds import TrainResult
        C = idx_seq.shape[1]
        outs = []
        for c in range(C):
            t = jax.tree_util.tree_map(lambda l: l[:, c:c + 1], tasks_seq)
            outs.append(self.engine.train_fn(
                self.global_vars, t, idx_seq[:, c:c + 1],
                mask_seq[:, c:c + 1], jnp.asarray([c], jnp.int32), rng))
        cat0 = lambda *ls: jnp.concatenate(ls, axis=0)
        cat1 = lambda *ls: jnp.concatenate(ls, axis=1)
        n_seg_deltas = len(outs[0].seg_deltas)
        return TrainResult(
            deltas=jax.tree_util.tree_map(cat0, *[o.deltas for o in outs]),
            fg_grads=jax.tree_util.tree_map(cat0,
                                            *[o.fg_grads for o in outs]),
            fg_feature=jnp.concatenate([o.fg_feature for o in outs], 0),
            metrics=jax.tree_util.tree_map(cat1,
                                           *[o.metrics for o in outs]),
            delta_norms=jnp.concatenate([o.delta_norms for o in outs], 0),
            batch_loss=jnp.concatenate([o.batch_loss for o in outs], 1),
            batch_dist=jnp.concatenate([o.batch_dist for o in outs], 1),
            seg_deltas=[jax.tree_util.tree_map(
                cat0, *[o.seg_deltas[s] for o in outs])
                for s in range(n_seg_deltas)])

    # ------------------------------------------------------------- recording
    def _record(self, epoch, seg_epochs, agent_names, adv_names, tasks_list,
                metrics, locals_, globals_, delta_norms, wv, alpha, times,
                batches=None, mask_list=None, seg_locals=None, robust=None):
        # metrics leaves are [I, C, E]; tasks_list one ClientTask per segment.
        # Local clean evals: final segment from locals_, intermediate
        # segments (interval > 1) from seg_locals — matching the reference's
        # per-global-epoch cadence (image_train.py:268-271, :150-155). The
        # poison battery stays round-final: the reference runs it in the
        # poison branch against the round's submitted update.
        params = self.params
        rec = self.recorder
        tasks = tasks_list[-1]
        # round-final rows carry the round's LAST global epoch, like the
        # reference's temp_global_epoch = epoch + interval - 1 (main.py:196)
        final_ep = seg_epochs[-1]
        # per-client flags hold if ANY segment of the round poisoned
        # (a client may poison at epoch 3 of a (3,4) interval round)
        poisoning_any = np.zeros(len(agent_names), bool)
        adv_slot_any = np.full(len(agent_names), -1, np.int64)
        for t in tasks_list:
            poisoning_any |= np.asarray(t.poisoning_per_batch)[
                :len(agent_names)] > 0
            adv_slot_any = np.maximum(adv_slot_any,
                                      np.asarray(t.adv_slot)
                                      [:len(agent_names)])
        for c, name in enumerate(agent_names):
            for s, ep in enumerate(seg_epochs):
                n_e = int(tasks_list[s].num_epochs[c])
                for e in range(n_e):
                    count = max(float(metrics.count[s, c, e]), 1.0)
                    rec.add_train(name, (ep - 1) * n_e + e + 1, ep, e + 1,
                                  float(metrics.loss_sum[s, c, e]) / count,
                                  100.0 * float(metrics.correct[s, c, e])
                                  / count,
                                  int(metrics.correct[s, c, e]), int(count))
                if batches is not None:
                    # [I, C, E*S] per-batch channels; only steps whose batch
                    # mask is non-empty ran (padded epochs/steps are no-ops).
                    # The loss channel is benign-only: the reference calls
                    # train_batch_vis in the benign branch alone
                    # (image_train.py:225-228), while distance is tracked in
                    # both branches (:107-112, :235-240).
                    bloss, bdist = batches
                    S = mask_list[s].shape[2]
                    valid = mask_list[s][c].any(axis=-1).reshape(-1)  # [E*S]
                    seg_poisons = (np.asarray(
                        tasks_list[s].poisoning_per_batch)[c] > 0)
                    want_loss = (bool(params.get("vis_train_batch_loss"))
                                 and not seg_poisons)
                    want_dist = bool(params.get("batch_track_distance"))
                    for st in np.nonzero(valid)[0]:
                        e_i, b_i = int(st) // S, int(st) % S
                        tle = (ep - 1) * n_e + e_i + 1
                        if want_loss:
                            rec.add_batch_loss(name, tle, ep, e_i + 1, b_i, S,
                                               float(bloss[s, c, st]))
                        if want_dist:
                            rec.add_batch_distance(
                                name, tle, ep, e_i + 1, b_i, S,
                                float(bdist[s, c, st]))
            poisoning = bool(poisoning_any[c])
            # the FINAL segment's clean row gates on that segment's own
            # poisoning flag (a client may poison epoch 3 of a (3,4) round
            # and still get its benign epoch-4 row, image_train.py:267-271)
            final_seg_poisons = bool(
                np.asarray(tasks_list[-1].poisoning_per_batch)[c] > 0)
            baseline = bool(params["baseline"])
            if seg_locals is not None:
                # intermediate-segment rows (interval > 1): the reference
                # runs the whole battery inside the per-global-epoch loop —
                # same gating as the final segment below
                for s, seg_ev in enumerate(seg_locals):
                    ep_s = seg_epochs[s]
                    seg_poisons = (np.asarray(
                        tasks_list[s].poisoning_per_batch)[c] > 0)
                    if not (seg_poisons and baseline):
                        # image_train.py:148-155 gating
                        rec.add_test(name, ep_s,
                                     float(seg_ev.clean.loss[c]),
                                     float(seg_ev.clean.acc[c]),
                                     int(seg_ev.clean.correct[c]),
                                     int(seg_ev.clean.count[c]))
                    if seg_poisons and self.is_poison_run:
                        if not baseline:  # pre-scale row (:157-164)
                            rec.add_poisontest(
                                name, ep_s,
                                float(seg_ev.poison_pre.loss[c]),
                                float(seg_ev.poison_pre.acc[c]),
                                int(seg_ev.poison_pre.correct[c]),
                                int(seg_ev.poison_pre.count[c]))
                        # post-scale row (:275-282)
                        rec.add_poisontest(
                            name, ep_s,
                            float(seg_ev.poison_post.loss[c]),
                            float(seg_ev.poison_post.acc[c]),
                            int(seg_ev.poison_post.correct[c]),
                            int(seg_ev.poison_post.count[c]))
                    if (self.is_poison_run and int(np.asarray(
                            tasks_list[s].adv_slot)[c]) >= 0):
                        # per-agent trigger row runs for every adversary
                        # every global epoch (:285-295)
                        rec.add_triggertest(
                            name, f"{name}_trigger", "", ep_s,
                            float(seg_ev.agent_trigger.loss[c]),
                            float(seg_ev.agent_trigger.acc[c]),
                            int(seg_ev.agent_trigger.correct[c]),
                            int(seg_ev.agent_trigger.count[c]))
            if locals_ is not None:
                lr = locals_
                # the local clean eval for a poisoning client happens inside
                # `if not baseline` in the reference (image_train.py:148-155);
                # benign clients always get one (:267-271)
                if not (final_seg_poisons and baseline):
                    rec.add_test(name, final_ep, float(lr.clean.loss[c]),
                                 float(lr.clean.acc[c]),
                                 int(lr.clean.correct[c]),
                                 int(lr.clean.count[c]))
                if poisoning and self.is_poison_run:
                    if not baseline:
                        rec.add_poisontest(name, final_ep,
                                           float(lr.poison_pre.loss[c]),
                                           float(lr.poison_pre.acc[c]),
                                           int(lr.poison_pre.correct[c]),
                                           int(lr.poison_pre.count[c]))
                    rec.add_poisontest(name, final_ep,
                                       float(lr.poison_post.loss[c]),
                                       float(lr.poison_post.acc[c]),
                                       int(lr.poison_post.correct[c]),
                                       int(lr.poison_post.count[c]))
                if (self.is_poison_run and
                        int(adv_slot_any[c]) >= 0):
                    rec.add_triggertest(
                        name, f"{name}_trigger", "", final_ep,
                        float(lr.agent_trigger.loss[c]),
                        float(lr.agent_trigger.acc[c]),
                        int(lr.agent_trigger.correct[c]),
                        int(lr.agent_trigger.count[c]))
            if poisoning and not baseline:
                rec.scale_temp_one_row.extend(
                    [epoch, round(float(delta_norms[c]), 4)])

        rec.add_test("global", final_ep, float(globals_.clean.loss),
                     float(globals_.clean.acc), int(globals_.clean.correct),
                     int(globals_.clean.count))
        if self.is_poison_run:
            g = globals_
            rec.add_poisontest("global", final_ep, float(g.poison.loss),
                               float(g.poison.acc), int(g.poison.correct),
                               int(g.poison.count))
            rec.add_triggertest("global", "combine", "", final_ep,
                                float(g.poison.loss), float(g.poison.acc),
                                int(g.poison.correct), int(g.poison.count))
            if params.is_centralized_attack:
                # gated on centralized_test_trigger (main.py:226)
                names = [f"global_in_index_{j}_trigger"
                         for j in range(self.engine.num_global_triggers)]
            else:
                names = [f"global_in_{a}_trigger"
                         for a in params.adversary_list]
            for j, tname in enumerate(names):
                rec.add_triggertest(
                    "global", tname, "", final_ep,
                    float(g.per_trigger.loss[j]), float(g.per_trigger.acc[j]),
                    int(g.per_trigger.correct[j]),
                    int(g.per_trigger.count[j]))
        if rec.scale_temp_one_row:
            rec.scale_temp_one_row.append(round(float(globals_.clean.acc), 4))
        if self.params.aggregation != cfg.AGGR_MEAN:
            rec.add_weight_result(list(agent_names), wv.tolist(),
                                  alpha.tolist(), epoch=epoch)
        rec.add_round_json(
            epoch=epoch, agents=[str(a) for a in agent_names],
            adversaries=[str(a) for a in adv_names],
            is_updated=self.last_is_updated,
            global_acc=float(globals_.clean.acc),
            global_loss=float(globals_.clean.loss),
            backdoor_acc=(float(globals_.poison.acc)
                          if self.is_poison_run else None),
            **times, **(robust or {}))
        rec.save(self.is_poison_run)

    # ------------------------------------------------------------------- run
    @property
    def checkpoint_manager(self) -> ckpt.CheckpointManager:
        """Manifest/retention policy bound to the CURRENT run folder —
        rebuilt when the folder changes (tests reassign ``exp.folder``
        after construction). Pending async-manifest state is module-level
        in checkpoint.py, so a rebuild loses nothing."""
        if self._ckpt_mgr is None or self._ckpt_mgr.folder != self.folder:
            self._ckpt_mgr = ckpt.CheckpointManager(
                self.folder,
                keep_last_n=int(self.params.get("keep_last_n", 0)),
                manifests=bool(self.params.get("checkpoint_manifests",
                                               True)))
        return self._ckpt_mgr

    def save_model(self, epoch: int, fl: Optional[RoundInFlight] = None,
                   async_save: bool = False,
                   extra_aux: Optional[Dict[str, Any]] = None):
        """Checkpoint the round's post-aggregation state. With `fl`, saves
        the state captured at that round's dispatch (required under
        pipelining — the live attributes already belong to the next round);
        `async_save` routes through orbax's AsyncCheckpointer so the commit
        overlaps the next round's compute (run() waits before returning).
        Every committed snapshot gets an integrity manifest (immediately
        for sync saves; once the commit provably landed for async ones),
        then retention GC runs (checkpoint.py::CheckpointManager).
        `extra_aux` merges additional keys into the full-state sidecar —
        the buffered-async driver rides its streaming state (arrival heap,
        buffer, live cohorts) here under ``async_state``."""
        params = self.params
        if not params["save_model"] or self.folder is None:
            return
        mgr = self.checkpoint_manager
        with telemetry.span("round/checkpoint", round=epoch):
            model_vars = fl.vars_after if fl is not None else self.global_vars
            fg_state = fl.fg_after if fl is not None else self.fg_state
            rng = fl.rng_after if fl is not None else self._snapshot_rng()
            path = self.folder / "model_last.pt.tar"
            lr = float(params["lr"])
            written = [path]
            if epoch in list(params["save_on_epochs"]):
                written.append(Path(str(path) + f".epoch_{epoch}"))
            # best-val snapshot whenever the global eval loss improves
            # (helper.py:433-435, called with epoch_loss from main.py:233)
            if self.last_global_loss < self.best_loss:
                written.append(Path(str(path) + ".best"))
                self.best_loss = self.last_global_loss
            # before force=True replaces committed snapshots: land owed
            # async manifests, drop queued ones for the doomed dirs, and
            # clone each verified snapshot to <name>.prev so a kill at any
            # instant of this save leaves a verified resume point
            mgr.prepare_overwrite(written, async_save,
                                  writer=jax.process_index() == 0)
            for p in written:
                ckpt.save_checkpoint(p, model_vars, epoch, lr,
                                     async_save=async_save)
            # full-state sidecar (deviation, documented in checkpoint.py):
            # the reference loses FoolsGold memory / best loss / RNG position
            # on restart; we persist them so resume replays the exact
            # trajectory. Every snapshot gets one — resuming from
            # .epoch_N/.best must not silently reset the defense. One writer
            # on multi-process.
            mem = fg_state.memory
            if jax.process_index() == 0 and (jax.process_count() == 1
                                             or mem.is_fully_addressable):
                aux = {"epoch": int(epoch),
                       "fg_memory": np.asarray(mem),
                       "best_loss": float(self.best_loss),
                       "last_backdoor_acc": self.last_backdoor_acc,
                       **rng}
                if extra_aux:
                    aux.update(extra_aux)
                if self.engine.fault_cfg.stale_enabled:
                    # the stale lane's replay source: what the server
                    # received THIS round (deltas_after under pipelining —
                    # the live _prev_deltas may already be next round's).
                    # Model-sized × C, but the lane is single-process-only
                    # and opt-in; without it the first post-resume stale
                    # replay would silently replay a zero delta.
                    src = (fl.deltas_after if fl is not None
                           else self._prev_deltas)
                    if src is not None:
                        aux["prev_deltas"] = jax.tree_util.tree_map(
                            np.asarray, src)
                for p in written:
                    ckpt.save_aux_state(p, aux)
            if jax.process_index() == 0:  # one manifest/GC writer
                # manifests cover the step dir + the sidecar when one was
                # written (sharded-fg multi-host runs skip the sidecar but
                # must still get verifiable — hence resumable — snapshots);
                # sync saves get them now, async ones once committed
                mgr.note_saved(written, epoch, async_save=async_save)
                mgr.gc()

    def run(self, epochs: Optional[int] = None) -> Dict[str, Any]:
        self.interrupted = False
        from dba_mod_tpu.parallel.distributed import PeerLostError
        # the guard context installs the SIGTERM/SIGINT handlers around the
        # run loop (and restores the previous ones after) — a no-op unless
        # graceful_shutdown is on
        with self.guard:
            if self.peers is not None:
                # heartbeats + the peer-lost watchdog verdict live exactly
                # as long as the round loop
                self.peers.start()
                self.guard.attach_peer_health(self.peers)
            try:
                return self._run_rounds(epochs)
            except PeerLostError:
                telemetry.count("run/peer_lost")
                raise
            except Exception as exc:
                # classify: a collective that failed because its peer
                # vanished must surface as PeerLost (exit 77, relaunch
                # shrunk), not as a generic crash — poll the heartbeats
                # long enough for a real loss to become stale
                lost = self._classify_peer_failure()
                if lost:
                    telemetry.count("run/peer_lost")
                    raise PeerLostError(
                        lost, detail=f"collective failure: "
                        f"{type(exc).__name__}") from exc
                raise
            finally:
                try:
                    # EVERY exit path — normal return, graceful stop, or a
                    # mid-run exception — must land the in-flight async
                    # commit (force=True already deleted the previous
                    # model_last) and write the manifests it was owed
                    with self.guard.watch("checkpoint/wait_async"):
                        ckpt.wait_for_async_saves()
                finally:
                    if self.peers is not None:
                        self.guard.attach_peer_health(None)
                        self.peers.stop()
                    # end-of-run telemetry: final trace.json flush + the
                    # printed phase-summary table (p50/p95 per span,
                    # recompile count, peak device memory) — also on a
                    # mid-run exception, so a crashed run still leaves a
                    # loadable trace
                    self._finish_telemetry()

    def _classify_peer_failure(self) -> List[int]:
        """An exception escaped the round loop: slow peer or gone peer?
        Poll the heartbeats for up to one timeout window — a dead host's
        file goes stale within it, a live-but-erroring world's does not.
        Empty list = not a peer loss (re-raise the original)."""
        if self.peers is None:
            return []
        deadline = (time.monotonic() + self.peers.timeout_s
                    + self.peers.interval_s)
        while True:
            lost = self.peers.lost_peers()
            if lost or time.monotonic() >= deadline:
                return lost
            time.sleep(min(max(self.peers.interval_s, 0.05), 0.25))

    def _finish_telemetry(self) -> None:
        t = self.telemetry
        if not t.enabled:
            return
        t.record_memory()
        t.close()
        print(t.summary_table())

    def _run_rounds(self, epochs: Optional[int] = None) -> Dict[str, Any]:
        if str(self.params.get("mode", "sync")) == "async":
            # the buffered-async engine owns the whole loop: cohort
            # dispatch, arrival simulation, K-arrival merges, recording,
            # and checkpointing (fl/async_rounds.py). run()'s guard /
            # wait_for_async_saves / telemetry teardown still wrap it.
            from dba_mod_tpu.fl.async_rounds import AsyncDriver
            return AsyncDriver(self).run(epochs)
        last: Dict[str, Any] = {}
        end = epochs if epochs is not None else int(self.params["epochs"])
        profile_dir = str(self.params.get("profile_dir", "") or "")
        # pipeline_rounds: overlap round N's host fetch/record with round
        # N+1's device compute (depth 1). Checkpoints ride orbax async saves
        # — save_model(fl=...) uses the state captured at dispatch, and
        # AsyncCheckpointer serializes commits, so per-epoch checkpoints
        # land in program order (tests/test_async_checkpoint.py). Profiling
        # forces sequential rounds (a trace needs one round's dispatch+fetch
        # alone on the timeline), and so does telemetry: finalize(N) flushes
        # round N's histogram window, which dispatch(N+1) — fully synced on
        # the split path — would otherwise pollute with round N+1's spans.
        # overlap_eval rides the same depth-1 loop: its dispatch returns
        # with round N's eval batteries still in flight, so dispatching
        # N+1's core before finalizing N is what actually hides them
        if ((bool(self.params.get("pipeline_rounds", False))
                or self._overlap)
                and not profile_dir and not self.telemetry.enabled):
            def finalize_and_log(fl):
                r = self.finalize_round(fl)
                self.save_model(fl.epoch, fl=fl, async_save=True)
                # one full round has finished end-to-end: every program a
                # steady-state round needs has compiled — later compiles
                # are retrace regressions (telemetry counts + warns)
                self.telemetry.mark_warm()
                logger.info("epoch %d done in %.2fs acc=%.2f backdoor=%s",
                            r["epoch"], r["round_time"], r["global_acc"],
                            r["backdoor_acc"])
                return r

            # (run()'s finally holds the wait_for_async_saves that used to
            # live here — it now covers every exit path, not just this one)
            pending: Optional[RoundInFlight] = None
            for epoch in range(self.start_epoch, end + 1, self.interval):
                if self.guard.stop_requested:
                    self._note_interrupted(epoch)
                    break
                self._round_boundary(epoch)
                fl = self.dispatch_round(epoch)
                if pending is not None:
                    last = finalize_and_log(pending)
                pending = fl
            if pending is not None:
                last = finalize_and_log(pending)
            return last
        for epoch in range(self.start_epoch, end + 1, self.interval):
            if self.guard.stop_requested:
                # round-boundary stop: the previous round's save_model
                # already committed a verified checkpoint and the recorder
                # saved — nothing mid-flight to lose
                self._note_interrupted(epoch)
                break
            self._round_boundary(epoch)
            if profile_dir and epoch == self.start_epoch + self.interval:
                # trace the first post-compile round (SURVEY §5 tracing row)
                with jax.profiler.trace(profile_dir):
                    last = self.run_round(epoch)
            else:
                last = self.run_round(epoch)
            self.save_model(epoch)
            self.telemetry.mark_warm()  # first full round ends warmup
            logger.info("epoch %d done in %.2fs acc=%.2f backdoor=%s",
                        epoch, last["round_time"], last["global_acc"],
                        last["backdoor_acc"])
        return last

    def _round_boundary(self, epoch: int) -> None:
        """Elastic round-boundary work, in order: (1) the host-loss fault
        lane may SIGKILL this process (multi-process runs — the designated
        victim dies HERE, at a boundary, so committed rounds stay
        committed); (2) beat + peer staleness check, optionally the
        bounded barrier — a dead peer surfaces as PeerLostError now,
        outside any collective, instead of a wedged program. No-op when
        the elastic layer and the host-loss lane are off."""
        self._maybe_kill_self(epoch)
        if self.peers is None:
            return
        if self.heartbeat_barrier_s > 0:
            self.peers.barrier(epoch, self.heartbeat_barrier_s)
        else:
            self.peers.check(epoch)

    def _maybe_kill_self(self, epoch: int) -> None:
        """Multi-process enactment of the host-loss fault lane
        (fl/faults.py::host_loss_victim): every process derives the same
        per-epoch victim from (fault_seed, epoch); the victim SIGKILLs
        itself — no handlers, no cleanup, exactly the preemption shape the
        elastic layer must survive. Single-process runs enact the lane
        inside the round program instead (host_loss_in_program)."""
        from dba_mod_tpu.fl import faults as flt
        fcfg = self.engine.fault_cfg
        if (not fcfg.host_loss_enabled or fcfg.host_loss_in_program
                or jax.process_count() == 1):
            return
        rng_f = jax.random.fold_in(self._fault_key, epoch)
        victim = int(flt.host_loss_victim(fcfg, rng_f))
        if victim != jax.process_index():
            return
        logger.critical(
            "fault injection: host-loss lane kills process %d at the "
            "epoch-%d boundary (SIGKILL — survivors must detect, exit %d, "
            "and relaunch shrunk)", victim, epoch,
            run_guard.EXIT_PEER_LOST)
        logging.shutdown()
        os.kill(os.getpid(), signal.SIGKILL)

    def _note_interrupted(self, next_epoch: int) -> None:
        """A graceful-stop request was honored at a round boundary: record
        it so the CLI can exit with run_guard.EXIT_INTERRUPTED and a
        wrapper can relaunch with ``--resume auto``."""
        self.interrupted = True
        telemetry.count("run/interrupted")
        logger.warning(
            "graceful stop honored at the round boundary before epoch %d — "
            "writing final state and exiting (resume with --resume auto)",
            next_epoch)
