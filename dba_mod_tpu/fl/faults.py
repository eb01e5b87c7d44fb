"""Deterministic fault injection for the round path.

The reference simulator (and the seed port) assumes every selected client
returns a finite, well-formed delta. Real federated deployments — and the
robust-aggregation literature this framework exists to study — are defined by
partial participation and byzantine payloads. This module perturbs per-round
client *outcomes* (what the server receives), never the training computation
itself: faults model the uplink, not the local SGD.

Fault classes (per client, per round; mutually exclusive, resolved in
priority order host-loss > dropout > corrupt > blowup > stale):

  dropout — the client never reports. Its payload is zeroed and it is
            excluded from the survivor mask (the server always knows who
            reported, independent of any screening).
  corrupt — the payload arrives NaN/Inf-poisoned (bit flips, truncated
            uploads). Caught by the server's finite screen.
  blowup  — the payload is scaled by ``fault_blowup_factor`` (fp overflow,
            exploding local training). Caught by the norm screen when
            enabled; otherwise it may push the aggregated model non-finite,
            which the round-level retry path handles.
  stale   — the client replays the delta it *submitted* the previous round
            (straggler whose round-N upload arrives at round N+1). Finite
            and norm-plausible, hence deliberately NOT screenable. Applies
            to deltas only: FoolsGold aggregates gradient accumulators, so
            under FoolsGold a stale client is a no-op by construction.

Host-level lane (``fault_host_loss_prob``, PR 6): a whole *host* vanishes
at a round boundary — the deployment-layer failure the elastic layer
(parallel/distributed.py) exists to survive. The victim is a pure
function of the same per-round fault key (:func:`host_loss_victim`), so
both enactments agree on who dies and when:

  - multi-process runs: the experiment driver evaluates the victim
    host-side at the round boundary and the designated process SIGKILLs
    itself — the survivors then exercise the real detect → classify →
    restart-shrunk path (heartbeats, exit 77, shrunk relaunch) in CI
    rather than hoping it works;
  - single-process runs (``fault_num_hosts`` virtual hosts): the victim
    host's whole contiguous client slice is dropped through the survivor
    mask inside the round program — the masked-cohort semantics a real
    shrink converges to, without needing processes.

The plan is a pure function of ``(fault_seed, epoch)`` via ``jax.random`` —
a fault schedule reproduces exactly across runs and resumes, and is
independent of every other RNG stream (selection, plans, training). The
stale lane's replay source (last round's submitted deltas) is checkpointed
in the full-state aux sidecar (``save_model`` runs), so a resumed run's
first stale replay is faithful; only sidecar-less resumes (pretrain /
model-only checkpoints) fall back to a zero delta. All injection runs
inside the jitted round program; with ``fault_injection: false`` none of
it is traced, so the fault path costs nothing when disabled.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from dba_mod_tpu import config as cfg
from dba_mod_tpu.ops.aggregation import _bc_mask as _bc

logger = logging.getLogger("dba_mod_tpu")


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Static (compile-time) fault-injection knobs."""
    enabled: bool
    dropout_prob: float
    corrupt_prob: float
    blowup_prob: float
    blowup_factor: float
    stale_prob: float
    seed: int
    # host-level lane: P(the round loses one whole host) and the host
    # count the client axis is partitioned into. `host_loss_in_program` is
    # the enactment switch — True (single-process) masks the victim's
    # client slice inside the round program; False (multi-process) leaves
    # the round program untouched and the experiment driver kills the
    # victim process at the boundary instead (the loss must not be
    # double-counted).
    host_loss_prob: float = 0.0
    num_hosts: int = 0
    host_loss_in_program: bool = True

    @property
    def stale_enabled(self) -> bool:
        return self.enabled and self.stale_prob > 0.0

    @property
    def host_loss_enabled(self) -> bool:
        return self.enabled and self.host_loss_prob > 0.0

    @classmethod
    def from_params(cls, p: cfg.Params) -> "FaultConfig":
        probs = {k: float(p.get(f"fault_{k}_prob", 0.0))
                 for k in ("dropout", "corrupt", "blowup", "stale",
                           "host_loss")}
        for k, v in probs.items():
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"fault_{k}_prob={v} not in [0, 1]")
        enabled = bool(p.get("fault_injection", False))
        pc = jax.process_count()
        if pc > 1:
            # real hosts: the experiment kills the victim process at the
            # round boundary; the program sees only the consequences
            num_hosts, in_program = pc, False
        else:
            num_hosts, in_program = int(p.get("fault_num_hosts", 0)), True
            if enabled and probs["host_loss"] > 0.0 and num_hosts < 2:
                # NOT an error: a 2-process run with the lane on loses its
                # victim by design, the survivor exits 77, and the
                # supervisor relaunches ONE process with the same YAML —
                # raising here would break the exact recovery path the
                # lane exists to exercise. Single-process simulation needs
                # an explicit fault_num_hosts >= 2; without one the lane
                # is off, loudly.
                logger.warning(
                    "fault_host_loss_prob=%s ignored: single-process run "
                    "with fault_num_hosts=%d — set fault_num_hosts >= 2 "
                    "to simulate host loss through the survivor mask "
                    "(a shrunk-to-1 elastic relaunch lands here by "
                    "design and must start)", probs["host_loss"],
                    num_hosts)
                probs["host_loss"] = 0.0
        return cls(enabled=enabled,
                   dropout_prob=probs["dropout"],
                   corrupt_prob=probs["corrupt"],
                   blowup_prob=probs["blowup"],
                   blowup_factor=float(p.get("fault_blowup_factor", 1e8)),
                   stale_prob=probs["stale"],
                   seed=int(p.get("fault_seed", 0)),
                   host_loss_prob=probs["host_loss"],
                   num_hosts=num_hosts,
                   host_loss_in_program=in_program)


class FaultPlan(NamedTuple):
    """Per-client fault assignment for one round (all [C] bool)."""
    dropped: jax.Array
    corrupt: jax.Array
    blowup: jax.Array
    stale: jax.Array


# fold_in tag isolating the host-loss stream from the per-client draws:
# enabling the host lane must not reshuffle the client-lane assignments an
# existing fault_seed already produces (and vice versa)
_HOST_LANE_TAG = 0x4057


def host_loss_victim(fcfg: FaultConfig, rng: jax.Array) -> jax.Array:
    """Scalar victim for the host-loss lane: the host index the round
    loses, or -1 for no loss. Pure function of the per-round fault key, so
    the experiment driver (multi-process boundary kill) and the round
    program (single-process survivor-mask simulation) derive the SAME
    victim independently."""
    kl, kv = jax.random.split(jax.random.fold_in(rng, _HOST_LANE_TAG))
    lost = jax.random.uniform(kl, ()) < fcfg.host_loss_prob
    v = jax.random.randint(kv, (), 0, max(fcfg.num_hosts, 1))
    return jnp.where(lost, v, -1)


def host_of_lane(num_lanes: int, num_hosts: int) -> jax.Array:
    """[C] host index per client lane: contiguous proportional slices,
    the same leading-axis partition `parallel/mesh.py::_place` hands each
    process of a real multi-host run."""
    return (jnp.arange(num_lanes) * num_hosts) // max(num_lanes, 1)


def make_fault_plan(fcfg: FaultConfig, rng: jax.Array,
                    counted: jax.Array) -> FaultPlan:
    """Draw one round's fault assignment. ``counted`` ([C] bool) marks real
    clients — inert mesh-padding lanes never fault (their zero deltas must
    stay zero or padding would perturb FedAvg's static divisor). The
    host-loss lane resolves first (the whole host vanished — its clients
    can't independently corrupt or straggle) and folds into ``dropped``:
    downstream, a host-dropped client is exactly a client that never
    reported."""
    kd, kc, kb, ks = jax.random.split(rng, 4)

    def draw(k, p, free):
        hit = (jax.random.uniform(k, counted.shape) < p) & free
        return hit, free & ~hit

    free = counted
    host_dropped = jnp.zeros_like(counted)
    if fcfg.host_loss_enabled and fcfg.host_loss_in_program:
        victim = host_loss_victim(fcfg, rng)
        hosts = host_of_lane(counted.shape[0], fcfg.num_hosts)
        host_dropped = (hosts == victim) & counted
        free = free & ~host_dropped
    dropped, free = draw(kd, fcfg.dropout_prob, free)
    corrupt, free = draw(kc, fcfg.corrupt_prob, free)
    blowup, free = draw(kb, fcfg.blowup_prob, free)
    stale, _ = draw(ks, fcfg.stale_prob, free)
    return FaultPlan(dropped | host_dropped, corrupt, blowup, stale)


def perturb_tree(tree: Any, plan: FaultPlan, fcfg: FaultConfig,
                 stale_tree: Optional[Any] = None) -> Any:
    """Apply one round's faults to a client-stacked payload pytree.

    Non-float leaves pass through untouched (NaN has no integer encoding;
    the survivor mask, not the payload, is what excludes a dropped client's
    integer state). When ``stale_tree`` is None the stale lane is a no-op.
    """
    def f(leaf, stale_leaf):
        if not jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            return leaf
        x = jnp.where(_bc(plan.corrupt, leaf), jnp.nan, leaf)
        x = jnp.where(_bc(plan.blowup, leaf),
                      leaf * jnp.asarray(fcfg.blowup_factor, leaf.dtype), x)
        if stale_leaf is not None:
            x = jnp.where(_bc(plan.stale, leaf),
                          stale_leaf.astype(leaf.dtype), x)
        x = jnp.where(_bc(plan.dropped, leaf),
                      jnp.zeros((), leaf.dtype), x)
        return x

    if stale_tree is None:
        return jax.tree_util.tree_map(lambda l: f(l, None), tree)
    return jax.tree_util.tree_map(f, tree, stale_tree)
