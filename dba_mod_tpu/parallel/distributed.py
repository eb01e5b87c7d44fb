"""Multi-host (DCN) initialization + the elastic peer-health layer.

One FL round is a single SPMD program, so pod-scale runs need only
`jax.distributed` process bootstrap: every host runs the same driver, the
mesh spans all hosts' devices, per-host input shards are placed with
`jax.make_array_from_process_local_data`, and XLA routes the aggregation
collectives over ICI within a slice and DCN across slices. This is the
TPU-native replacement for the NCCL/MPI backend slot the reference leaves
empty (SURVEY §2.2 communication row).

Elasticity (:class:`PeerHealth`): a JAX collective cannot survive a peer
vanishing mid-program — a lost host leaves the survivors wedged inside the
next collective, indistinguishable from a slow peer. Elastic rounds
therefore mean **detect → classify → restart shrunk**, never in-flight
recovery:

- every process writes a per-host heartbeat file into a shared directory
  (``heartbeat_dir``; local disk for single-machine multi-process runs, the
  shared checkpoint filesystem for real pods) every
  ``heartbeat_interval_s``;
- at round boundaries the driver beats with the round epoch and runs a
  non-blocking staleness check (optionally a bounded-timeout barrier), so
  "peer is gone" is distinguished from "peer is slow" *outside* any
  collective;
- when a stall does happen inside a collective, the PR-4 watchdog consults
  :meth:`PeerHealth.lost_peers` at its hard deadline and exits with the
  distinct ``EXIT_PEER_LOST`` (77) verdict instead of the generic stall
  abort — the supervisor (scripts/elastic_smoke.sh is the reference
  recipe) relaunches the survivors with ``JAX_NUM_PROCESSES`` shrunk and
  ``--resume auto``, and the mesh/padding layers rebuild over the
  surviving devices.

Heartbeats carry a membership *generation* (default: the world size, so a
shrink-restart never confuses the old world's files with the new one's;
override with ``DBA_ELASTIC_GEN`` for equal-size replacement restarts, or
have the supervisor clean ``heartbeat_dir``). Files from a different
generation are ignored. Everything here is a strict no-op unless
``heartbeat_interval_s > 0`` in a multi-process run: no thread, no files.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax

logger = logging.getLogger("dba_mod_tpu")

_initialized = False


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> bool:
    """Initialize jax.distributed when running multi-host.

    Explicit args win; otherwise standard env vars
    (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID) or cloud
    auto-detection. Returns True when a multi-process runtime was set up.
    No-op (False) for the common single-host case.
    """
    global _initialized
    coordinator_address = (coordinator_address or
                           os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if coordinator_address is None and num_processes is None:
        return False
    if _initialized:  # idempotent: every Experiment calls this
        return jax.process_count() > 1
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=(num_processes if num_processes is not None else
                       int(os.environ.get("JAX_NUM_PROCESSES", "0")) or None),
        process_id=(process_id if process_id is not None else
                    int(os.environ.get("JAX_PROCESS_ID", "-1"))
                    if "JAX_PROCESS_ID" in os.environ else None))
    _initialized = True
    logger.info("jax.distributed initialized: process %d/%d, %d local / %d "
                "global devices", jax.process_index(), jax.process_count(),
                jax.local_device_count(), jax.device_count())
    return jax.process_count() > 1


class PeerLostError(RuntimeError):
    """A peer host is gone (heartbeat stale past the timeout), not slow.

    Raised at round boundaries (and synthesized from collective failures by
    Experiment.run's classification pass). The CLI maps it to
    ``run_guard.EXIT_PEER_LOST`` (77) so a supervisor can relaunch the
    survivors shrunk instead of reporting a crash."""

    def __init__(self, lost: List[int], detail: str = ""):
        self.lost = sorted(lost)
        msg = (f"peer host(s) {self.lost} lost — heartbeat stale past the "
               f"timeout")
        if detail:
            msg += f" ({detail})"
        super().__init__(msg)


class PeerHealth:
    """File-based peer liveness for one multi-process run.

    One instance per process. :meth:`start` writes the first heartbeat and
    launches a daemon beat thread; :meth:`beat` (also called at round
    boundaries with the boundary epoch) rewrites this host's file
    atomically; :meth:`lost_peers` reads every peer's file and returns the
    ids whose heartbeat is stale past ``timeout_s`` — the classification
    primitive the round boundary, the failure classifier, and the watchdog
    verdict all share. :meth:`barrier` is the bounded-timeout
    round-boundary barrier: it waits (never past ``timeout``) for every
    peer to reach a boundary epoch, raising :class:`PeerLostError` the
    moment any peer's heartbeat goes stale — a slow peer times the barrier
    out (returns False, the caller proceeds into the collective and the
    watchdog takes over), a dead one is reported before the program can
    wedge."""

    def __init__(self, folder: str | Path, process_id: int, world_size: int,
                 interval_s: float, timeout_s: float = 0.0,
                 gen: Optional[int] = None):
        self.folder = Path(folder)
        self.process_id = int(process_id)
        self.world_size = int(world_size)
        self.interval_s = float(interval_s)
        self.timeout_s = float(timeout_s) if timeout_s > 0 else (
            6.0 * self.interval_s)
        # membership generation: the world size unless the supervisor says
        # otherwise — a 2→1 shrink restart must not read the dead world's
        # heartbeat files as current-generation peers
        env_gen = os.environ.get("DBA_ELASTIC_GEN")
        self.gen = int(gen if gen is not None else
                       env_gen if env_gen is not None else self.world_size)
        self.boundary_epoch = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._started_wall: Optional[float] = None
        self._known_lost: set = set()
        self._lock = threading.Lock()

    # ------------------------------------------------------------- lifecycle
    def start(self) -> None:
        self.folder.mkdir(parents=True, exist_ok=True)
        self._started_wall = time.time()
        self._stop.clear()
        self.beat()
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="dba-heartbeat")
            self._thread.start()
        logger.info("peer health: process %d/%d gen=%d beating every %.2fs "
                    "into %s (timeout %.2fs)", self.process_id,
                    self.world_size, self.gen, self.interval_s, self.folder,
                    self.timeout_s)

    def stop(self) -> None:
        """Clean shutdown: final beat marked ``stopped`` so peers draining
        at a different instant don't read the quiescing file as a loss."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=max(1.0, self.interval_s))
        if self._started_wall is not None:
            try:
                self.beat(stopped=True)
            except OSError:  # pragma: no cover — fs went away at teardown
                pass

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.beat()
            except OSError as exc:  # pragma: no cover — transient fs error
                logger.warning("peer health: heartbeat write failed (%r)",
                               exc)

    # ------------------------------------------------------------------ beat
    def _path(self, pid: int) -> Path:
        return self.folder / f"host_{pid}.json"

    def beat(self, boundary_epoch: Optional[int] = None,
             stopped: bool = False) -> None:
        # the whole write-then-rename stays under the lock: the daemon
        # beat thread and the main thread's boundary beat share one tmp
        # path, and an unlocked interleaving could rename a torn tmp into
        # place — which a peer would read as "unparsable = missing" and,
        # past the grace window, spuriously classify as a lost host
        with self._lock:
            if boundary_epoch is not None:
                self.boundary_epoch = int(boundary_epoch)
            payload = {"pid": self.process_id, "gen": self.gen,
                       "time": time.time(),
                       "boundary_epoch": self.boundary_epoch,
                       "ospid": os.getpid(), "stopped": bool(stopped)}
            path = self._path(self.process_id)
            tmp = path.with_suffix(f".tmp{self.process_id}")
            tmp.write_text(json.dumps(payload))
            tmp.replace(path)  # atomic: peers never read a torn heartbeat

    def _read(self, pid: int) -> Optional[Dict]:
        try:
            d = json.loads(self._path(pid).read_text())
        except (OSError, ValueError):
            return None
        return d if d.get("gen") == self.gen else None

    # ------------------------------------------------------------ liveness
    @property
    def peer_ids(self) -> List[int]:
        return [p for p in range(self.world_size) if p != self.process_id]

    def lost_peers(self, now: Optional[float] = None) -> List[int]:
        """Peer ids whose heartbeat is stale past ``timeout_s``.

        A peer with no current-generation file yet is only lost once the
        startup grace window (3× timeout from :meth:`start`) has passed —
        jax.distributed.initialize barriers all processes at startup, so a
        live peer writes its first beat within milliseconds of ours. A
        peer whose final beat is marked ``stopped`` exited cleanly and is
        never reported."""
        if self._started_wall is None:
            return []
        now = time.time() if now is None else now
        in_grace = (now - self._started_wall) < 3.0 * self.timeout_s
        lost = []
        for pid in self.peer_ids:
            d = self._read(pid)
            if d is None:
                if not in_grace:
                    lost.append(pid)
                continue
            if d.get("stopped"):
                continue
            if now - float(d["time"]) > self.timeout_s:
                lost.append(pid)
        new = set(lost) - self._known_lost
        if new:
            self._known_lost |= new
            from dba_mod_tpu.utils import telemetry
            telemetry.count("peer/heartbeat_missed", len(new))
            logger.error(
                "peer health: heartbeat from peer(s) %s stale past %.2fs — "
                "peer lost (slow peers keep beating; a silent one is gone)",
                sorted(new), self.timeout_s)
        return lost

    def check(self, epoch: int) -> None:
        """Non-blocking round-boundary check: beat with the boundary epoch,
        then raise :class:`PeerLostError` if any peer's heartbeat is
        stale — the cheap per-round detection path (one file write + one
        directory read)."""
        self.beat(boundary_epoch=epoch)
        lost = self.lost_peers()
        if lost:
            raise PeerLostError(lost, detail=f"epoch {epoch} boundary")

    def barrier(self, epoch: int, timeout: float) -> bool:
        """Bounded-timeout boundary barrier: True when every peer reported
        a boundary epoch >= ``epoch`` within ``timeout`` seconds, False on
        timeout (peer slow — proceed, the watchdog owns in-collective
        stalls). Raises :class:`PeerLostError` if a peer dies while we
        wait."""
        self.beat(boundary_epoch=epoch)
        deadline = time.monotonic() + float(timeout)
        poll = max(min(self.interval_s / 2.0, 0.25), 0.02)
        while True:
            lost = self.lost_peers()
            if lost:
                raise PeerLostError(lost, detail=f"epoch {epoch} barrier")
            behind = []
            for pid in self.peer_ids:
                d = self._read(pid)
                if d is None or int(d.get("boundary_epoch", 0)) < epoch:
                    behind.append(pid)
            if not behind:
                return True
            if time.monotonic() >= deadline:
                logger.warning(
                    "peer health: barrier for epoch %d timed out after "
                    "%.2fs waiting on peer(s) %s — peers are slow, not "
                    "gone; proceeding", epoch, timeout, behind)
                return False
            time.sleep(poll)
