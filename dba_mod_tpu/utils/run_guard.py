"""Process-level crash/preemption tolerance: graceful shutdown + watchdog.

PR 1 made individual rounds survive bad *clients*; this module makes the
*process* killable. Preemptible TPUs deliver SIGTERM with a short grace
window, operators deliver SIGINT, and a wedged runtime delivers nothing at
all — three failure shapes, two tools:

- :class:`GracefulShutdown` — SIGTERM/SIGINT set a stop flag that the
  experiment loop checks at round boundaries; the run writes a final
  verified checkpoint, flushes the recorder and telemetry, and the CLI
  exits with :data:`EXIT_INTERRUPTED` so wrappers can distinguish
  "preempted, resume me" from success and from crashes. A second signal
  forces immediate exit (``128 + signum``) for operators who mean it.
- :class:`Watchdog` — a monotonic-deadline timer around the round path's
  host-blocking sync points (``jax.device_get`` at finalize, the robust
  screen sync, the async-checkpoint wait). A stall past ``watchdog_soft_s``
  logs a loud diagnostic (zone label, epoch, elapsed, the telemetry span
  stack captured at zone entry, the round's account so far); past
  ``watchdog_hard_s`` the process is aborted with :data:`EXIT_WATCHDOG` — a
  wedged run dies *checkpointed*
  (the previous round's verified checkpoint is on disk) instead of burning
  quota silently.

Both are strict no-ops when disabled (the config defaults): no signal
handlers installed, no threads started, zero per-round work beyond one
attribute check. :class:`RunGuard` bundles them behind the config knobs
(``graceful_shutdown``, ``watchdog_soft_s``, ``watchdog_hard_s``).
"""
from __future__ import annotations

import contextlib
import json
import logging
import os
import signal
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from dba_mod_tpu.utils import telemetry

logger = logging.getLogger("dba_mod_tpu")

# Distinct exit codes so run wrappers (k8s, slurm, the crash/elastic smoke
# harnesses) can tell the exit shapes apart without parsing logs. 75/76/77
# follow the sysexits.h convention of "temporary failure — retrying is the
# fix"; 77 additionally tells the wrapper the retry must SHRINK the world.
EXIT_INTERRUPTED = 75   # graceful stop after SIGTERM/SIGINT; resume-able
EXIT_WATCHDOG = 76      # watchdog hard abort: a sync point stalled past
                        # watchdog_hard_s; the last committed checkpoint
                        # is the resume point
EXIT_PEER_LOST = 77     # a peer host is gone (stall coincides with missed
                        # heartbeats, or the round-boundary check found a
                        # stale peer): relaunch the SURVIVORS with
                        # JAX_NUM_PROCESSES shrunk and --resume auto
                        # (README "Elastic multi-host")

_NULL_CM = contextlib.nullcontext()


def _flush_checkpoints_bounded(timeout_s: float = 10.0) -> None:
    """Best-effort landing of in-flight async checkpoint commits before an
    abort exit. Bounded: the abort path must never trade a wedged round
    for a wedged flush (an async commit whose collective peer died would
    block forever), so the wait runs on a side thread and is abandoned at
    the deadline — the previous round's manifest-verified snapshot is
    already on disk either way (checkpoint.py flushes async manifests
    every round)."""
    done = threading.Event()

    def _wait():
        try:
            from dba_mod_tpu import checkpoint as ckpt  # lazy: no cycle
            ckpt.wait_for_async_saves()
        except Exception:  # noqa: BLE001 — aborting anyway
            pass
        finally:
            done.set()

    threading.Thread(target=_wait, daemon=True,
                     name="dba-abort-flush").start()
    if not done.wait(timeout_s):
        logger.warning("abort: async checkpoint flush did not finish in "
                       "%.0fs — exiting on the previous verified snapshot",
                       timeout_s)


class GracefulShutdown:
    """SIGTERM/SIGINT → stop flag; second signal → immediate exit.

    Handlers are installed only via :meth:`install` (RunGuard's
    ``__enter__``), only when enabled, and only from the main thread
    (Python restricts ``signal.signal`` to it); :meth:`uninstall` restores
    whatever was there before, so nested/sequential experiments in one
    process (parity A/Bs) don't fight over handlers."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self, enabled: bool = False):
        self.enabled = bool(enabled)
        self._stop = threading.Event()
        self._prev: Dict[int, Any] = {}
        self._signal_count = 0
        # injectable for tests — the real thing must be os._exit: a second
        # signal means "now", and raising inside a signal handler would
        # unwind into whatever JAX host callback happens to be on the stack
        self._force_exit: Callable[[int], None] = os._exit

    @property
    def stop_requested(self) -> bool:
        return self._stop.is_set()

    def request_stop(self) -> None:
        """Programmatic stop (tests; also lets hooks trigger the same
        round-boundary drain a signal would)."""
        self._stop.set()

    def install(self) -> None:
        # fresh run, fresh state: without this, a second run() on the same
        # Experiment would exit immediately on the stale stop flag, and —
        # worse — its FIRST signal would take the force-exit branch and
        # skip the final checkpoint/flush the graceful path promises
        self._stop.clear()
        self._signal_count = 0
        if not self.enabled or self._prev:
            return
        if threading.current_thread() is not threading.main_thread():
            logger.warning("graceful_shutdown: not on the main thread — "
                           "signal handlers not installed")
            return
        for sig in self.SIGNALS:
            self._prev[sig] = signal.signal(sig, self._handler)

    def uninstall(self) -> None:
        for sig, prev in self._prev.items():
            signal.signal(sig, prev)
        self._prev.clear()

    def _handler(self, signum, frame) -> None:
        self._signal_count += 1
        if self._signal_count >= 2:
            # the operator insists: no checkpoint, no flush, out now
            self._force_exit(128 + int(signum))
            return
        self._stop.set()
        # NO telemetry.count here: counters take telemetry's non-reentrant
        # module lock, and a handler runs on the main thread — a signal
        # landing while that thread holds the lock (any counter/histogram
        # update) would self-deadlock the process. The honored stop is
        # counted at the round boundary (run/interrupted).
        try:
            name = signal.Signals(signum).name
        except ValueError:  # pragma: no cover — unknown signum
            name = str(signum)
        logger.warning(
            "received %s — finishing the current round, then writing a "
            "final checkpoint and exiting with code %d; signal again to "
            "force immediate exit", name, EXIT_INTERRUPTED)


def _account_so_far(round_id: Optional[int]) -> str:
    """The stalled round's row of `telemetry.round_accounts` from the spans
    it has finished (its dispatch's leaves; the open ones are the stack at
    entry), as JSON; "-" where it has finished none."""
    recent = max(0, len(telemetry.spans()) - 256)
    rows = [a for a in telemetry.round_accounts(recent)
            if a["round"] == round_id]
    return json.dumps(rows[-1]) if rows else "-"


class _Zone:
    __slots__ = ("label", "t0", "soft_at", "hard_at", "soft_fired",
                 "epoch", "spans", "round")

    def __init__(self, label: str, t0: float, soft_at: float, hard_at: float,
                 epoch: Optional[int], spans: List[str],
                 round_id: Optional[int]):
        self.label = label
        self.t0 = t0
        self.soft_at = soft_at
        self.hard_at = hard_at
        self.soft_fired = False
        self.epoch = epoch
        self.spans = spans
        self.round = round_id  # of the innermost open span: knob on or off


class Watchdog:
    """Monotonic-deadline stall detector for host-blocking sync points.

    ``with watchdog.zone("round/finalize"):`` arms a deadline; leaving the
    block disarms it. One daemon thread (started lazily on the first armed
    zone, never when disabled) watches the active zone: at
    ``soft_s`` it logs a stall diagnostic once — the zone label, current
    epoch, elapsed seconds, the stalled round's account from the spans it
    has finished (`telemetry.round_accounts`) and the telemetry span stack
    captured at zone entry (captured *in the arming thread*; the span stack is
    thread-local, and the arming thread is the one that is about to be
    wedged inside the zone) — at ``hard_s`` it aborts the process via
    `on_hard` (default: flush logging, ``os._exit(EXIT_WATCHDOG)``).
    Deadlines use ``time.monotonic()`` so wall-clock adjustments can
    neither fire nor suppress the timer."""

    def __init__(self, soft_s: float = 0.0, hard_s: float = 0.0,
                 on_hard: Optional[Callable[[], None]] = None):
        self.soft_s = float(soft_s)
        self.hard_s = float(hard_s)
        self.enabled = self.soft_s > 0 or self.hard_s > 0
        self._on_hard = on_hard or self._default_abort
        self._cv = threading.Condition()
        self._zone: Optional[_Zone] = None
        self._thread: Optional[threading.Thread] = None
        self.soft_stalls = 0
        self.hard_aborts = 0
        # elastic verdict hook (parallel/distributed.py::PeerHealth
        # .lost_peers): when set, a hard stall that coincides with missed
        # peer heartbeats is classified as "peer gone" and the abort exits
        # EXIT_PEER_LOST instead of EXIT_WATCHDOG — the supervisor then
        # relaunches shrunk rather than same-size
        self.peer_probe: Optional[Callable[[], List[int]]] = None
        # the verdict the hard-abort path logged — _default_abort reuses
        # it so the logged code, the run/peer_lost counter, and the real
        # exit code can never disagree (a peer crossing the staleness
        # threshold between two probes would otherwise split them)
        self._verdict: Optional["tuple[int, List[int]]"] = None

    @contextlib.contextmanager
    def zone(self, label: str):
        if not self.enabled:
            yield
            return
        self._ensure_thread()
        t = telemetry.current()
        t0 = time.monotonic()
        z = _Zone(label, t0,
                  t0 + self.soft_s if self.soft_s > 0 else float("inf"),
                  t0 + self.hard_s if self.hard_s > 0 else float("inf"),
                  t.current_epoch, t.span_stack(), telemetry.open_round())
        with self._cv:
            self._zone = z
            self._cv.notify()
        try:
            yield
        finally:
            with self._cv:
                self._zone = None
                self._cv.notify()

    def _ensure_thread(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="dba-watchdog")
        self._thread.start()

    def _loop(self) -> None:
        while True:
            with self._cv:
                z = self._zone
                if z is None:
                    self._cv.wait()
                    continue
                now = time.monotonic()
                nxt = min(z.hard_at,
                          z.soft_at if not z.soft_fired else float("inf"))
                if now < nxt:
                    # cap the wait so a re-armed zone is noticed promptly
                    self._cv.wait(min(nxt - now, 1.0))
                    continue
            # a deadline passed. Re-verify the zone is still armed right
            # before acting — the sync point may have completed in the gap
            # since the deadline was read, and a recovered process must
            # not be aborted (nor a misleading stall logged).
            elapsed = now - z.t0
            if not z.soft_fired and now >= z.soft_at:
                with self._cv:
                    armed = self._zone is z
                if not armed:
                    continue
                z.soft_fired = True
                self.soft_stalls += 1
                telemetry.count("watchdog/soft_stalls")
                logger.error(
                    "watchdog: %s has stalled for %.1fs (soft limit %.1fs) "
                    "— epoch=%s open spans at entry=%s, the round's account "
                    "so far=%s; hard abort %s",
                    z.label, elapsed, self.soft_s, z.epoch,
                    z.spans or ["-"], _account_so_far(z.round),
                    (f"at {self.hard_s:.1f}s" if self.hard_s > 0
                     else "disabled"))
            if now >= z.hard_at:
                # hold the lock across the abort: a zone exit racing this
                # blocks on the cv until the process dies, so a sync point
                # that completed just before the deadline check can never
                # be killed after the fact
                with self._cv:
                    if self._zone is not z:
                        continue
                    self.hard_aborts += 1
                    telemetry.count("watchdog/hard_aborts")
                    code, lost = self._verdict = self.abort_verdict()
                    if lost:
                        telemetry.count("run/peer_lost")
                    logger.critical(
                        "watchdog: %s stalled past the hard limit (%.1fs > "
                        "%.1fs) — epoch=%s span stack at entry=%s; %s"
                        "aborting with exit code %d (the last committed "
                        "checkpoint is the resume point)", z.label, elapsed,
                        self.hard_s, z.epoch, z.spans or ["-"],
                        (f"stall coincides with missed heartbeats from "
                         f"peer(s) {lost} — peer lost, relaunch the "
                         f"survivors shrunk; " if lost else ""),
                        code)
                    self._on_hard()
                    # an injected on_hard (tests) returns — drop the zone
                    # so the abort doesn't re-fire every poll
                    self._zone = None

    def abort_verdict(self) -> "tuple[int, List[int]]":
        """Classify the hard stall: (exit code, lost peer ids). A stall
        with missed peer heartbeats is a peer loss (EXIT_PEER_LOST) — the
        survivor is wedged in a collective whose peer vanished, and only a
        shrunk relaunch can make progress; anything else is the generic
        wedged-runtime abort (EXIT_WATCHDOG). A probe failure never masks
        the abort itself."""
        lost: List[int] = []
        if self.peer_probe is not None:
            try:
                lost = list(self.peer_probe())
            except Exception:  # noqa: BLE001 — the verdict is best-effort
                lost = []
        if lost:
            return EXIT_PEER_LOST, lost
        return EXIT_WATCHDOG, lost

    def _default_abort(self) -> None:  # pragma: no cover — kills the process
        # reuse the verdict _loop just logged/counted; probe fresh only if
        # an injected caller reached here without one
        code, _ = self._verdict or self.abort_verdict()
        _flush_checkpoints_bounded()
        logging.shutdown()
        os._exit(code)


class RunGuard:
    """The experiment-facing bundle: one stop flag + one watchdog, built
    from config. ``with guard:`` installs/uninstalls the signal handlers
    around the run loop; both members are inert when their knobs are off
    (the acceptance contract: no threads, no handlers, no per-round cost
    beyond an attribute check)."""

    def __init__(self, graceful_shutdown: bool = False,
                 watchdog_soft_s: float = 0.0, watchdog_hard_s: float = 0.0):
        self.shutdown = GracefulShutdown(enabled=graceful_shutdown)
        self.watchdog = Watchdog(soft_s=watchdog_soft_s,
                                 hard_s=watchdog_hard_s)

    @classmethod
    def from_params(cls, params) -> "RunGuard":
        return cls(
            graceful_shutdown=bool(params.get("graceful_shutdown", False)),
            watchdog_soft_s=float(params.get("watchdog_soft_s", 0.0)),
            watchdog_hard_s=float(params.get("watchdog_hard_s", 0.0)))

    @property
    def stop_requested(self) -> bool:
        return self.shutdown.stop_requested

    def attach_peer_health(self, peers) -> None:
        """Wire the elastic peer-health layer into the watchdog verdict:
        a hard stall that coincides with missed heartbeats exits
        EXIT_PEER_LOST (77) instead of EXIT_WATCHDOG (76). `peers` is a
        PeerHealth (parallel/distributed.py) or None to detach."""
        self.watchdog.peer_probe = (peers.lost_peers
                                    if peers is not None else None)

    def watch(self, label: str):
        """Watchdog zone around a host-blocking sync point; the shared
        null context when the watchdog is off."""
        if not self.watchdog.enabled:
            return _NULL_CM
        return self.watchdog.zone(label)

    def __enter__(self) -> "RunGuard":
        self.shutdown.install()
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown.uninstall()
