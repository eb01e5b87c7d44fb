"""Process-wide telemetry: layer-boundary spans on the profiler's clock, a
metrics registry, and XLA compile/memory instrumentation for the round path.

- **Spans** — ``with telemetry.span("round/plan", round=epoch):`` is the one
  way the program marks a layer boundary, and it is always on. A span enters
  a ``jax.profiler.TraceAnnotation`` (so under any ``jax.profiler`` trace —
  ``profile_dir``, or a benchmark harness — it lies in the same
  ``.xplane.pb`` as the device operations) and appends one
  :class:`SpanRecord` (name, start and end; the enclosing span; the round; the
  counts the block gave it with ``.count(...)``) to a bounded process-wide
  list, read with :func:`spans`. **Two clocks**: a record's start is
  ``time.time_ns()``, the clock the profiler's ``TraceMe`` reads, so the
  record lies on a device trace's timeline; its length is a
  ``time.perf_counter_ns()`` difference (``end_ns`` is the start plus it), so
  a step of the wall clock can neither make nor hide a stall. A span times
  HOST work:
  it never syncs the device, so the program that is traced is the program
  that is timed. Device time per phase comes from the ``jax.named_scope``
  names inside the round program (``phase/train``, ``phase/aggregate``,
  ``phase/local_battery``, ``phase/global_battery``; fl/rounds.py) under a
  profiler trace. With nothing exporting a span costs three clock reads, one
  list append and an inactive ``TraceMe``.
- **Round accounts** — :func:`round_accounts` reduces the records to one row
  a round: its extent, the milliseconds in each leaf span (``round/wait`` is
  the wait for the device, ``round/fetch`` the transfer alone), each
  parent's self time, the time in no span, ``host_ms`` (the extent less the
  wait and the time in no span) and the host counters
  :class:`RoundBoundary` samples where a round's finalize ends (CPU time,
  run-queue wait, context switches, faults, block I/O, the collector's
  pauses, compiles). :meth:`RoundBoundary.close` logs one ``slow round``
  warning, knob on or off, when a round's row stands out from the rows
  before it (the two rules are the ``SLOW_*`` constants below).
- **Compile stages** — a ``jax.monitoring`` listener, installed once per
  process and counting whether or not the knob is on, sums seconds per stage
  and per jitted function: ``xla/trace_secs``, ``xla/lower_secs``,
  ``xla/compile_secs`` (on a persistent-cache hit this is the load) and
  ``xla/cache_retrieval_secs``; read with :func:`compile_stages`.
- **Exporters** (the ``telemetry`` knob) — :class:`Telemetry` adds the
  metrics registry (counters cumulative, gauges last value, histograms
  windowed between flushes), one JSON line per round in ``telemetry.jsonl``,
  the Chrome-trace ``trace.json`` written from the span list, the
  TensorBoard mirror under ``telemetry/...``, the end-of-run summary table,
  the recompile-after-warmup alarm (:meth:`Telemetry.mark_warm`) and device
  memory gauges (``memory_stats()``; absent on the CPU backend). The knob
  selects exporters only: it never decides whether spans exist nor which
  program a round runs.

The module keeps ONE process-wide current instance (:func:`current`),
defaulting to a no-op null object that holds no state. These files are
additive observability, not part of the reference-parity CSV set (PARITY.md).
"""
from __future__ import annotations

import gc
import json
import logging
import os
import re
import resource
import statistics
import threading
import time
from collections import deque
from pathlib import Path
from typing import Any, Callable, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

logger = logging.getLogger("dba_mod_tpu")

# jax.monitoring event fired on every backend compile — i.e. every jit cache
# miss that actually reaches XLA (tracing-only cache hits don't fire it).
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# persistent-compile-cache misses (only fired when the disk cache is enabled)
PERSISTENT_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# fired inside the backend-compile event on a persistent-cache hit, with no
# fun_name: it takes the name of the lowering that preceded it on the thread
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "xla/trace_secs",
    LOWER_EVENT: "xla/lower_secs",
    BACKEND_COMPILE_EVENT: "xla/compile_secs",
    CACHE_RETRIEVAL_EVENT: "xla/cache_retrieval_secs",
}

_LOCK = threading.Lock()


# ------------------------------------------------------------------- spans
class SpanRecord(NamedTuple):
    """One finished span. ``start_ns`` is ``time.time_ns()``: the clock of the
    profiler's annotations (an xplane's ``profile_start_time`` is its zero);
    ``end_ns`` is the start plus the span's ``time.perf_counter_ns()``
    length."""
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]   # the enclosing span on this thread
    round: Optional[int]    # `round=` of this span, else the enclosing one's
    tid: int
    counts: Optional[Dict[str, int]] = None  # what `_Span.count` was given


MAX_SPAN_RECORDS = 200_000  # about 20 spans a round; later ones are dropped
_records: List[SpanRecord] = []
_dropped = 0
_local = threading.local()  # .stack: open (name, round) pairs; .lowered


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "ids", "counts", "_annotation", "_parent", "_round",
                 "_t0", "_p0")

    def __init__(self, name: str, ids: Dict[str, Any]):
        self.name = name
        self.ids = ids
        self.counts = None

    def count(self, **counts: int) -> None:
        """Exact counts of the work inside this span (``round/plan``: the
        plan's steps), recorded where the work happens: they go onto the
        span's record."""
        self.counts = {**(self.counts or {}), **counts}

    def __enter__(self):
        stack = _stack()
        self._parent, outer_round = stack[-1] if stack else (None, None)
        self._round = self.ids.get("round", outer_round)
        stack.append((self.name, self._round))
        self._annotation = TraceAnnotation(self.name, **self.ids)
        self._annotation.__enter__()
        self._t0 = time.time_ns()
        self._p0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        global _dropped
        end = self._t0 + (time.perf_counter_ns() - self._p0)
        self._annotation.__exit__(*exc)
        _stack().pop()
        record = SpanRecord(self.name, self._t0, end, self._parent,
                            self._round, threading.get_ident(), self.counts)
        kept = len(_records) < MAX_SPAN_RECORDS
        if kept:
            _records.append(record)
        else:
            _dropped += 1
        if _current.enabled:
            _current._on_span(record, kept)
        return False


def span(name: str, **ids):
    """Nestable block that marks a layer boundary of the program; `ids`
    (``round=epoch``) go onto the profiler annotation and the record."""
    return _Span(name, ids)


def spans(since: int = 0) -> List[SpanRecord]:
    """The process's span records from index `since`, in order of their end."""
    return _records[since:]


def phase() -> str:
    """The calling thread's innermost open span, "-" outside any."""
    stack = getattr(_local, "stack", None)
    return stack[-1][0] if stack else "-"


def span_stack() -> List[str]:
    """Names of the calling thread's open spans (thread-local — a caller that
    needs another thread's stack captures it *in* that thread, as the
    watchdog does at zone entry)."""
    return [name for name, _ in getattr(_local, "stack", None) or ()]


def open_round() -> Optional[int]:
    """The round of the calling thread's innermost open span."""
    stack = getattr(_local, "stack", None)
    return stack[-1][1] if stack else None


class _SpanAccess:
    """`t.span(...)`, `t.phase()`, `t.span_stack()` on either telemetry
    object: the module's, whatever instance is current."""
    span = staticmethod(span)
    phase = staticmethod(phase)
    span_stack = staticmethod(span_stack)


# ---------------------------------------------------------- round accounts
ROUND_WAIT = "round/wait"          # the leaf that waits for the device
ROUND_FINALIZE = "round/finalize"  # carries the boundary's host counters
ROUND_RECORD = "round/record"      # carries what the recorder wrote
ROUND_CHECKPOINT = "round/checkpoint"  # ends after its round's finalize
RECORD_COUNTS = ("files", "bytes")
# The slow-round rules (RoundBoundary.close); constants, not parameters.
# Rule "host_ms": over SLOW_HOST_MS and over SLOW_HOST_RATIO times the median
# `host_ms` of the rounds before it that compiled nothing, once there are
# SLOW_HOST_MIN_ROUNDS of them (`host_ms` is steady from the first round, and
# the stalls met so far fell in a window's first rounds). Rule "extent_ms":
# the extent over SLOW_EXTENT_RATIO times the longest extent of any round
# before it, once there are SLOW_EXTENT_MIN_ROUNDS: a poisoned round is half
# again a clean one, so this rule needs a period of the schedule behind it
# and cannot see a late device in a run's first rounds.
SLOW_HOST_MS = 50.0
SLOW_HOST_RATIO = 3.0
SLOW_HOST_MIN_ROUNDS = 2
SLOW_EXTENT_RATIO = 1.25
SLOW_EXTENT_MIN_ROUNDS = 8
SLOW_HISTORY = 64  # rows the running medians are taken over

# process-wide tallies the boundary samples: the collector's (one
# `gc.callbacks` entry) and the compiler's (`_on_event_duration`); both are
# installed by `install_xla_listeners`
_gc_tally = {"gc_collections": 0, "gc_pause_ns": 0, "gc_gen2": 0}
_gc_started_ns = 0
_compile_tally = {"compiles": 0, "compile_ns": 0}
# None once the kernel has refused it: a sample then costs no failed open
_schedstat: Optional[str] = "/proc/thread-self/schedstat"


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_started_ns
    if phase == "start":
        _gc_started_ns = time.perf_counter_ns()
        return
    _gc_tally["gc_collections"] += 1
    _gc_tally["gc_pause_ns"] += time.perf_counter_ns() - _gc_started_ns
    if info.get("generation") == 2:
        _gc_tally["gc_gen2"] += 1


def _sample_host() -> Dict[str, int]:
    """Cumulative host counters of the calling thread and its process.
    `runq_wait_ns` (time runnable but not running: the second field of the
    thread's schedstat) is absent where the kernel does not give the file."""
    usage = resource.getrusage(resource.RUSAGE_SELF)
    sample = {"wall_ns": time.perf_counter_ns(),
              "cpu_ns": time.thread_time_ns(),
              "proc_cpu_ns": time.process_time_ns(),
              "nvcsw": usage.ru_nvcsw, "nivcsw": usage.ru_nivcsw,
              "majflt": usage.ru_majflt, "inblock": usage.ru_inblock,
              "oublock": usage.ru_oublock, **_gc_tally, **_compile_tally}
    global _schedstat
    if _schedstat is not None:
        try:
            with open(_schedstat) as f:
                sample["runq_wait_ns"] = int(f.read().split()[1])
        except (OSError, IndexError, ValueError):
            _schedstat = None
    return sample


def _account(records: List[SpanRecord]) -> Dict[str, Any]:
    """One round's records -> its row. Spans of one thread nest, so in order
    of their starts the enclosing record of each is the nearest one before it
    that bears its `parent`'s name (a length comes from another clock than a
    start, so intervals are not compared): a record that encloses none is a
    leaf, one that does gives its self time (its length less its children's),
    and what no outermost record covers is `between`. Leaves, self times and
    `between` sum to the extent."""
    start = min(r.start_ns for r in records)
    extent = max(r.end_ns for r in records) - start
    ordered = sorted(records, key=lambda r: (r.tid, r.start_ns, -r.end_ns))
    covered = [0] * len(ordered)   # by its children
    parents = set()
    between = extent
    stack: List[int] = []
    for i, r in enumerate(ordered):
        while stack and not (ordered[stack[-1]].tid == r.tid
                             and ordered[stack[-1]].name == r.parent):
            stack.pop()
        if stack:
            covered[stack[-1]] += r.end_ns - r.start_ns
            parents.add(stack[-1])
        else:
            between -= r.end_ns - r.start_ns
        stack.append(i)
    leaves: Dict[str, int] = {}
    selfs: Dict[str, int] = {}
    counts: Dict[str, Any] = {}
    for i, r in enumerate(ordered):
        into = selfs if i in parents else leaves
        into[r.name] = (into.get(r.name, 0)
                        + r.end_ns - r.start_ns - covered[i])
        if r.name == ROUND_FINALIZE and r.counts:
            counts.update(r.counts)
        elif r.name == ROUND_RECORD and r.counts:
            counts.update({k: r.counts[k] for k in RECORD_COUNTS
                           if k in r.counts})
    wait = leaves.get(ROUND_WAIT, 0)
    ms = lambda ns: ns / 1e6
    return {"round": records[0].round, "start_ns": start,
            "extent_ms": ms(extent),
            "leaves": {k: ms(v) for k, v in leaves.items()},
            "self": {k: ms(v) for k, v in selfs.items()},
            "between_ms": ms(between), "wait_ms": ms(wait),
            "host_ms": ms(extent - wait - between), "counts": counts}


def round_accounts(since: int = 0,
                   records: Optional[List[SpanRecord]] = None,
                   ) -> List[Dict[str, Any]]:
    """One row a round, in order of their starts: a pure reduction of the
    span records that carry a round (the process's from index `since`, or
    `records`). A row holds `round`, `start_ns`, `extent_ms` (first start to
    last end of the round's records), `leaves` (milliseconds in each leaf
    span, by name), `self` (each parent's self time), `between_ms` (inside
    the extent, in no span of the round: a harness's own device wait between
    `dispatch_round` and `finalize_round`, the next round's dispatch under
    `pipeline_rounds`; 0 in `run_round`), `wait_ms` (the `round/wait` leaf),
    `host_ms` (the extent less `wait_ms` and `between_ms`) and `counts` (what
    :class:`RoundBoundary` put on `round/finalize`, and the recorder's
    `files` and `bytes` from `round/record`). A round id that comes again
    after its `round/finalize` (a second experiment in the process) opens a
    row of its own; `round/checkpoint` ends after the finalize and stays in
    its round's row. Records the full list turned away have no row: their
    number is `spans_dropped` in the last row's counts."""
    groups: List[List[SpanRecord]] = []
    open_group: Dict[Any, List[SpanRecord]] = {}
    finalized = set()
    for r in (_records[since:] if records is None else records):
        if r.round is None:
            continue
        if r.round in finalized and r.name != ROUND_CHECKPOINT:
            finalized.discard(r.round)
            del open_group[r.round]
        if r.round not in open_group:
            open_group[r.round] = []
            groups.append(open_group[r.round])
        open_group[r.round].append(r)
        if r.name == ROUND_FINALIZE:
            finalized.add(r.round)
    rows = sorted((_account(g) for g in groups), key=lambda a: a["start_ns"])
    if rows and _dropped and records is None:
        rows[-1]["counts"]["spans_dropped"] = _dropped
    return rows


def _medians(rows) -> Dict[str, Any]:
    """The median of every number of the rows, key by key."""
    def med(values):
        values = [v for v in values if v is not None]
        return statistics.median(values) if values else None
    out: Dict[str, Any] = {k: med(r[k] for r in rows)
                           for k in ("extent_ms", "between_ms", "wait_ms",
                                     "host_ms")}
    for group in ("leaves", "self", "counts"):
        keys = {k for r in rows for k in r[group]}
        out[group] = {k: med(r[group].get(k) for r in rows)
                      for k in sorted(keys)}
    return out


class RoundBoundary:
    """What an experiment keeps from one round to the next: the last sample
    of the host counters, where its closed rounds' records end, and the
    bounded history the slow-round rules read."""

    def __init__(self):
        self._last = _sample_host()
        self._mark = len(_records)
        self._quiet: deque = deque(maxlen=SLOW_HISTORY)  # compiled nothing
        self._rounds = 0
        self._longest_ms = 0.0

    def counts(self) -> Dict[str, int]:
        """The host counters' deltas since the previous call (the first:
        since this object was built), for the `round/finalize` span that is
        about to end: the rows tile the process's time, so a stall between
        two rounds is in the second one's counts. `cpu_ns` is the calling
        thread's: the thread that built the experiment runs its rounds."""
        now = _sample_host()
        last, self._last = self._last, now
        return {k: v - last[k] for k, v in now.items() if k in last}

    def close(self, round_id: int) -> Optional[Dict[str, Any]]:
        """The account of the round whose finalize just ended, and the
        slow-round line if one of the two rules holds. One pass over the
        records made since the previous round closed."""
        rows = [a for a in round_accounts(self._mark)
                if a["round"] == round_id]
        self._mark = next(
            (i for i in range(self._mark, len(_records))
             if _records[i].round is not None
             and _records[i].round > round_id), len(_records))
        if not rows:
            return None   # the record list is full
        row = rows[-1]
        hosts = [q["host_ms"] for q in self._quiet]
        rule = None
        if (len(hosts) >= SLOW_HOST_MIN_ROUNDS
                and row["host_ms"] > SLOW_HOST_MS
                and row["host_ms"] > SLOW_HOST_RATIO
                * statistics.median(hosts)):
            rule = "host_ms"
        elif (self._rounds >= SLOW_EXTENT_MIN_ROUNDS
                and row["extent_ms"] > SLOW_EXTENT_RATIO * self._longest_ms):
            rule = "extent_ms"
        if rule is not None:
            usual = _medians(self._quiet)
            # the part furthest over its usual length; a stall of the host
            # is not looked for in the wait for the device
            over = {name: value - (usual[group].get(name) or 0.0)
                    for group in ("leaves", "self")
                    for name, value in row[group].items()
                    if rule == "extent_ms" or name != ROUND_WAIT}
            logger.warning("slow round %s", json.dumps(
                {"round": round_id, "rule": rule,
                 "leaf": max(over, key=over.get), "account": row,
                 "median_of": len(self._quiet), "median": usual}))
        self._rounds += 1
        self._longest_ms = max(self._longest_ms, row["extent_ms"])
        if not row["counts"].get("compiles"):
            self._quiet.append(row)
        return row


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile of an ascending-sorted list (q in [0, 1])."""
    if not sorted_vals:
        return 0.0
    i = min(round(q * (len(sorted_vals) - 1)), len(sorted_vals) - 1)
    return sorted_vals[i]


class Counter:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, n: int = 1) -> None:
        with _LOCK:
            self.value += int(n)


class Gauge:
    __slots__ = ("value",)

    def __init__(self):
        self.value: Optional[float] = None

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Windowed histogram: observations accumulate until the next per-round
    flush snapshots-and-resets the window; exact all-run count/sum ride
    along (the end-of-run p50/p95 span summary draws on the per-span
    durations Telemetry keeps, not on histogram windows)."""

    __slots__ = ("window", "total_count", "total_sum")

    def __init__(self):
        self.window: List[float] = []
        self.total_count = 0
        self.total_sum = 0.0

    def observe(self, v: float) -> None:
        v = float(v)
        with _LOCK:
            self.window.append(v)
            self.total_count += 1
            self.total_sum += v

    def snapshot_and_reset(self) -> Dict[str, float]:
        with _LOCK:
            vals, self.window = self.window, []
        vals.sort()
        return {"count": len(vals), "sum": sum(vals),
                "min": vals[0] if vals else 0.0,
                "max": vals[-1] if vals else 0.0,
                "p50": _percentile(vals, 0.50),
                "p95": _percentile(vals, 0.95)}


class _NullMetric:
    """Shared no-op counter/gauge/histogram for the disabled path."""
    value = 0

    def inc(self, n: int = 1) -> None:
        pass

    def set(self, v: float) -> None:
        pass

    def observe(self, v: float) -> None:
        pass


_NULL_METRIC = _NullMetric()


class _NullTelemetry(_SpanAccess):
    """The disabled telemetry object: it exports nothing and holds no state
    (spans are recorded by the module, not by an instance). `enabled` is the
    one attribute hot paths check. Shared singleton."""
    enabled = False
    current_epoch: Optional[int] = None

    def sync(self, x: Any) -> Any:
        return x

    def counter(self, name: str) -> _NullMetric:
        return _NULL_METRIC

    def gauge(self, name: str) -> _NullMetric:
        return _NULL_METRIC

    def histogram(self, name: str) -> _NullMetric:
        return _NULL_METRIC

    def set_epoch(self, epoch: Optional[int]) -> None:
        pass

    def mark_warm(self) -> None:
        pass

    def record_memory(self) -> None:
        pass

    def flush_round(self, epoch: int,
                    account: Optional[Dict[str, Any]] = None) -> None:
        pass

    def write_trace(self) -> None:
        pass

    def summary_table(self) -> str:
        return "telemetry disabled"

    def close(self) -> None:
        pass


NULL = _NullTelemetry()


class Telemetry(_SpanAccess):
    """One run's exporters and registry. Construct via :func:`configure` so
    call sites throughout the round path resolve it through :func:`current`.
    Its ``trace.json`` and summary table cover the span records made since
    it was built."""

    enabled = True
    TRACE_WRITE_EVERY = 20  # flushes between periodic trace.json rewrites

    def __init__(self, folder: Optional[Path] = None,
                 tb_sink: Optional[Callable[[str, float, int], None]] = None):
        self.folder = Path(folder) if folder is not None else None
        self.tb_sink = tb_sink
        self._origin_ns = time.time_ns()
        self._first_span = len(_records)
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._flush_count = 0
        self._warm = False
        self.current_epoch: Optional[int] = None
        self.peak_memory_bytes = 0
        if self.folder is not None:
            self.folder.mkdir(parents=True, exist_ok=True)
            # truncate a stale jsonl from a previous run in the same folder
            (self.folder / "telemetry.jsonl").write_text("")

    # ------------------------------------------------------------- registry
    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            with _LOCK:
                c = self._counters.setdefault(name, Counter())
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            with _LOCK:
                g = self._gauges.setdefault(name, Gauge())
        return g

    def histogram(self, name: str) -> Histogram:
        h = self._histograms.get(name)
        if h is None:
            with _LOCK:
                h = self._histograms.setdefault(name, Histogram())
        return h

    # ---------------------------------------------------------------- spans
    def own_spans(self) -> List[SpanRecord]:
        return _records[self._first_span:]

    def _on_span(self, record: SpanRecord, kept: bool) -> None:
        """A span ended while this instance is current: feed the per-round
        duration histogram; count a record the full list turned away."""
        if not kept:
            self.counter("trace/dropped_events").inc()
        self.histogram(f"span/{record.name}").observe(
            (record.end_ns - record.start_ns) / 1e9)

    def sync(self, x: Any) -> Any:
        """``jax.block_until_ready`` on `x` — for the standalone programs of
        the split paths (:func:`instrument`), never the fused round."""
        import jax
        return jax.block_until_ready(x)

    def set_epoch(self, epoch: Optional[int]) -> None:
        self.current_epoch = epoch

    # ------------------------------------------------------ instrumentation
    def mark_warm(self) -> None:
        """Declare warmup over: every program a steady-state round needs has
        compiled. Any backend compile after this is a retrace regression —
        counted in ``xla/recompiles_after_warmup`` and logged loudly.
        Idempotent — only the first call flips the flag."""
        if self._warm:
            return
        self._warm = True
        # materialize the counter so post-warmup flushes report an explicit
        # 0 rather than an absent key
        self.counter("xla/recompiles_after_warmup")
        logger.info("telemetry: warmup complete after %d XLA compiles; "
                    "further compiles are counted as recompiles",
                    self.counter("xla/compiles").value)

    def record_memory(self) -> None:
        """Device memory gauges from the backend, when it reports them
        (TPU/GPU do; the CPU backend returns None and this is a no-op)."""
        try:
            import jax
            stats = jax.local_devices()[0].memory_stats()
        except Exception:  # noqa: BLE001 — absent backend support must
            stats = None   # never break a round
        if not stats:
            return
        for key in ("bytes_in_use", "peak_bytes_in_use",
                    "largest_alloc_size", "bytes_limit"):
            if key in stats:
                self.gauge(f"memory/{key}").set(stats[key])
        peak = stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
        self.peak_memory_bytes = max(self.peak_memory_bytes, int(peak))

    # ----------------------------------------------------------- round flush
    def flush_round(self, epoch: int,
                    account: Optional[Dict[str, Any]] = None) -> None:
        """One JSON line per round: cumulative counters, last-value gauges,
        the histogram window since the previous flush (span durations,
        delta norms) and, as `account`, the round's row of
        :func:`round_accounts`. Mirrored to TensorBoard when a sink is
        wired."""
        self.record_memory()
        with _LOCK:
            counters = {k: c.value for k, c in self._counters.items()}
            gauges = {k: g.value for k, g in self._gauges.items()
                      if g.value is not None}
            hist_items = list(self._histograms.items())
        hists = {}
        for k, h in hist_items:
            snap = h.snapshot_and_reset()
            if snap["count"]:
                hists[k] = {m: round(v, 6) for m, v in snap.items()}
        row = {"epoch": int(epoch), "time": time.time(),
               "counters": counters, "gauges": gauges, "histograms": hists}
        if account is not None:
            row["account"] = account
        if self.folder is not None:
            with open(self.folder / "telemetry.jsonl", "a") as f:
                f.write(json.dumps(row) + "\n")
            # trace.json is a full rewrite (the Chrome trace format is one
            # JSON document), so a per-round rewrite would make trace I/O
            # quadratic over a long run — persist on the first flush and
            # every Kth after; close() always writes the complete trace
            self._flush_count += 1
            if self._flush_count % self.TRACE_WRITE_EVERY == 1:
                self.write_trace()
        if self.tb_sink is not None:
            step = int(epoch)
            for k, v in counters.items():
                self.tb_sink(f"telemetry/{k}", float(v), step)
            for k, v in gauges.items():
                self.tb_sink(f"telemetry/{k}", float(v), step)
            for k, snap in hists.items():
                self.tb_sink(f"telemetry/{k}/p50", snap["p50"], step)
                self.tb_sink(f"telemetry/{k}/p95", snap["p95"], step)

    # ----------------------------------------------------------- trace file
    def write_trace(self) -> None:
        """Atomic rewrite of ``trace.json`` (Chrome trace format). Called
        periodically from :meth:`flush_round` and always from :meth:`close`,
        so a crashed run still leaves a loadable (if slightly stale)
        trace."""
        if self.folder is None:
            return
        pid = os.getpid()
        events = [{"name": r.name, "ph": "X", "cat": "span",
                   "ts": (r.start_ns - self._origin_ns) / 1e3,
                   "dur": (r.end_ns - r.start_ns) / 1e3,
                   "pid": pid, "tid": r.tid,
                   "args": {"round": r.round, "parent": r.parent,
                            **(r.counts or {})}}
                  for r in self.own_spans()]
        meta = [{"name": "process_name", "ph": "M", "pid": pid,
                 "args": {"name": "dba_mod_tpu"}}]
        doc = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        path = self.folder / "trace.json"
        tmp = path.with_name(path.name + ".tmp")
        tmp.write_text(json.dumps(doc))
        os.replace(tmp, path)

    # -------------------------------------------------------------- summary
    def summary_table(self) -> str:
        """End-of-run phase summary: p50/p95 per span, the three rounds with
        the largest `host_ms`, recompile count, peak device memory."""
        by_name: Dict[str, List[float]] = {}
        for r in self.own_spans():
            by_name.setdefault(r.name, []).append(
                (r.end_ns - r.start_ns) / 1e9)
        spans = {k: sorted(v) for k, v in by_name.items()}
        lines = [f"{'span':<32} {'count':>6} {'total_s':>9} "
                 f"{'p50_ms':>9} {'p95_ms':>9}"]
        for name in sorted(spans):
            vals = spans[name]
            lines.append(
                f"{name:<32} {len(vals):>6} {sum(vals):>9.3f} "
                f"{_percentile(vals, 0.50) * 1e3:>9.2f} "
                f"{_percentile(vals, 0.95) * 1e3:>9.2f}")
        accounts = round_accounts(self._first_span)
        for a in sorted(accounts, key=lambda a: -a["host_ms"])[:3]:
            named = {**a["self"], **a["leaves"]}
            named.pop(ROUND_WAIT, None)
            top = max(named, key=named.get)
            lines.append(
                f"round {a['round']}: host_ms {a['host_ms']:.2f} of "
                f"{a['extent_ms']:.2f} (wait {a['wait_ms']:.2f}, between "
                f"{a['between_ms']:.2f}); most in {top} {named[top]:.2f}")
        compiles = self.counter("xla/compiles").value
        recompiles = self.counter("xla/recompiles_after_warmup").value
        mem = (f"{self.peak_memory_bytes / 2**20:.1f} MiB"
               if self.peak_memory_bytes else "n/a")
        lines.append(f"xla compiles: {compiles} "
                     f"(after warmup: {recompiles}) | "
                     f"peak device memory: {mem}")
        return "\n".join(lines)

    def close(self) -> None:
        """Final trace/summary flush; safe to call more than once."""
        if self.folder is not None:
            self.write_trace()


# --------------------------------------------------------- process-wide state
_current: Any = NULL
_listeners_installed = False


def current() -> Any:
    """The process-wide telemetry instance (the null object when off)."""
    return _current


def configure(enabled: bool, folder: Optional[Path] = None,
              tb_sink: Optional[Callable[[str, float, int], None]] = None,
              ) -> Any:
    """Install (or clear) the process-wide exporter instance. With `enabled`
    False the null object is installed and no files are touched; spans and
    compile stages are recorded either way.
    One instance per process: a second Experiment in the same process takes
    over the module-level current, so the span histograms and the counters
    of SHARED code paths (checkpoint.py, rounds.py eval wrappers) follow the
    most recent experiment; an Experiment's own registry and per-round flush
    go through its `self.telemetry` handle and are unaffected."""
    global _current
    install_xla_listeners()  # compile stages count with the knob off too
    _current = (Telemetry(folder=folder, tb_sink=tb_sink) if enabled
                else NULL)
    return _current


def sync(x: Any) -> Any:
    if _current.enabled:
        _current.sync(x)
    return x


def count(name: str, n: int = 1) -> None:
    if _current.enabled:
        _current.counter(name).inc(n)


def set_epoch(epoch: Optional[int]) -> None:
    _current.set_epoch(epoch)


def instrument(fn: Callable, name: str, batches: int = 0) -> Callable:
    """Wrap a compiled callable so every call runs under a synced span
    (`jax.block_until_ready` on the result — honest device time under async
    dispatch). Zero-overhead passthrough while telemetry is off; `batches`
    increments the ``eval/batches`` counter per call when set."""
    def wrapped(*args, **kwargs):
        t = _current
        if not t.enabled:
            return fn(*args, **kwargs)
        with t.span(name):
            out = fn(*args, **kwargs)
            t.sync(out)
        if batches:
            t.counter("eval/batches").inc(batches)
        return out
    wrapped.__wrapped__ = fn
    wrapped.__name__ = getattr(fn, "__name__", name)
    return wrapped


# ------------------------------------------------------------- XLA listeners
_compile_stages: Dict[str, Dict[str, float]] = {}
_JIT_WRAPPER = re.compile(r"^p?jit[(_]|\)$")


def compile_stages() -> Dict[str, Dict[str, float]]:
    """Seconds this process spent per jitted function and compile stage:
    ``{"round_fn": {"xla/trace_secs": .., "xla/lower_secs": ..,
    "xla/compile_secs": .., "xla/cache_retrieval_secs": ..}, ...}``. A stage
    a function never reached is absent (no retrieval on a cache miss)."""
    with _LOCK:
        return {fun: dict(stages) for fun, stages in _compile_stages.items()}


def _on_event_duration(event: str, duration: float, **kwargs) -> None:
    stage = COMPILE_STAGE_OF.get(event)
    if stage is None:
        return
    # tracing names the function (`round_fn`), lowering and the backend
    # compile its module (`jit(round_fn)`, `jit_round_fn`)
    fun = str(kwargs.get("fun_name") or getattr(_local, "lowered", "?"))
    fun = _JIT_WRAPPER.sub("", fun)
    if event == LOWER_EVENT:
        _local.lowered = fun
    with _LOCK:
        stages = _compile_stages.setdefault(fun, {})
        stages[stage] = stages.get(stage, 0.0) + float(duration)
        if event == BACKEND_COMPILE_EVENT:  # a cache retrieval lies inside
            _compile_tally["compiles"] += 1
            _compile_tally["compile_ns"] += int(duration * 1e9)
    t = _current
    if not t.enabled:
        return
    t.histogram(stage).observe(duration)
    if event != BACKEND_COMPILE_EVENT:
        return
    t.counter("xla/compiles").inc()
    if t._warm:
        t.counter("xla/recompiles_after_warmup").inc()
        logger.warning(
            "telemetry: XLA backend compile AFTER warmup (%.2fs) — a shape "
            "or constant is retracing in the steady state", duration)


def _on_event(event: str, **kwargs) -> None:
    if _current.enabled and event == PERSISTENT_CACHE_MISS_EVENT:
        _current.counter("xla/persistent_cache_misses").inc()


def install_xla_listeners() -> None:
    """Register the jax.monitoring listeners, and the collector's callback
    the round boundary reads, once per process. The listeners forward to
    whatever instance is current, so they are safe to leave installed when
    telemetry is later disabled."""
    global _listeners_installed
    if _listeners_installed:
        return
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    jax.monitoring.register_event_listener(_on_event)
    gc.callbacks.append(_on_gc)
    _listeners_installed = True


# -------------------------------------------------------------- logging setup
class _PhaseFilter(logging.Filter):
    """Injects epoch/phase context (the current telemetry span) into every
    record so the formatter can show where in the round a line came from."""

    def filter(self, record: logging.LogRecord) -> bool:
        ep = _current.current_epoch
        record.phase = f"e{ep}/{phase()}" if ep is not None else phase()
        return True


_LOG_FORMAT = "%(asctime)s %(levelname).1s [%(phase)s] %(message)s"


def setup_logging(folder: Optional[Path] = None,
                  level: int = logging.INFO) -> logging.Logger:
    """Idempotent configuration of the ``dba_mod_tpu`` logger.

    Replaces the previous per-Experiment ``logging.basicConfig`` + stacked
    ``FileHandler`` (two experiments in one process — e.g. a parity A/B —
    each added a handler and every line went to both files, duplicated).
    The stream handler and formatter are configured exactly once; the
    run-folder file handler is REPLACED when a new folder is configured, so
    log lines follow the active experiment. With no `folder` the logger is
    returned untouched — folder-less runs (bench.py, ``--no-save``) stay as
    quiet as they were before this helper existed."""
    lg = logging.getLogger("dba_mod_tpu")
    if folder is None:
        return lg
    fmt = logging.Formatter(_LOG_FORMAT)
    if not getattr(lg, "_dba_configured", False):
        lg.setLevel(level)
        sh = logging.StreamHandler()
        sh.setFormatter(fmt)
        sh.addFilter(_PhaseFilter())
        lg.addHandler(sh)
        lg.propagate = False
        lg._dba_configured = True  # type: ignore[attr-defined]
    path = os.path.abspath(str(Path(folder) / "log.txt"))
    existing = [h for h in lg.handlers
                if getattr(h, "_dba_run_file", False)]
    if any(getattr(h, "baseFilename", None) == path for h in existing):
        return lg
    for h in existing:
        lg.removeHandler(h)
        h.close()
    fh = logging.FileHandler(path)
    fh.setFormatter(fmt)
    fh.addFilter(_PhaseFilter())
    fh._dba_run_file = True  # type: ignore[attr-defined]
    lg.addHandler(fh)
    return lg
