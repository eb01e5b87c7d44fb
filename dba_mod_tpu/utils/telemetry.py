"""Process-wide telemetry: layer-boundary spans on the profiler's clock, a
metrics registry, and XLA compile/memory instrumentation for the round path.

- **Spans** — ``with telemetry.span("round/plan", round=epoch):`` is the one
  way the program marks a layer boundary, and it is always on. A span enters
  a ``jax.profiler.TraceAnnotation`` (so under any ``jax.profiler`` trace —
  ``profile_dir``, or a benchmark harness — it lies in the same
  ``.xplane.pb`` as the device operations) and appends one
  :class:`SpanRecord` (name, start/end in ``time.time_ns()``, which is the
  clock the profiler's ``TraceMe`` reads; the enclosing span; the round; the
  counts the block gave it with ``.count(...)``) to a bounded process-wide
  list, read with :func:`spans`. A span times HOST work:
  it never syncs the device, so the program that is traced is the program
  that is timed. Device time per phase comes from the ``jax.named_scope``
  names inside the round program (``phase/train``, ``phase/aggregate``,
  ``phase/local_battery``, ``phase/global_battery``; fl/rounds.py) under a
  profiler trace. With nothing exporting a span costs two clock reads, one
  list append and an inactive ``TraceMe``.
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

import json
import logging
import os
import re
import threading
import time
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
    """One finished span. Times are ``time.time_ns()``: the clock of the
    profiler's annotations (an xplane's ``profile_start_time`` is its zero)."""
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
                 "_t0")

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
        return self

    def __exit__(self, *exc):
        global _dropped
        end = time.time_ns()
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


def spans_dropped() -> int:
    return _dropped


def phase() -> str:
    """The calling thread's innermost open span, "-" outside any."""
    stack = getattr(_local, "stack", None)
    return stack[-1][0] if stack else "-"


def span_stack() -> List[str]:
    """Names of the calling thread's open spans (thread-local — a caller that
    needs another thread's stack captures it *in* that thread, as the
    watchdog does at zone entry)."""
    return [name for name, _ in getattr(_local, "stack", None) or ()]


class _SpanAccess:
    """`t.span(...)`, `t.phase()`, `t.span_stack()` on either telemetry
    object: the module's, whatever instance is current."""
    span = staticmethod(span)
    phase = staticmethod(phase)
    span_stack = staticmethod(span_stack)


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

    def flush_round(self, epoch: int) -> None:
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
    def flush_round(self, epoch: int) -> None:
        """One JSON line per round: cumulative counters, last-value gauges,
        and the histogram window since the previous flush (span durations,
        delta norms). Mirrored to TensorBoard when a sink is wired."""
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
        """End-of-run phase summary: p50/p95 per span, recompile count, peak
        device memory."""
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


def observe(name: str, v: float) -> None:
    if _current.enabled:
        _current.histogram(name).observe(v)


def set_gauge(name: str, v: float) -> None:
    if _current.enabled:
        _current.gauge(name).set(v)


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
    """Register the jax.monitoring listeners once per process. The listeners
    forward to whatever instance is current, so they are safe to leave
    installed when telemetry is later disabled."""
    global _listeners_installed
    if _listeners_installed:
        return
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    jax.monitoring.register_event_listener(_on_event)
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
