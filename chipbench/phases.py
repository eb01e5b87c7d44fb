"""What the per-layer readers added with the program's own spans and scope
names share: the program's span records and compile stages, read in-process,
and the run's own `.xplane.pb`, reduced with `jax.profiler.ProfileData`.

What this file calls or names in the program (`chipbench/program.py` lists
the rest); a PR that renames one keeps the readers running:

- `dba_mod_tpu.utils.telemetry.spans()` -> records with `.name`,
  `.start_ns`, `.end_ns`, `.parent`, `.round`;
  `dba_mod_tpu.utils.telemetry.compile_stages()` -> `{function: {stage:
  seconds}}` with the stages `xla/trace_secs`, `xla/lower_secs`,
  `xla/compile_secs`, `xla/cache_retrieval_secs`
- the span names `setup/data`, `round/plan`, `round/stage`, `round/enqueue`,
  `round/fetch`, `round/record`, `round/checkpoint` (each also a
  `jax.profiler.TraceAnnotation` of that name in a profiler trace)
- the `jax.named_scope` names `phase/train`, `phase/aggregate`,
  `phase/local_battery`, `phase/global_battery` inside the round program, the
  kernel name `fused_sgd_update` (a chunk index follows it), and `round_fn`,
  the name of the jitted round program

A program without them (the parent of the PR that added this file) gives
`None` for every number here, never 0 and never an exception.

A reader's `ctx` carries no path: the harness writes the traced run's profile
under `chipbench/_out/<cell>.<seed>.1/trace/` and removes it only after the
readers ran, so `find_run_xplane` takes the newest `.xplane.pb` there.

In the trace (looked at by hand on a TPU v5e): device planes are
`/device:TPU:<n>`; the operations of device 0 are the events of its `XLA Ops`
line, named by their whole HLO instruction (a Pallas kernel by the name its
`pallas_call` was given: `%vmap_fused_sgd_update_0_.3 = ... custom-call`).
There is no `Framework Name Scope` line, and an event's own stats hold times
only: the scope path (`jit(round_fn)/phase/train/while/body/...`) is the
`tf_op` stat of the operation's *event metadata*, beside `flops` and
`bytes_accessed`. `jax.profiler.ProfileData` does not hand out event
metadata, so `op_scopes` reads that one table from the file's protobuf wire
format; events and times come from `ProfileData` as in `trace.py`. The
program's spans are host-plane events.

- device time under a scope: the union of the intervals, clipped to the
  traced span, of the device-0 operations whose scope path contains it. A
  `while` carries its own scope, so the union counts it once and not again
  by the operations of its body. An operation the compiler left without any
  scope path (on the chip: three of the battery's `while` loops, and
  `reverse` operations it put into their bodies) counts under the scope whose
  operations cover more than half of its interval;
- `unattributed_s`: device-0 busy time under none of the four scopes, and
  `unattributed_ops`: the operations that make most of it;
- the traced span is `chipbench/trace.py`'s: first to last `chipbench/*`
  annotation;
- an idle gap of device 0 is attributed to the leaf program span that covers
  its midpoint, else to `no_span`;
- `by_round` (in the printed line, read by no metric): the same reduction
  over each traced round's own three annotations, so that a clean and a
  poisoned round of one trace can be told apart.

`python -m chipbench.phases <dir-or-file>` prints the reduction and what the
trace holds; `--record-sample <dir>` records a small trace on the device that
is there (how `testdata/phases_sample.xplane.pb` was made); `--span-cost`
times 10^5 empty spans.
"""
from __future__ import annotations

import bisect
import functools
import json
import statistics
import sys
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, Optional

from chipbench import trace

HERE = Path(__file__).resolve().parent
SCOPES = ("phase/train", "phase/aggregate", "phase/local_battery",
          "phase/global_battery")
KERNEL = "fused_sgd_update"
ROUND_PROGRAM = "round_fn"
LEAF_SPANS = ("round/plan", "round/stage", "round/enqueue", "round/fetch",
              "round/record", "round/checkpoint")
SCOPE_STAT = "tf_op"


# ------------------------------------------------------- in-process: the host
def program_spans(ctx=None) -> Optional[list]:
    """The program's span records (a self-check hands its own in `ctx`)."""
    if ctx and "program_spans" in ctx:
        return ctx["program_spans"]
    try:
        from dba_mod_tpu.utils import telemetry
        return list(telemetry.spans())
    except (ImportError, AttributeError):
        return None


def compile_stages(ctx=None,
                   function: str = ROUND_PROGRAM) -> Optional[Dict[str, float]]:
    if ctx and "compile_stages" in ctx:
        return ctx["compile_stages"].get(function)
    try:
        from dba_mod_tpu.utils import telemetry
        return telemetry.compile_stages().get(function)
    except (ImportError, AttributeError):
        return None


def span_seconds(ctx, name: str) -> Optional[float]:
    """Sum over the process of the spans of that name (set-up spans: one)."""
    spans = program_spans(ctx)
    found = [r for r in spans or () if r.name == name]
    return sum(r.end_ns - r.start_ns for r in found) / 1e9 if found else None


def window_span_ms(ctx, name: str, harness_span: str) -> Optional[float]:
    """Median of the program's span `name` over the window's rounds: the last
    n records of it, n being the rounds the harness clocked (`harness_span`)."""
    n = len(ctx["spans"].get(harness_span) or ())
    found = [r for r in program_spans(ctx) or () if r.name == name]
    if not n or len(found) < n:
        return None
    return statistics.median((r.end_ns - r.start_ns) / 1e6 for r in found[-n:])


# ------------------------------------------------------- the trace: the device
def find_run_xplane(root: Optional[Path] = None) -> Optional[Path]:
    found = list((root or HERE / "_out").glob("*/trace/**/*.xplane.pb"))
    return max(found, key=lambda p: p.stat().st_mtime) if found else None


def _varint(buf, i: int):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited and fixed-width fields."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {wire}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def op_scopes(path, plane_name: str) -> Dict[str, str]:
    """{operation: scope path} of one plane, from the `tf_op` stat of its
    event metadata. XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4
    and .stat_metadata = 5 (maps: key = 1, value = 2); X*Metadata.name = 2,
    XEventMetadata.stats = 5; XStat.metadata_id = 1, .str_value = 5,
    .ref_value = 7 (a stat_metadata id whose name is the string)."""
    text = lambda view: bytes(view).decode("utf-8", "replace")
    data = memoryview(trace.find_xplane(path).read_bytes())
    for field, plane in _fields(data):
        entries = list(_fields(plane)) if field == 1 else ()
        if not any(f == 2 and text(v) == plane_name for f, v in entries):
            continue
        stat_names = {}
        for f, entry in entries:
            if f == 5:
                kv = dict(_fields(entry))
                stat_names[kv[1]] = text(dict(_fields(kv[2])).get(2, b""))
        scopes = {}
        for f, entry in entries:
            if f != 4:
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            name = next((text(v) for k, v in meta if k == 2), "")
            for k, stat in meta:
                stat = dict(_fields(stat)) if k == 5 else {}
                if stat_names.get(stat.get(1)) == SCOPE_STAT:
                    scopes[name] = (text(stat[5]) if 5 in stat
                                    else stat_names.get(stat.get(7), ""))
        return scopes
    return {}


def read_trace(path) -> Dict[str, Any]:
    """{ops: device 0's [(name, scope path, start ns, end ns)], harness:
    [(name, start, end)] of the `chipbench/*` annotations, spans: [(name,
    start, end)] of the program's leaf spans}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(trace.find_xplane(path)))
    ordinal = lambda p: int(p.name[len(trace.DEVICE_PREFIX):].split()[0])
    devices = sorted((p for p in data.planes
                      if p.name.startswith(trace.DEVICE_PREFIX)), key=ordinal)
    ops, harness, spans = [], [], []
    if devices:
        scopes = op_scopes(path, devices[0].name)
        lines = list(devices[0].lines)
        for line in [l for l in lines if l.name == trace.OPS_LINE] or [
                l for l in lines if l.name not in trace.SUMMARY_LINES]:
            for e in line.events:
                ops.append((e.name, scopes.get(e.name, ""), float(e.start_ns),
                            float(e.start_ns + e.duration_ns)))
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                at = (float(e.start_ns), float(e.start_ns + e.duration_ns))
                if e.name.startswith(trace.ANNOTATION_PREFIX):
                    harness.append((e.name, *at))
                elif e.name in LEAF_SPANS:
                    spans.append((e.name, *at))
    return {"ops": ops, "harness": sorted(harness, key=lambda a: a[1]),
            "spans": sorted(spans, key=lambda a: a[1])}


class _Cover:
    """Sorted, disjoint intervals with prefix sums: how much of [a, b] they
    cover, by bisection (a trace holds millions of them)."""

    def __init__(self, intervals):
        self.starts = [a for a, _ in intervals]
        self.ends = [b for _, b in intervals]
        self.sums = [0.0]
        for a, b in intervals:
            self.sums.append(self.sums[-1] + (b - a))
        self.total = self.sums[-1]

    def inside(self, a: float, b: float) -> float:
        i = bisect.bisect_right(self.ends, a)    # first that ends after a
        j = bisect.bisect_left(self.starts, b)   # first that starts at b or on
        if i >= j:
            return 0.0
        return (self.sums[j] - self.sums[i] - max(0.0, a - self.starts[i])
                - max(0.0, self.ends[j - 1] - b))


def reduce_events(ops: list, harness: list, spans: list) -> Dict[str, Any]:
    """The arithmetic, apart from the file format (the self-check and the
    tests drive it on synthetic events)."""
    if not ops:
        raise ValueError("the trace holds no operation of device 0")
    if harness:
        lo, hi = harness[0][1], max(a[2] for a in harness)
    else:
        lo, hi = min(o[2] for o in ops), max(o[3] for o in ops)
    seconds = lambda intervals: sum(b - a for a, b in intervals) / 1e9
    ops = [(name, scope, max(a, lo), min(b, hi)) for name, scope, a, b in ops
           if min(b, hi) > max(a, lo)]
    busy = trace.union([(a, b) for _, _, a, b in ops])
    named = {s: [(a, b) for _, scope, a, b in ops if s in scope]
             for s in SCOPES}
    unnamed = [op for op in ops if not any(s in op[1] for s in SCOPES)]
    # an operation the compiler left without a name (a `while` it rebuilt
    # around named operations) counts under the scope that covers most of it
    cover = {s: _Cover(trace.union(i)) for s, i in named.items()}
    for _, _, a, b in unnamed:
        most = max(SCOPES, key=lambda s: cover[s].inside(a, b))
        if cover[most].inside(a, b) > (b - a) / 2:
            named[most].append((a, b))
    by_scope = {s: trace.union(i) for s, i in named.items()}
    scope_s = {s: seconds(i) for s, i in by_scope.items() if i}
    scoped = _Cover(trace.union([i for s in SCOPES for i in by_scope[s]]))
    kernel = (trace.union([(a, b) for name, _, a, b in ops if KERNEL in name])
              or trace.union([(a, b) for _, scope, a, b in ops
                              if KERNEL in scope]))
    loose: Dict[str, float] = defaultdict(float)  # busy under no scope, by op
    for name, scope, a, b in unnamed:
        free = (b - a) - scoped.inside(a, b)
        if free > 0:
            loose[f"{trace.short_name(name)} | {scope[-80:]}"] += free / 1e9
    idle: Dict[str, float] = defaultdict(float)
    for a, b in trace.gaps(busy, lo, hi):
        mid = (a + b) / 2
        owner = next((n for n, s, e in spans if s <= mid <= e), "no_span")
        idle[owner] += (b - a) / 1e9
    idle_s = sum(idle.values())
    return {"window_s": (hi - lo) / 1e9, "busy_s": seconds(busy),
            "scope_s": scope_s,
            "unattributed_s": seconds(busy) - scoped.total / 1e9,
            "unattributed_ops": sorted(
                ([k, v] for k, v in loose.items()), key=lambda kv: -kv[1])[:8],
            "kernel_s": seconds(kernel) if kernel else None,
            "scopes_in_trace": bool(scope_s),
            "idle_by_program_span": dict(idle),
            "idle_attributed_pct": (100.0 * (idle_s - idle.get("no_span", 0.0))
                                    / idle_s if idle_s and spans else None)}


def reduce_by_round(t: Dict[str, Any]) -> list:
    """One reduction for each traced round (the harness opens a round with
    `chipbench/dispatch` and waits for the device before it closes it, so a
    round's operations lie inside its three annotations): what sets a clean
    round apart from a poisoned one in the same trace."""
    starts = [i for i, a in enumerate(t["harness"])
              if a[0] == trace.ANNOTATION_PREFIX + "dispatch"]
    keep = ("window_s", "busy_s", "scope_s", "kernel_s", "unattributed_s",
            "idle_by_program_span")
    out = []
    for i, j in zip(starts, starts[1:] + [len(t["harness"])]):
        r = reduce_events(t["ops"], t["harness"][i:j], t["spans"])
        out.append({k: r[k] for k in keep})
    return out


@functools.lru_cache(maxsize=1)
def _reduce_run() -> Optional[Dict[str, Any]]:
    path = find_run_xplane()
    if path is None or program_spans() is None:
        return None
    t = read_trace(path)
    if not t["ops"]:
        return None
    reduced = reduce_events(t["ops"], t["harness"], t["spans"])
    print(json.dumps({"phase": "program_spans", **reduced,
                      "by_round": reduce_by_round(t),
                      "round_program_compile": compile_stages()}), flush=True)
    return reduced


def run_phases(ctx=None) -> Optional[Dict[str, Any]]:
    """The reduction of this run's own trace, made once for all readers; its
    JSON line goes out before the result line."""
    if ctx and "phases" in ctx:
        return ctx["phases"]
    return _reduce_run()


def scope_device_ms(ctx, scope: str) -> Optional[float]:
    reduced, traced = run_phases(ctx), ctx.get("traced")
    if not reduced or not traced or not traced.get("rounds"):
        return None
    s = reduced["scope_s"].get(scope)
    return None if s is None else 1e3 * s / traced["rounds"]


# ------------------------------------------------------------------ by hand
def describe(path) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(trace.find_xplane(path)))
    for plane in data.planes:
        print("PLANE", plane.name, dict(plane.stats))
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for e in events[:4]:
                print("    ", e.name[:100], e.start_ns, e.duration_ns,
                      {k: str(v)[:120] for k, v in dict(e.stats).items()})


def record_sample(out_dir: str) -> None:
    """Three small rounds of a program with a named scope around a loop and
    the program's own named fused update, under the program's spans and the
    harness's annotations."""
    import jax
    import jax.numpy as jnp
    from dba_mod_tpu.ops.fused_update import make_fused_step_update
    from dba_mod_tpu.utils import telemetry

    fused = make_fused_step_update(0.9, 5e-4, False, use_pallas=True,
                                   interpret=jax.default_backend() != "tpu")

    @jax.jit
    def round_fn(w, x):
        with jax.named_scope("phase/train"):
            def step(_, w):
                g = {"k": jnp.tanh(x @ w["k"]), "b": w["b"] * 0.5}
                lr = jnp.full((w["b"].shape[0],), 0.1)
                valid = jnp.ones((w["b"].shape[0],), bool)
                new, _, _, _ = jax.vmap(fused)(lr, valid, w, g, g, {}, {}, {})
                return new
            w = jax.lax.fori_loop(0, 4, step, w)
        with jax.named_scope("phase/global_battery"):
            return w, jnp.sum(w["k"] @ x)

    w = {"k": jnp.ones((8, 256, 256), jnp.float32) * 0.01,
         "b": jnp.ones((8, 256), jnp.float32)}
    x = jnp.ones((256, 256), jnp.float32) * 0.01
    jax.block_until_ready(round_fn(w, x))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # annotations only, as the harness traces
    jax.profiler.start_trace(out_dir, profiler_options=options)
    for epoch in range(1, 4):
        with jax.profiler.TraceAnnotation("chipbench/dispatch"):
            with telemetry.span("round/plan", round=epoch):
                x = x * 1.0
            with telemetry.span("round/enqueue", round=epoch):
                w, out = round_fn(w, x)
        with jax.profiler.TraceAnnotation("chipbench/device_wait"):
            jax.block_until_ready((w, out))
        with jax.profiler.TraceAnnotation("chipbench/finalize"):
            with telemetry.span("round/fetch", round=epoch):
                float(out)
    jax.profiler.stop_trace()
    print(trace.find_xplane(out_dir))


def span_cost(n: int = 100_000) -> None:
    """ns per empty span with nothing exporting and no profiler session."""
    import time
    from dba_mod_tpu.utils import telemetry
    t0 = time.perf_counter()
    for _ in range(n):
        with telemetry.span("cost/empty"):
            pass
    print(json.dumps({"phase": "span_cost", "spans": n,
                      "ns_per_span": (time.perf_counter() - t0) / n * 1e9}))


if __name__ == "__main__":
    if sys.argv[1] == "--record-sample":
        record_sample(sys.argv[2])
    elif sys.argv[1] == "--span-cost":
        span_cost()
    else:
        describe(sys.argv[1])
        t = read_trace(sys.argv[1])
        print(json.dumps(reduce_events(t["ops"], t["harness"], t["spans"]),
                         indent=1))
