"""What the readers of a round's account share: the program's own reduction of
its span records to one row a round, read in-process, and (for
`idle_in_wait_ms` alone) the run's own `.xplane.pb`.

What this file calls or names in the program (`chipbench/program.py`,
`chipbench/phases.py` and `chipbench/steps.py` list the rest); a PR that
renames one keeps the readers running:

- `dba_mod_tpu.utils.telemetry.round_accounts(since=0, records=None)` -> one
  dict a round, in order of their starts, with `round`, `start_ns`,
  `extent_ms`, `leaves` ({span name: ms}; the leaf `round/wait` is the wait for
  the device), `self` ({parent span: ms}), `between_ms`, `wait_ms`, `host_ms`
  (the extent less the wait and the time in no span) and `counts`;
- in `counts`, from the `round/finalize` span (deltas since the previous
  round's finalize ended): `wall_ns`, `cpu_ns` (the round thread's),
  `compiles`, `gc_pause_ns`; from the `round/record` span: `bytes` (what `Recorder.save`
  wrote). The program puts more there (`proc_cpu_ns`, `runq_wait_ns`, `nvcsw`,
  `nivcsw`, `majflt`, `inblock`, `oublock`, `gc_collections`, `gc_gen2`,
  `compile_ns`, `files`): printed in the finding line, read by no metric. **On
  the chip tool's machine** (looked at, PR 39) `/proc/thread-self/schedstat`
  does not exist, so `runq_wait_ns` is absent and has no reader;
  `getrusage` gives zeros for the switches, faults and blocks; and the
  thread's CPU clock ticks at 10 ms, which is `host_offcpu_ms`'s grain;
- `dba_mod_tpu.utils.telemetry.RoundBoundary` (`--boundary-cost` only).

A program without `round_accounts` (the parent of the PR that added this
file), and records without the span or the count a reader needs, give `None`
for that number, never 0 and never an exception.

Which rounds: a traced run's process sends through `dispatch_round` and
`finalize_round` the warm round of set-up and window rounds 1 to 3 (the check
rounds call the round program directly and leave no span). "The window's
rounds" are the last n rows, n being the rounds the harness clocked; "the
traced rounds" are those of them the trace holds (`traced.window_rounds`). In
a traced run the harness waits for the device between `dispatch_round` and
`finalize_round`: that wait is `between_ms`, `wait_ms` is near 0, and `host_ms`
is what `dispatch_ms` + `finalize_ms` clock from outside.

`idle_in_wait_ms` reads `ctx["trace"]` (`chipbench/trace.py`'s reduction) and
prints beside it, as a finding line read by no metric, what the host's
threads did in each gap of device 0 over 1 ms that lies inside a
`chipbench/device_wait` annotation. In the trace (looked at by hand on a TPU
v5e, a traced run of `lfm2_split_phrase_attack`, python tracer off): every
plane that is not `/device:TPU:<n>` is the host's, here the one plane
`/host:CPU`, with one line a thread, named `<thread name>/<tid>`:

- `python`: the `TraceMe`s made from Python on the round thread: the program's
  spans, the harness's `chipbench/*` annotations, `np.asarray(jax.Array)`,
  `ArrayImpl.copy_to_host_async`, `PjitFunction(<name>)`, `shard_args`,
  `PythonRefManager::CollectGarbage`;
- `main/<tid>`: the runtime's own calls on that same thread: `Wait for
  donation holds`, `Wait for usage holds`, `DeferredTpuAllocator::Allocate`,
  `AllocateRawBuffer`, `CommonPjRtBuffer::ToLiteral`, `MemoryDeallocation`;
- `tfrt-non-blocking-queue/<tid>`: the enqueue of a program:
  `DoEnqueueProgram`, `EnqueueContinuationProgram`,
  `tpu::System::Execute=>IssueSequencedEvent`;
- `futex-default-SDomainT/<tid>`: completions: `tpu::System::Execute=>Done`,
  `Release semaphore`, `ReadSyncFlag`, `CompleteCallbacks`;
- `pjrt-tpu-tasks/<tid>` (a pool of about eight): transfers and layout:
  `D2H Dispatch`, `H2D Dispatch`, `tpu::System::TransferFromDevice`,
  `XlaLinearize`, `XlaDelinearize`, `Transpose::Execute`;
- `EventFDAsyncWorker/<tid>`: transfers' completions,
  `tpu::System::TransferFromDevice=>IssueEvent=>Done` and the same of
  `TransferToDevice`.

A late runtime shows as a gap of device 0 that ends with
`tfrt-non-blocking-queue`'s `DoEnqueueProgram` or begins with
`futex-default-SDomainT`'s `Execute=>Done`; a late host as a gap under the
`python` line's own spans.

`python -m chipbench.accounts <dir-or-file>` prints the host planes' lines
and the gaps of a trace; `--boundary-cost` times 10^5 rounds' worth of the
boundary's sampling and reduction.
"""
from __future__ import annotations

import functools
import json
import statistics
import sys
from typing import Callable, Dict, List, Optional

from chipbench import phases, trace

WAIT_LEAF = "round/wait"
HARNESS_SPAN = "dispatch"
WAIT_ANNOTATION = "device_wait"
GAP_NS = 1e6          # a gap worth looking into
EVENTS_A_GAP = 24     # the longest of them, in the finding line


# ------------------------------------------------------- in-process: the host
@functools.lru_cache(maxsize=1)
def _run_rows() -> Optional[List[dict]]:
    """This process's rows, reduced once for all readers (they run after the
    window); the JSON line goes out before the result line."""
    try:
        from dba_mod_tpu.utils import telemetry
        rows = telemetry.round_accounts()
    except (ImportError, AttributeError):
        return None
    print(json.dumps({"phase": "round_accounts", "rows": rows}), flush=True)
    return rows


def round_accounts(ctx=None) -> Optional[List[dict]]:
    """The program's rows (a self-check hands its own records in `ctx`)."""
    if not (ctx and "program_spans" in ctx):
        return _run_rows()
    try:
        from dba_mod_tpu.utils import telemetry
        return telemetry.round_accounts(
            records=list(ctx["program_spans"] or ()))
    except (ImportError, AttributeError):
        return None


def window_rows(ctx) -> Optional[List[dict]]:
    n = len(ctx["spans"].get(HARNESS_SPAN) or ())
    rows = round_accounts(ctx) or ()
    return list(rows[-n:]) if n and len(rows) >= n else None


def traced_rows(ctx) -> Optional[List[dict]]:
    rows, traced = window_rows(ctx), ctx.get("traced")
    if not rows or not traced or not traced.get("window_rounds"):
        return None
    picked = [r - 1 for r in traced["window_rounds"]]
    return [rows[i] for i in picked] if max(picked) < len(rows) else None


def host_ms(row: dict) -> Optional[float]:
    """`host_ms` of a row that has the wait apart (without the leaf the wait
    hides in `round/fetch` and the number is another)."""
    return row["host_ms"] if WAIT_LEAF in row["leaves"] else None


def offcpu_ms(row: dict) -> Optional[float]:
    """Host time in which the round thread was not running: the tile's wall
    time (previous finalize's end to this one's) outside the device wait and
    `between`, less the thread's CPU time over the same tile."""
    c = row["counts"]
    if "cpu_ns" not in c or "wall_ns" not in c or host_ms(row) is None:
        return None
    return ((c["wall_ns"] - c["cpu_ns"]) / 1e6 - row["wait_ms"]
            - row["between_ms"])


def count(key: str, per: float) -> Callable[[dict], Optional[float]]:
    """The count `key` of a row in units of `per`; nothing where a program
    did not count it."""
    return lambda row: (row["counts"][key] / per if key in row["counts"]
                        else None)


def over(rows, value: Callable[[dict], Optional[float]],
         how=statistics.mean) -> Optional[float]:
    """`how` over `value` of each row; nothing where there is no row, or a
    row lacks what `value` reads."""
    values = [value(r) for r in rows or ()]
    if not values or any(v is None for v in values):
        return None
    return how(values)


def host_stall_ms(ctx) -> Optional[float]:
    """Over every round of the process that compiled nothing: the largest
    `host_ms` less their median."""
    quiet = [host_ms(r) for r in round_accounts(ctx) or ()
             if r["counts"].get("compiles") == 0]
    if len(quiet) < 2 or any(v is None for v in quiet):
        return None
    return max(quiet) - statistics.median(quiet)


# ------------------------------------------------------- the trace: the device
def idle_in_wait_ms(ctx) -> Optional[float]:
    t, traced = ctx.get("trace"), ctx.get("traced")
    if not t or not traced or not traced.get("rounds"):
        return None
    if "program_spans" not in ctx:  # a run of the harness, not a self-check
        _print_gaps(t)
    return 1e3 * t["idle_by_span"].get(WAIT_ANNOTATION, 0.0) / traced["rounds"]


def host_lines(path) -> List[dict]:
    """[{plane, line, events: [(name, start ns, end ns)]}] of the host's
    planes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(trace.find_xplane(path)))
    out = []
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            out.append({"plane": plane.name, "line": line.name, "events": [
                (e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                for e in line.events]})
    return out


def gaps_in_wait(devices: Dict[str, list], annotations: list) -> list:
    """[(start ns, end ns)] of device 0's idle gaps over `GAP_NS` whose
    midpoint a `device_wait` annotation covers (the arithmetic of
    `trace.reduce_events`, which keeps their lengths alone)."""
    names = sorted(devices, key=lambda n: int(
        n[len(trace.DEVICE_PREFIX):].split()[0]))
    if not names or not annotations:
        return []
    lo, hi = annotations[0][1], max(a[2] for a in annotations)
    busy = trace.union(trace.clip([(a, b) for _, a, b in devices[names[0]]],
                                  lo, hi))
    waits = [(s, e) for n, s, e in annotations if n == WAIT_ANNOTATION]
    return [(a, b) for a, b in trace.gaps(busy, lo, hi)
            if b - a > GAP_NS
            and any(s <= (a + b) / 2 <= e for s, e in waits)]


def events_in_gaps(gaps: list, lines: List[dict]) -> list:
    """For each gap, the host events of every thread that overlap it, the
    longest overlaps first."""
    out = []
    for a, b in gaps:
        found = []
        for l in lines:
            for name, s, e in l["events"]:
                if name.startswith(trace.ANNOTATION_PREFIX):
                    continue
                over = min(e, b) - max(s, a)
                if over > 0:
                    found.append({"plane": l["plane"], "line": l["line"],
                                  "event": name[:100],
                                  "from_gap_start_ms": (s - a) / 1e6,
                                  "ms": (e - s) / 1e6,
                                  "overlap_ms": over / 1e6})
        found.sort(key=lambda f: -f["overlap_ms"])
        out.append({"gap_ms": (b - a) / 1e6, "events_overlapping": len(found),
                    "events": found[:EVENTS_A_GAP]})
    return out


def _print_gaps(reduced: dict) -> None:
    """The finding line; the trace is read again only where the reduction
    the harness made holds a gap worth it."""
    if not any(owner == WAIT_ANNOTATION and s * 1e9 > GAP_NS
               for owner, s in reduced.get("idle_gaps") or ()):
        return
    path = phases.find_run_xplane()
    if path is None:
        return
    planes = trace.read_planes(path)
    gaps = gaps_in_wait(planes["devices"], planes["annotations"])
    print(json.dumps({"phase": "idle_in_wait",
                      "gaps": events_in_gaps(gaps, host_lines(path))}),
          flush=True)


# ------------------------------------------------------------------ by hand
def describe(path) -> None:
    lines = host_lines(path)
    for l in lines:
        names: Dict[str, int] = {}
        for name, _, _ in l["events"]:
            names[name[:60]] = names.get(name[:60], 0) + 1
        top = sorted(names.items(), key=lambda kv: -kv[1])[:8]
        print(json.dumps({"plane": l["plane"], "line": l["line"],
                          "events": len(l["events"]), "most": top}))
    planes = trace.read_planes(path)
    gaps = gaps_in_wait(planes["devices"], planes["annotations"])
    print(json.dumps({"gaps": events_in_gaps(gaps, lines)}, indent=1))


def boundary_cost(n: int = 100_000) -> None:
    """us a round of what the program adds at a round's boundary, timed
    alone: the counters' sample, and the reduction of a round's eleven
    records with the slow-round check (less the eleven spans themselves)."""
    import logging
    import time
    from dba_mod_tpu.utils import telemetry
    names = ("round/plan", "round/stage", "round/enqueue")
    # rounds of microseconds trip the extent rule; its check is timed, its
    # line is not wanted
    logging.getLogger("dba_mod_tpu").setLevel(logging.ERROR)

    def one_round(rnd, boundary):
        with telemetry.span("round/dispatch", round=rnd):
            for name in names:
                with telemetry.span(name, round=rnd):
                    pass
        with telemetry.span("round/finalize", round=rnd) as fin:
            for name in ("round/wait", "round/fetch", "round/record"):
                with telemetry.span(name, round=rnd):
                    pass
            if boundary is not None:
                fin.count(**boundary.counts())
        if boundary is not None:
            boundary.close(rnd)

    boundary = telemetry.RoundBoundary()
    t0 = time.perf_counter()
    for _ in range(n):
        boundary.counts()
    sample_us = (time.perf_counter() - t0) / n * 1e6
    rounds = min(n, telemetry.MAX_SPAN_RECORDS // 20)  # 9 records a round
    t0 = time.perf_counter()
    for rnd in range(rounds):
        one_round(rnd, None)
    bare_us = (time.perf_counter() - t0) / rounds * 1e6
    boundary = telemetry.RoundBoundary()
    t0 = time.perf_counter()
    for rnd in range(rounds, 2 * rounds):
        one_round(rnd, boundary)
    whole_us = (time.perf_counter() - t0) / rounds * 1e6
    print(json.dumps({"phase": "boundary_cost", "samples": n,
                      "sample_us": sample_us, "rounds": rounds,
                      "nine_spans_us": bare_us,
                      "boundary_us_a_round": whole_us - bare_us}))


if __name__ == "__main__":
    if sys.argv[1] == "--boundary-cost":
        boundary_cost()
    else:
        describe(sys.argv[1])
