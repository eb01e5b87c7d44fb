"""Self-check of the round accounts and their six readers, on the CPU:

    python -m chipbench.selfcheck_accounts

- the six readers on synthetic records with a known answer: the warm round
  of set-up (it compiled, so the stall reader leaves it out) and a window of
  three rounds as a traced run leaves them (the harness's device wait between
  dispatch and finalize), the last two traced, the last with a 46 ms stall in
  `round/record` during which the thread was off its core;
- the same on records without the `round/wait` span and without counts (a
  program before it had them), on records with the span and no counts, and
  on none: nothing, not zero; `idle_in_wait_ms` reads the trace alone;
- on `testdata/accounts_sample.json`, the round records of one traced run of
  `lfm2_split_phrase_attack` on a TPU v5e (the warm round, then the window):
  every row's extent, wait, `between` and `host_ms` against the same made by
  hand from the records, the parts of each row against its extent, and the
  six readers against what that run printed.
Exits non-zero on the first disagreement.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import List, NamedTuple, Optional

from chipbench import run as harness
from chipbench.selfcheck import check
from chipbench.selfcheck import close as _close

HERE = Path(__file__).resolve().parent
SAMPLE = HERE / "testdata" / "accounts_sample.json"
NAMES = ("round_host_ms", "host_stall_ms_max", "host_offcpu_ms",
         "gc_pause_ms", "record_kib", "idle_in_wait_ms")
MS = 10 ** 6
BETWEEN_MS = 1000.0   # the harness's device wait of a traced run
WAIT_MS = 0.25        # what is left for `round/wait` after it
GAP_MS = 2.0          # one finalize's end to the next dispatch's start
IDLE_IN_WAIT_S = 0.111
WANT = {"round_host_ms": 29.0,       # median of 27, 29, 75
        "host_stall_ms_max": 46.0,   # 75 less the median of 27, 29, 75
        "host_offcpu_ms": 27.0,      # mean of 3.5 and 50.5
        "gc_pause_ms": 6.0,          # mean of 0 and 12
        "record_kib": 42.0,          # mean of 40 and 44
        "idle_in_wait_ms": 1e3 * IDLE_IN_WAIT_S / 2}


def close(a, b, tol=1e-9) -> bool:
    return a is not None and _close(a, b, tol)


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]
    round: Optional[int]
    tid: int = 0
    counts: Optional[dict] = None


def round_records(rnd: int, t0: int, host_ms: float, wait: bool = True,
                  counts: bool = True, compiles: int = 0,
                  offcpu_ms: float = 1.5, runq_ms: float = 0.5,
                  gc_ms: float = 0.0, kib: float = 40.0) -> List[Span]:
    """One round as a traced run leaves it, in order of the spans' ends:
    dispatch 14 ms (plan 3, stage 6, enqueue 4, 1 its own), the harness's
    wait, finalize (wait, fetch 3.5, record the rest, 0.5 its own)."""
    at = lambda ms: t0 + int(ms * MS)
    fin = 14 + BETWEEN_MS
    waited = WAIT_MS if wait else 0.0
    record_to = fin + waited + host_ms - 14 - 0.5
    extent = record_to + 0.5
    on_finalize = {
        "wall_ns": int((GAP_MS + extent) * MS),
        "cpu_ns": int((host_ms - offcpu_ms) * MS), "proc_cpu_ns": 0,
        "nvcsw": 3, "nivcsw": 0, "majflt": 0, "inblock": 0, "oublock": 96,
        "gc_collections": int(gc_ms > 0), "gc_pause_ns": int(gc_ms * MS),
        "gc_gen2": 0, "compiles": compiles, "compile_ns": compiles * MS,
        "runq_wait_ns": int(runq_ms * MS)} if counts else None
    on_record = {"files": 9, "bytes": int(kib * 1024)} if counts else None
    records = [
        Span("round/plan", at(0.5), at(3.5), "round/dispatch", rnd),
        Span("round/stage", at(3.5), at(9.5), "round/dispatch", rnd),
        Span("round/enqueue", at(9.5), at(13.5), "round/dispatch", rnd),
        Span("round/dispatch", at(0), at(14), None, rnd)]
    if wait:
        records.append(Span("round/wait", at(fin + 0.25), at(fin + 0.5),
                            "round/finalize", rnd))
    records += [
        Span("round/fetch", at(fin + 0.25 + waited), at(fin + 3.75 + waited),
             "round/finalize", rnd),
        Span("round/record", at(fin + 3.75 + waited), at(record_to),
             "round/finalize", rnd, 0, on_record),
        Span("round/finalize", at(fin), at(extent), None, rnd, 0,
             on_finalize)]
    return records


def synthetic_records(wait: bool = True, counts: bool = True) -> List[Span]:
    rounds = [dict(host_ms=26.0, compiles=1),            # the warm round
              dict(host_ms=27.0),                        # window round 1
              dict(host_ms=29.0, offcpu_ms=1.5, runq_ms=0.5, kib=40.0),
              dict(host_ms=75.0, offcpu_ms=48.5, runq_ms=1.5, gc_ms=12.0,
                   kib=44.0)]                            # the stall
    records = [Span("setup/data", 0, 5 * MS, None, None)]
    for i, kw in enumerate(rounds):
        records += round_records(3 + i, (1 + i) * 2000 * MS, wait=wait,
                                 counts=counts, **kw)
    return records


def context(records, rounds_in_window: int = 3,
            traced_rounds=(2, 3), idle_in_wait_s: float = IDLE_IN_WAIT_S):
    return {"spans": {"dispatch": [0.014] * rounds_in_window,
                      "finalize": [0.013] * rounds_in_window},
            "counters": {},
            "trace": {"idle_by_span": {"device_wait": idle_in_wait_s,
                                       "finalize": 0.03},
                      "idle_gaps": [["device_wait", idle_in_wait_s]]},
            "traced": {"rounds": len(traced_rounds),
                       "window_rounds": list(traced_rounds)},
            "program_spans": records, "compile_stages": {}, "phases": None}


def readers():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    found = {m["name"]: (m, mod) for m, mod in harness.load_readers(
        bench, bench["workloads"][0]["name"]) if m["name"] in NAMES}
    check(set(found) == set(NAMES), "BENCHMARK.json lists the six readers")
    return found


def by_hand(records: List[Span]) -> dict:
    """A round's extent, wait, `between` and host time from its records'
    own starts and ends, without the reduction under test."""
    of = {r.name: r for r in records}
    lo = min(r.start_ns for r in records)
    hi = max(r.end_ns for r in records)
    between = of["round/finalize"].start_ns - of["round/dispatch"].end_ns
    wait = of["round/wait"].end_ns - of["round/wait"].start_ns
    return {"extent_ms": (hi - lo) / MS, "between_ms": between / MS,
            "wait_ms": wait / MS, "host_ms": (hi - lo - between - wait) / MS}


def main() -> int:
    from dba_mod_tpu.utils import telemetry
    found = readers()
    full = context(synthetic_records())
    bare = context(synthetic_records(wait=False, counts=False))
    uncounted = context(synthetic_records(counts=False))
    empty = context(None)
    for name, (m, mod) in found.items():
        value = mod.read(full)
        check(close(value, WANT[name]), f"reader {name} = {value}")
        check((mod.LAYER, mod.UNIT, mod.MOVES)
              == (m["layer"], m["unit"], m["moves"]),
              f"reader {name} states the layer, unit and moves of "
              "BENCHMARK.json")
        if name == "idle_in_wait_ms":
            check(close(mod.read(bare), WANT[name])
                  and mod.read(dict(bare, trace=None)) is None,
                  f"reader {name} reads the trace alone")
            continue
        check(mod.read(bare) is None and mod.read(empty) is None,
              f"reader {name} returns nothing where nothing is to read")
        if name != "host_stall_ms_max":  # it reads every quiet round
            check(mod.read(context(synthetic_records(), 6)) is None,
                  f"reader {name} returns nothing with fewer rows than rounds")
        check((mod.read(uncounted) is None) == (name != "round_host_ms"),
              f"reader {name}: records with the wait and without counts")

    sample = json.loads(SAMPLE.read_text())
    records = [Span(r["name"], r["start_ns"], r["end_ns"], r["parent"],
                    r["round"], r["tid"], r["counts"])
               for r in sample["records"]]
    rows = telemetry.round_accounts(records=records)
    rounds = sorted({r.round for r in records})
    check([a["round"] for a in rows] == rounds and len(rounds) == 4,
          f"recorded sample: one row for each of the rounds {rounds}")
    for a in rows:
        hand = by_hand([r for r in records if r.round == a["round"]])
        check(all(close(a[k], v, 1e-9) for k, v in hand.items()),
              f"recorded sample, round {a['round']}: the row is {hand}")
        parts = (sum(a["leaves"].values()) + sum(a["self"].values())
                 + a["between_ms"])
        check(abs(parts - a["extent_ms"]) < 1e-3,
              f"recorded sample, round {a['round']}: leaves, self times and "
              "between sum to the extent")
        check(a["counts"]["wall_ns"] / 1e6 >= a["extent_ms"]
              and a["counts"]["cpu_ns"] <= a["counts"]["wall_ns"],
              f"recorded sample, round {a['round']}: the counts' tile holds "
              "the extent")
    ctx = context(records, sample["rounds_in_window"],
                  sample["traced"]["window_rounds"],
                  sample["idle_in_wait_s"])
    for name, (_, mod) in found.items():
        value = mod.read(ctx)
        check(close(value, sample["readings"][name], 1e-6),
              f"recorded sample: reader {name} = {value}")
    print("chipbench.selfcheck_accounts: all passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
