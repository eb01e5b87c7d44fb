"""Self-check of the two readers of the plan's step counts, on the CPU:

    python -m chipbench.selfcheck_steps

- both readers on a synthetic context with a known answer: four rounds
  recorded, three in the window, one of them with no real step; the shares
  are sums of parts over sums of wholes (a median of per-round shares would
  read 10 and 61 there, not 20 and 35);
- both on `testdata/steps_sample.json`: the `round/plan` records of one run
  of `tiny_dba_attack` on a TPU v5e (set-up's two check rounds and warm round,
  then the window), against counts made by hand from the same records;
- both on an empty context, and on records that carry no counts (the program
  before it counted): nothing, not zero.
Exits non-zero on the first disagreement.
"""
from __future__ import annotations

import json
import sys
from collections import namedtuple
from pathlib import Path

from chipbench import run as harness
from chipbench import steps
from chipbench.selfcheck import check, close

HERE = Path(__file__).resolve().parent
SAMPLE = HERE / "testdata" / "steps_sample.json"
NAMES = ("train_steps_run_pct", "train_lane_fill_pct")
Span = namedtuple("Span", "name start_ns end_ns parent round counts")
BareSpan = namedtuple("BareSpan", "name start_ns end_ns parent round")


def context(records, rounds_in_window: int) -> dict:
    return {"spans": {"dispatch": [0.01] * rounds_in_window}, "counters": {},
            "trace": None, "traced": None, "program_spans": records,
            "compile_stages": {}, "phases": None}


def synthetic_records():
    count = lambda plan, run, real, lanes: {
        "steps_plan": plan, "steps_run": run, "lane_steps_real": real,
        "lanes": lanes}
    plans = [count(370, 1, 10, 10),      # a check round of set-up: not read
             count(370, 37, 370, 10),    # 10 % of the plan, every lane full
             count(370, 185, 407, 10),   # 50 %, 22 % full: one lane's tail
             count(370, 0, 0, 10)]       # no real step: it adds to the plan only
    records = []
    for rnd, c in enumerate(plans, 1):
        records += [Span("round/plan", rnd * 100, rnd * 100 + 7, None, rnd, c),
                    Span("round/stage", rnd * 100 + 8, rnd * 100 + 9, None,
                         rnd, None)]
    return records


def readers():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    found = {m["name"]: (m, mod) for m, mod in harness.load_readers(
        bench, bench["workloads"][0]["name"]) if m["name"] in NAMES}
    check(set(found) == set(NAMES), "BENCHMARK.json lists both readers")
    return found


def main() -> int:
    found = readers()
    ctx = context(synthetic_records(), 3)
    want = {"train_steps_run_pct": 100 * (37 + 185 + 0) / (3 * 370),   # 20
            "train_lane_fill_pct": 100 * (370 + 407) / (370 + 1850)}   # 35
    empty = context(None, 0)
    bare = context([BareSpan(*r[:5]) for r in synthetic_records()], 3)
    for name, (m, mod) in found.items():
        value = mod.read(ctx)
        check(close(value, want[name]), f"reader {name} = {value}")
        check((mod.LAYER, mod.UNIT, mod.MOVES)
              == (m["layer"], m["unit"], m["moves"]),
              f"reader {name} states the layer, unit and moves of "
              "BENCHMARK.json")
        check(mod.read(empty) is None and mod.read(context([], 3)) is None,
              f"reader {name} returns nothing where nothing is to read")
        check(mod.read(bare) is None,
              f"reader {name} returns nothing from records without counts")
        check(mod.read(context(synthetic_records(), 5)) is None,
              f"reader {name} returns nothing with fewer plans than rounds")

    sample = json.loads(SAMPLE.read_text())
    records = [Span(r["name"], r["start_ns"], r["end_ns"], None, r["round"],
                    r["counts"]) for r in sample["records"]]
    n = sample["rounds_in_window"]
    window = [r.counts for r in records if r.name == "round/plan"][-n:]
    check(len(window) == n and all(
        0 < c["steps_run"] <= c["steps_plan"]
        and c["steps_run"] <= c["lane_steps_real"]
        <= c["steps_run"] * c["lanes"] for c in window),
        f"recorded sample: {n} window rounds, every count within its bounds")
    by_hand = {
        "train_steps_run_pct": 100 * sum(c["steps_run"] for c in window)
        / sum(c["steps_plan"] for c in window),
        "train_lane_fill_pct": 100 * sum(c["lane_steps_real"] for c in window)
        / sum(c["steps_run"] * c["lanes"] for c in window)}
    for name, (_, mod) in found.items():
        value = mod.read(context(records, n))
        check(close(value, by_hand[name])
              and close(value, sample["readings"][name]),
              f"recorded sample: reader {name} = {value}")
    print("chipbench.selfcheck_steps: all passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
