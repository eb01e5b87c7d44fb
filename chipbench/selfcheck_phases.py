"""Self-check of `phases.py` and of the readers that go through it, on the CPU:

    python -m chipbench.selfcheck_phases

- the reduction on synthetic events with a known answer: a `while` nested
  around the operations of its body, a `while` the compiler left without a
  scope path, an idle gap inside `round/fetch`, one inside no span of the
  program;
- the reduction on `testdata/phases_sample.xplane.pb`, a small trace recorded
  on a TPU v5e by `python -m chipbench.phases --record-sample` (three rounds
  of a jitted `round_fn`: a four-step loop with the program's own named
  fused update under `phase/train`, a reduction under
  `phase/global_battery`, under the program's spans and the harness's
  annotations);
- every reader that imports `phases` on a synthetic context, and on an empty
  one (a program without spans or scopes).
Exits non-zero on the first disagreement.
"""
from __future__ import annotations

import json
import sys
from collections import namedtuple
from pathlib import Path

from chipbench import phases
from chipbench import run as harness
from chipbench.selfcheck import check, close

HERE = Path(__file__).resolve().parent
SAMPLE = HERE / "testdata" / "phases_sample.xplane.pb"
Span = namedtuple("Span", "name start_ns end_ns parent round")


def synthetic_events():
    """Times in ns; one round in a traced span of 10 s set by the harness's
    annotations. Returns (ops, harness annotations, program spans)."""
    s = 1e9
    top = "jit(round_fn)/phase/"
    annotations = [("chipbench/dispatch", 0.0, 1 * s),
                   ("chipbench/device_wait", 1 * s, 8 * s),
                   ("chipbench/finalize", 8 * s, 10 * s)]
    spans = [("round/plan", 0.0, 0.4 * s), ("round/stage", 0.4 * s, 0.6 * s),
             ("round/enqueue", 0.6 * s, 1 * s),
             ("round/fetch", 8 * s, 8.5 * s),
             ("round/record", 8.5 * s, 9 * s)]
    body = top + "train/while/body/closed_call/"
    ops = [("%while.30 = loop", top + "train/while", 0.5 * s, 6.5 * s),
           ("%fusion.7 = conv", body + "conv", 1 * s, 3 * s),  # in the while
           ("%fusion.7 = conv", body + "conv", 4 * s, 6 * s),
           ("%vmap_fused_sgd_update_0_.3 = custom-call",
            body + "vmap(fused_sgd_update_0)/pallas_call", 3 * s, 3.5 * s),
           ("%vmap_fused_sgd_update_1_.4 = custom-call",
            body + "vmap(fused_sgd_update_1)/pallas_call", 6 * s, 6.25 * s),
           ("%fusion.9 = add", top + "aggregate/add", 6.5 * s, 6.6 * s),
           # a loop the compiler left without a scope path, around named
           # operations, a gap between them and an unnamed one of its own
           ("%while.27 = loop", "", 6.6 * s, 7.6 * s),
           ("%fusion.3 = conv", top + "local_battery/while/body/conv",
            6.6 * s, 7 * s),
           ("%reverse.1 = reverse", "", 7 * s, 7.05 * s),
           ("%fusion.3 = conv", top + "local_battery/while/body/conv",
            7.1 * s, 7.6 * s),
           ("%while.28 = loop", top + "global_battery/while", 7.6 * s, 8 * s),
           ("%copy.1 = copy", "", 8.2 * s, 8.3 * s)]      # under no scope
    return ops, annotations, spans


def synthetic_context(reduced):
    ms = 1e6
    rec = lambda name, t0, dur, rnd: Span(name, int(t0 * ms),
                                          int((t0 + dur) * ms), None, rnd)
    records = [rec("setup/data", 0, 80_000, None)]
    for rnd, at in ((3, 100_000), (4, 110_000), (5, 120_000), (6, 130_000)):
        records += [rec("round/plan", at, 7 + rnd, rnd),
                    rec("round/stage", at + 20, 2, rnd),
                    rec("round/fetch", at + 9000, 4, rnd),
                    rec("round/record", at + 9010, 1 + rnd, rnd)]
    return {"spans": {"dispatch": [0.01, 0.03, 0.02],       # three rounds
                      "finalize": [0.004, 0.002, 0.003]},   # in the window
            "counters": {}, "trace": None,
            "traced": {"rounds": 2, "window_s": 10.0},
            "program_spans": records,
            "compile_stages": {"round_fn": {"xla/trace_secs": 20.0,
                                            "xla/lower_secs": 5.0,
                                            "xla/compile_secs": 90.0,
                                            "xla/cache_retrieval_secs": 70.0},
                               "reference": {"xla/lower_secs": 1.0}},
            "phases": reduced}


def main() -> int:
    r = phases.reduce_events(*synthetic_events())
    check(close(r["window_s"], 10.0) and close(r["busy_s"], 7.6),
          "window from the harness's annotations; busy is the union")
    check(close(r["scope_s"]["phase/train"], 6.0),
          "a while counts once, not again by the operations of its body")
    check(close(r["scope_s"]["phase/aggregate"], 0.1)
          and close(r["scope_s"]["phase/global_battery"], 0.4),
          "device time under each of the other scopes")
    check(close(r["scope_s"]["phase/local_battery"], 1.0),
          "a while without a scope path counts under the scope of its body")
    check(close(r["kernel_s"], 0.75), "the named kernel, over its chunks")
    check(close(r["unattributed_s"], 0.1)
          and [op[:7] for op, _ in r["unattributed_ops"]] == ["%copy.1"]
          and close(r["unattributed_ops"][0][1], 0.1),
          "busy time under no scope, and the operation that makes it")
    idle = r["idle_by_program_span"]
    check(close(idle["round/plan"], 0.5) and close(idle["round/fetch"], 0.2),
          "a gap belongs to the leaf span that covers its midpoint")
    check(close(idle["no_span"], 1.7), "a gap inside no span is named so")
    check(close(r["idle_attributed_pct"], 100 * 0.7 / 2.4),
          "idle_attributed_pct is the attributed share of idle time")
    ops, annotations, _ = synthetic_events()
    bare = phases.reduce_events([(n, "", a, b) for n, _, a, b in ops],
                                annotations, [])
    check(bare["scope_s"] == {} and bare["idle_attributed_pct"] is None
          and not bare["scopes_in_trace"] and close(bare["kernel_s"], 0.75),
          "a trace without scopes or spans gives nothing, not zero")

    t = phases.read_trace(SAMPLE)
    sample = phases.reduce_events(t["ops"], t["harness"], t["spans"])
    check([n for n, _, _ in t["spans"]]
          == ["round/plan", "round/enqueue", "round/fetch"] * 3,
          "the recorded trace holds the program's nine spans in order")
    check(any("/phase/train/while" in scope for _, scope, _, _ in t["ops"]),
          "recorded trace: the scope path is in the operations' event metadata")
    check(set(sample["scope_s"]) == {"phase/train", "phase/global_battery"}
          and sample["scope_s"]["phase/train"]
          > sample["scope_s"]["phase/global_battery"] > 0,
          f"recorded trace: device seconds by scope {sample['scope_s']}")
    check(0 < sample["kernel_s"] < sample["scope_s"]["phase/train"],
          f"recorded trace: the kernel named fused_sgd_update "
          f"({sample['kernel_s']:.2e} s) lies inside phase/train")
    check(abs(sum(sample["idle_by_program_span"].values()) + sample["busy_s"]
              - sample["window_s"]) < 1e-6
          and 50 < sample["idle_attributed_pct"] <= 100,
          f"recorded trace: idle and busy fill the window; "
          f"{sample['idle_attributed_pct']:.1f} % of the idle time attributed")

    by_round = phases.reduce_by_round(t)
    # (rounds of a millisecond: the device's clock lies a round off the
    # host's there, so only the sums are held)
    check(len(by_round) == 3
          and all(b["busy_s"] <= b["window_s"] for b in by_round)
          and close(sum(b["scope_s"].get("phase/train", 0.0) for b in by_round),
                    sample["scope_s"]["phase/train"], 1e-6)
          and close(sum(b["busy_s"] for b in by_round), sample["busy_s"], 1e-6),
          "recorded trace: a reduction for each of the three rounds; their "
          "busy and train seconds add up to the trace's")

    ctx = synthetic_context(r)
    empty = {"spans": {}, "counters": {}, "trace": None, "traced": None,
             "program_spans": None, "compile_stages": {}, "phases": None}
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    want = {"data_build_s": 80.0, "lower_s": 25.0, "cache_load_s": 70.0,
            "plan_ms": 12.0, "stage_ms": 2.0, "fetch_ms": 4.0,
            "record_ms": 6.0, "train_device_ms": 3000.0,
            "aggregate_device_ms": 50.0,
            "local_battery_device_ms": 500.0,
            "global_battery_device_ms": 200.0,
            "idle_attributed_pct": 100 * 0.7 / 2.4}
    seen = set()
    for m, mod in harness.load_readers(bench, bench["workloads"][0]["name"]):
        if getattr(mod, "phases", None) is not phases:
            continue  # selfcheck.py's
        seen.add(m["name"])
        value = mod.read(ctx)
        check(m["name"] in want and close(value, want[m["name"]]),
              f"reader {m['name']} = {value}")
        check((mod.LAYER, mod.UNIT, mod.MOVES)
              == (m["layer"], m["unit"], m["moves"]),
              f"reader {m['name']} states the layer, unit and moves of "
              "BENCHMARK.json")
        check(mod.read(empty) is None,
              f"reader {m['name']} returns nothing where nothing is to read")
    check(seen == set(want), "every reader of the program's spans was checked")
    print("chipbench.selfcheck_phases: all passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
