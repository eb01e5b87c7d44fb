"""Self-check of the trace reduction and the metric arithmetic, on the CPU:

    python -m chipbench.selfcheck

- the reduction on synthetic device events with a known answer;
- the reduction on `testdata/sample.xplane.pb`, a small trace recorded on a
  TPU v5e by `python -m chipbench.trace --record-sample` (three annotated
  steps of two 1024x1024 matmuls each);
- the per-layer readers of the harness's own clocks, counters and trace
  reduction on a synthetic context (`selfcheck_phases` and `selfcheck_steps`
  check the readers of the program's spans, scopes and counts), and the
  end-to-end arithmetic on a synthetic list of round times.
Exits non-zero on the first disagreement.
"""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path

from chipbench import run as harness
from chipbench import trace

HERE = Path(__file__).resolve().parent


def close(a, b, tol=1e-9):
    return math.isclose(a, b, rel_tol=tol, abs_tol=tol)


def check(cond, what):
    if not cond:
        raise SystemExit(f"chipbench.selfcheck: {what}")
    print("ok:", what)


def synthetic_events():
    # times in ns; two devices, a window of 10 s set by the annotations
    s = 1e9
    annotations = [("dispatch", 0.0, 1 * s), ("device_wait", 1 * s, 8 * s),
                   ("finalize", 8 * s, 10 * s)]
    dev0 = [("%while.1 = loop", 0.5 * s, 6.5 * s),        # covers the two below
            ("%fusion.7 = conv", 1 * s, 3 * s), ("%fusion.7 = conv", 4 * s, 6 * s),
            ("%all-reduce.2 = sum", 7 * s, 7.5 * s),
            ("%fusion.9 = late", 9.5 * s, 11 * s)]          # clipped at 10 s
    dev1 = [("%fusion.7 = conv", 0.0, 5 * s)]
    return {"/device:TPU:0": dev0, "/device:TPU:1": dev1}, annotations


def main() -> int:
    devices, annotations = synthetic_events()
    r = trace.reduce_events(devices, annotations, chips=2)
    check(close(r["window_s"], 10.0), "window is the span of the annotations")
    # device 0 busy: [0.5,6.5] + [7,7.5] + [9.5,10] = 7.0; device 1: 5.0
    check(close(r["busy_s"], 6.0), "busy_s is the union per device, averaged")
    check(close(r["collective_s"], 0.5), "collective time on device 0")
    check(r["top_ops"][0][0].startswith("%while.1") and close(r["top_ops"][0][1], 6.0),
          "operations ranked by summed time, under the trace's names")
    idle = r["idle_by_span"]
    check(close(idle["dispatch"], 0.5) and close(idle["device_wait"], 0.5)
          and close(idle["finalize"], 2.0), "idle gaps named for the covering span")
    one = trace.reduce_events(devices, annotations, chips=1)
    check(close(one["busy_s"], 7.0), "a one-chip cell reads device 0 alone")

    sample = trace.reduce(HERE / "testdata" / "sample.xplane.pb", 1)
    planes = trace.read_planes(HERE / "testdata" / "sample.xplane.pb")
    names = [a[0] for a in planes["annotations"]]
    check(names == ["dispatch", "device_wait", "finalize"] * 3,
          "the recorded trace holds the harness's nine annotations in order")
    check(0 < sample["busy_s"] < sample["window_s"] < 1.0,
          f"recorded trace: busy {sample['busy_s']:.6f} s of {sample['window_s']:.6f} s")
    check(any("fusion" in n for n, _ in sample["top_ops"]),
          "recorded trace: the matmul fusion is among the device operations")
    check(abs(sum(v for _, v in sample["idle_gaps"]) + sample["busy_s"]
              - sample["window_s"]) < 1e-6, "idle gaps and busy time fill the window")

    ctx = {"spans": {"build": [20.0], "first_round": [100.0],
                     "steady_round": [4.0, 5.0], "dispatch": [0.010, 0.030, 0.020],
                     "finalize": [0.004, 0.002, 0.003]},
           "counters": {"compile_cache_hits": 3},
           "trace": r, "traced": {"rounds": 2, "window_s": 10.0}}
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    want = {"build_s": 20.0, "compile_s": 96.0, "compile_cache_hits": 3,
            "dispatch_ms": 20.0, "finalize_ms": 3.0,
            "device_idle_pct": 40.0, "round_device_ms": 3000.0}
    readers = [(m, mod) for m, mod in harness.load_readers(
        bench, bench["workloads"][0]["name"]) if m["name"] in want]
    check({m["name"] for m, _ in readers} == set(want),
          "BENCHMARK.json lists every reader this self-check expects")
    for m, mod in readers:
        value = mod.read(ctx)
        check(close(value, want[m["name"]]), f"reader {m['name']} = {value}")
        check((mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"], m["moves"]),
              f"reader {m['name']} states the layer, unit and moves of BENCHMARK.json")
        empty = mod.read({"spans": {}, "counters": {}, "trace": None, "traced": None})
        check(empty is None, f"reader {m['name']} returns nothing where nothing is to read")

    rounds = [2.0, 2.1, 2.0, 6.0]      # the last: a stall; window closed at 12.1 s
    e2e = harness.end_to_end(rounds_s=rounds, failed=1, no_models=10,
                             window_s=12.1, peak_bytes=3 * 2 ** 30, setup_s=50.0)
    check(close(e2e["client_updates_per_s"][0], 3 * 10 / 12.1)
          and close(e2e["round_s_max"][0], 6.0) and close(e2e["peak_hbm_gib"][0], 3.0)
          and close(e2e["setup_s"][0], 50.0),
          "end-to-end: finished rounds x clients over the whole window; the slowest round")
    print("chipbench.selfcheck: all passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
