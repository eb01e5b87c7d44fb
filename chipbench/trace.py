"""Reduction from a jax.profiler trace (`.xplane.pb`) to the benchmark's device
numbers, with nothing but JAX: `jax.profiler.ProfileData`.

- device planes are those named `/device:TPU:<n>`; their operations are the
  events of the line `XLA Ops` (all lines but the step and module summaries
  where a plane has no such line);
- the traced window runs from the start of the first to the end of the last
  `chipbench/*` annotation of the host's planes (the harness puts one around
  every dispatch, device wait and finalize), or over all device events where
  a trace has none;
- busy time of a device is the union of its operations' intervals inside the
  window; `busy_s` averages it over the devices used;
- an idle gap is a stretch of the window in which device 0 ran nothing; it is
  named for the harness annotation that covers its midpoint (`between_rounds`
  where none does).

`python -m chipbench.trace <dir-or-file>` prints the reduction and the trace's
planes and lines; `--record-sample <dir>` records a tiny trace on the device
that is there (how `testdata/sample.xplane.pb` was made).
"""
from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope",
                 "Framework Ops", "Source code")
ANNOTATION_PREFIX = "chipbench/"
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                    "collective-permute")


def find_xplane(path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(path.glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def read_planes(path) -> Dict[str, Any]:
    """{device planes: {name: [(op name, start ns, end ns)]}, annotations:
    [(name, start ns, end ns)]} from an xplane file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(find_xplane(path)))
    devices: Dict[str, list] = {}
    annotations = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = list(plane.lines)
            ops = [l for l in lines if l.name == OPS_LINE] or [
                l for l in lines if l.name not in SUMMARY_LINES]
            devices[plane.name] = [
                (e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                for l in ops for e in l.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        annotations.append(
                            (e.name[len(ANNOTATION_PREFIX):], float(e.start_ns),
                             float(e.start_ns + e.duration_ns)))
    return {"devices": devices, "annotations": sorted(annotations,
                                                      key=lambda a: a[1])}


def short_name(name: str) -> str:
    """A trace names an operation by its whole HLO instruction
    (`%fusion.3 = f32[8,128]{...} fusion(...), kind=...`); keep the
    instruction's name, its opcode and its first result shape."""
    m = re.match(r"(%[^ ]+) = \(?([a-z0-9]+\[[0-9,]*\])?", name)
    if not m:
        return name[:120]
    op = re.search(r"\s([a-z][a-z0-9\-]*)\(", name[m.end(1):])
    return " ".join(x for x in (m.group(1), op.group(1) if op else None,
                                m.group(2)) if x)[:120]


def reduce_events(devices: Dict[str, list], annotations: list,
                  chips: int) -> Dict[str, Any]:
    """The arithmetic, apart from the file format (selfcheck drives it on
    synthetic events too)."""
    names = sorted(devices, key=lambda n: int(n[len(DEVICE_PREFIX):].split()[0]))
    names = names[:chips]
    if not names or not any(devices[n] for n in names):
        raise ValueError("the trace holds no device operation")
    if annotations:
        lo, hi = annotations[0][1], max(a[2] for a in annotations)
    else:
        lo = min(e[1] for n in names for e in devices[n])
        hi = max(e[2] for n in names for e in devices[n])
    busy = {n: union(clip([(a, b) for _, a, b in devices[n]], lo, hi))
            for n in names}
    busy_s = sum(sum(b - a for a, b in busy[n]) for n in names) / len(names) / 1e9
    per_op: Dict[str, float] = defaultdict(float)
    collective_s = 0.0
    for name, a, b in devices[names[0]]:
        d = max(0.0, min(b, hi) - max(a, lo)) / 1e9
        if d <= 0:
            continue
        per_op[short_name(name)] += d
        if any(w in name for w in COLLECTIVE_WORDS):  # for a later cell's
            collective_s += d                           # `collective_ms` reader
    # nested events (a while loop and the ops of its body) would be counted
    # twice in a plain sum; the union above is not, and the ranking below is
    # of names as the trace gives them
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])
    idle: Dict[str, float] = defaultdict(float)
    longest = []
    for a, b in gaps(busy[names[0]], lo, hi):
        mid = (a + b) / 2
        owner = next((n for n, s, e in annotations if s <= mid <= e),
                     "between_rounds")
        idle[owner] += (b - a) / 1e9
        longest.append((owner, (b - a) / 1e9))
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_s,
            "devices": len(names),
            "top_ops": [[k, v] for k, v in top_ops],
            "idle_by_span": dict(idle),
            "idle_gaps": [[k, v] for k, v in
                          sorted(longest, key=lambda kv: -kv[1])],
            "collective_s": collective_s}


def reduce(path, chips: int) -> Dict[str, Any]:
    planes = read_planes(path)
    return reduce_events(planes["devices"], planes["annotations"], chips)


def describe(path) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(find_xplane(path)))
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events),
                  [e.name for e in events[:3]])


def record_sample(out_dir: str) -> None:
    """Three small matmul steps with the harness's annotations around them."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x) * 0.5

    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("chipbench/dispatch"):
            y = step(step(x))
        with jax.profiler.TraceAnnotation("chipbench/device_wait"):
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("chipbench/finalize"):
            float(y[0, 0])
    jax.profiler.stop_trace()
    print(find_xplane(out_dir))


if __name__ == "__main__":
    if sys.argv[1] == "--record-sample":
        record_sample(sys.argv[2])
    else:
        describe(sys.argv[1])
        r = reduce(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 1)
        r["top_ops"], r["idle_gaps"] = r["top_ops"][:15], r["idle_gaps"][:15]
        print(json.dumps(r, indent=1))
