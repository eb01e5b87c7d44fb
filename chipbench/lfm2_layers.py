"""What the per-layer readers of the `lfm2_moe` family's cell share: device
time under the named scopes the model and the streamed round put inside
`phase/train`, the counts the program puts on its `round/plan` and
`round/record` spans, and the operations a traced round's steps needed.

What this file names in the program (`chipbench/program.py`,
`chipbench/phases.py` and `chipbench/steps.py` list the rest); a program
without them gives `None` for every number here, never 0 and never an
exception:

- the `jax.named_scope` names `mixer` (short convolutions and attention),
  `router`, `experts` (the held experts' and the dense layer's SwiGLU
  products) and `optimizer` (the torch-SGD update) under `phase/train`; an
  operation of the backward pass carries the same word inside
  `transpose(jvp(...))`;
- on a `round/plan` record's `.counts`: `tokens_step` (positions a local
  step reads) and `client_steps` (real client-steps of the round);
- on a `round/record` record's `.counts`: `expert_tokens_held` (token-expert
  pairs the held experts computed in the round's steps),
  `expert_tokens_max` (the most one held expert was given in one step) and
  `expert_tokens_mean`.

Device time under a word: the union of the intervals, clipped to the traced
span, of device 0's operations whose scope path holds `phase/train` and the
word, over the rounds traced. The trace gives a `conditional` one event
without a scope path and none for the operations inside it (looked at by hand
on a TPU v5e: 16 a step, a tenth of a round). The only conditionals inside
`phase/train` are the expert layer's choice between its two paths, forward
and backward, so such an event counts under `experts` when the operation
with a scope path that ran last before it was `phase/train`'s. `client_step_mfu_pct`: the operations the
traced rounds' steps needed (`reference/lfm2.py::flops_per_token`, three
times forward, the experts' term from the counter instead of its
expectation) over their `phase/train` device time times the chip's bf16
peak (`peaks.json`): a share of the whole step.
"""
from __future__ import annotations

import functools
import json
import re
from pathlib import Path
from typing import List, Optional

from chipbench import flops, phases, trace

HERE = Path(__file__).resolve().parent
CONFIG = HERE / "configs" / "lfm2_24b_a2b_dba.json"
TRAIN = "phase/train"
PLAN_SPAN, RECORD_SPAN, HARNESS_SPAN = "round/plan", "round/record", "dispatch"
CONDITIONAL = re.compile(r"\sconditional\(")  # in an HLO instruction's text


@functools.lru_cache(maxsize=1)
def _run_ops():
    path = phases.find_run_xplane()
    if path is None:
        return None
    t = phases.read_trace(path)
    if not t["ops"]:
        return None
    if t["harness"]:
        lo, hi = t["harness"][0][1], max(a[2] for a in t["harness"])
    else:
        lo, hi = min(o[2] for o in t["ops"]), max(o[3] for o in t["ops"])
    return [(name, scope, max(a, lo), min(b, hi))
            for name, scope, a, b in t["ops"] if min(b, hi) > max(a, lo)]


def _scoped(ops):
    """(scope path, start, end) of every operation, an unscoped conditional
    taking `phase/train/experts` where it ran inside `phase/train`."""
    out, last = [], ""
    for name, scope, a, b in sorted(ops, key=lambda o: o[2]):
        if scope:
            last = scope
        elif TRAIN in last and CONDITIONAL.search(name):
            scope = TRAIN + "/experts"
        out.append((scope, a, b))
    return out


def scope_ms(ctx, words) -> Optional[float]:
    """Device ms a traced round under `phase/train` and any of `words`."""
    traced = ctx.get("traced")
    ops = ctx["lfm2_ops"] if "lfm2_ops" in ctx else _run_ops()
    if not ops or not traced or not traced.get("rounds"):
        return None
    found = trace.union([(a, b) for scope, a, b in _scoped(ops)
                         if TRAIN in scope and any(w in scope for w in words)])
    if not found:
        return None
    return sum(b - a for a, b in found) / 1e6 / traced["rounds"]


def window_counts(ctx, span: str) -> Optional[List[dict]]:
    """The counts on the last n records of `span`, n the rounds the harness
    clocked; nothing where a record carries none."""
    n = len(ctx["spans"].get(HARNESS_SPAN) or ())
    found = [r for r in phases.program_spans(ctx) or () if r.name == span]
    if not n or len(found) < n:
        return None
    counts = [getattr(r, "counts", None) for r in found[-n:]]
    return counts if all(counts) else None


def traced_counts(ctx, span: str, keys) -> Optional[List[dict]]:
    """Those of the traced rounds, each holding all of `keys`."""
    counts, traced = window_counts(ctx, span), ctx.get("traced")
    if not counts or not traced or not traced.get("window_rounds"):
        return None
    rounds = [r for r in traced["window_rounds"] if 1 <= r <= len(counts)]
    picked = [counts[r - 1] for r in rounds]
    if not picked or any(k not in c for c in picked for k in keys):
        return None
    return picked


def load_max_over_mean(ctx) -> Optional[float]:
    counts = window_counts(ctx, RECORD_SPAN)
    if not counts or any("expert_tokens_mean" not in c for c in counts):
        return None
    ratios = [c["expert_tokens_max"] / c["expert_tokens_mean"]
              for c in counts if c["expert_tokens_mean"]]
    return sum(ratios) / len(ratios) if ratios else None


def step_mfu_pct(ctx, device_kind: str = "TPU v5 lite") -> Optional[float]:
    from chipbench.reference import lfm2 as ref
    plans = traced_counts(ctx, PLAN_SPAN, ("tokens_step", "client_steps"))
    records = traced_counts(ctx, RECORD_SPAN, ("expert_tokens_held",))
    reduced = phases.run_phases(ctx)
    if not plans or not records or not reduced:
        return None
    seconds = reduced["scope_s"].get(TRAIN)
    if not seconds:
        return None
    model = (ctx.get("lfm2_model")
             or json.loads(CONFIG.read_text())["model"])
    arch = model["arch"]
    per = ref.flops_per_token(arch, int(model["seq_len"]), 0.0)
    pair = 3 * 2 * arch["hidden_size"] * arch["moe_intermediate_size"]
    forward = (sum(p["client_steps"] * p["tokens_step"] for p in plans)
               * per["forward"]
               + sum(r["expert_tokens_held"] for r in records) * pair)
    peak = flops.peak(device_kind)["bf16_flops_per_s"]
    return 100.0 * 3 * forward / (seconds * peak)
