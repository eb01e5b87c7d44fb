"""What the two readers of the plan's step counts share: the counts the
program puts on each round's `round/plan` span, read in-process.

What this file names in the program (`chipbench/program.py` and
`chipbench/phases.py` list the rest); a PR that renames one keeps the readers
running: a record of `dba_mod_tpu.utils.telemetry.spans()` named `round/plan`
carries `.counts`, a dict with

- `steps_plan`: the static length of the client step's loop in that round
  (segments x E x S of the `[I, C, E, S, B]` plan),
- `steps_run`: the steps in which any lane holds a real batch — what the
  loop runs (in chunks of a few steps), read by the round program from the
  same mask,
- `lane_steps_real`: the real client-steps (lane x step pairs with a batch),
- `lanes`: C, the lanes of the plan: the width of the full-width loop (a job
  of the width-1 loop runs one of them).

A program whose records carry no counts (the parent of the PR that added this
file) gives `None` for every number here, never 0 and never an exception.

`python -m chipbench.selfcheck_steps` checks both readers on the CPU.
"""
from __future__ import annotations

from typing import Callable, List, Optional

from chipbench import phases

PLAN_SPAN = "round/plan"
HARNESS_SPAN = "dispatch"


def window_counts(ctx) -> Optional[List[dict]]:
    """The counts of the window's rounds: those on the last n `round/plan`
    records, n being the rounds the harness clocked."""
    n = len(ctx["spans"].get(HARNESS_SPAN) or ())
    found = [r for r in phases.program_spans(ctx) or ()
             if r.name == PLAN_SPAN]
    if not n or len(found) < n:
        return None
    counts = [getattr(r, "counts", None) for r in found[-n:]]
    return counts if all(counts) else None


def window_total_pct(ctx, part: Callable[[dict], int],
                     whole: Callable[[dict], int]) -> Optional[float]:
    """100 x the sum of the parts over the sum of the wholes, over the
    window's rounds: a round weighs by its steps, so a poisoned round of 280
    steps counts for more than a clean one of 64. Nothing where no round has
    a whole."""
    counts = window_counts(ctx) or ()
    total = sum(whole(c) for c in counts)
    return 100.0 * sum(part(c) for c in counts) / total if total else None
