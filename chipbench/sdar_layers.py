"""What the per-layer readers of the `sdar_moe` family's cell share beside
`lfm2_layers.py` (the scopes' device time, the window's and the traced rounds'
counts: the streamed round's spans are one family's as the other's): the
operations a traced round's block-diffusion steps needed, and the share of
positions the noise masked.

What this file names in the program beside `lfm2_layers.py`'s list; a program
without them gives `None` for every number here, never 0 and never an
exception:

- the `jax.named_scope` name `noise` under `phase/train` (the step's draws
  and the building of the noisy and the clean stream);
- on a `round/plan` record's `.counts`: `tokens_step` (positions a local step
  sends through the layers: both streams of its rows), `client_steps`;
- on a `round/record` record's `.counts`: `positions_scored` (the positions
  the round's real steps normalised their loss over: every position of a
  valid row that is not padding) and `positions_masked` (those of them the
  noise masked, which alone carry a weight); `expert_tokens_held`.

`step_mfu_pct`: the operations the traced rounds' steps needed
(`reference/sdar.py::flops_per_position`: attention over the pairs the block
mask allows, the last layer's clean stream as far as the loss depends on it,
three times forward, the experts' term from the counter instead of its
expectation; recomputation not counted) over their `phase/train` device time
times the chip's bf16 peak (`peaks.json`): a share of the whole step.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Optional

from chipbench import flops, lfm2_layers, phases

HERE = Path(__file__).resolve().parent
CONFIG = HERE / "configs" / "sdar_30b_a3b_dba.json"


def masked_positions_pct(ctx) -> Optional[float]:
    """`positions_masked` over `positions_scored`, sums over the window's
    rounds."""
    counts = lfm2_layers.window_counts(ctx, lfm2_layers.RECORD_SPAN)
    keys = ("positions_masked", "positions_scored")
    if not counts or any(k not in c for c in counts for k in keys):
        return None
    scored = sum(c["positions_scored"] for c in counts)
    return (100.0 * sum(c["positions_masked"] for c in counts) / scored
            if scored else None)


def step_mfu_pct(ctx, device_kind: str = "TPU v5 lite") -> Optional[float]:
    from chipbench.reference import sdar as ref
    plans = lfm2_layers.traced_counts(ctx, lfm2_layers.PLAN_SPAN,
                                      ("tokens_step", "client_steps"))
    records = lfm2_layers.traced_counts(ctx, lfm2_layers.RECORD_SPAN,
                                        ("expert_tokens_held",
                                         "positions_scored"))
    reduced = phases.run_phases(ctx)
    if not plans or not records or not reduced:
        return None
    seconds = reduced["scope_s"].get(lfm2_layers.TRAIN)
    if not seconds:
        return None
    model = (ctx.get("sdar_model")
             or json.loads(CONFIG.read_text())["model"])
    arch = model["arch"]
    per = ref.flops_per_position(arch, int(model["seq_len"]), 0.0)
    forward = (sum(p["client_steps"] * p["tokens_step"] for p in plans)
               * per["forward"]
               + sum(r["expert_tokens_held"] for r in records)
               * ref.expert_pair_flops(arch))
    peak = flops.peak(device_kind)["bf16_flops_per_s"]
    return 100.0 * 3 * forward / (seconds * peak)
