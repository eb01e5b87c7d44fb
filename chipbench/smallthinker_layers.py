"""What the per-layer readers of the `smallthinker` family's cell share beside
`lfm2_layers.py` (the scopes' device time, the window's and the traced rounds'
counts: the streamed round's spans are one family's as the other's): the
operations and bytes a traced round's steps needed of the two kernels, their
shares of the chip's rooflines, and the step's share of the chip's peak.

What this file names in the program beside `lfm2_layers.py`'s list; a program
without them gives `None` for every number here, never 0 and never an
exception:

- the `jax.named_scope` names `mixer/attention_full` and
  `mixer/attention_window` (the attention kernel's calls in a global layer
  and in a window layer), `experts` (the grouped expert product) and `head`
  (the final norm and the head product) under `phase/train`; an operation of
  the backward pass and of `remat`'s recomputation carries the same words;
- on a `round/plan` record's `.counts`: `tokens_step`, `client_steps`,
  `attention_pairs_full` and `attention_pairs_window` (the (query, key) pairs
  each kind's mask allows a row a layer);
- on a `round/record` record's `.counts`: `expert_tokens_held`.

**Operations and bytes count the work the mathematics needs, whatever
implements it**, never tiles visited or rows padded; 2 a multiply-add, float32
bytes. A training step runs a layer's forward pass, runs it again inside the
backward pass (`nn.remat`: the kernels' scopes hold the recomputation's device
time, so it is counted: once more the forward's) and its backward pass:

- attention, a layer a step: the forward's score and value products over the
  pairs the layer's mask allows (`reference/smallthinker.py::pair_flops` a
  pair: 4 x 128 a query head), the backward's four products (dv, dp, dq, dk:
  twice the forward's); it reads q, k, v and writes the output forward, reads
  q, k, v, the output and its gradient and writes three gradients backward;
- the experts, a layer a step: `expert_pair_flops` (6 x 2560 x 768) a routed
  (position, held expert) pair forward, twice that backward; forward it reads
  a pair's row and writes one and reads the held experts' matrices, backward
  it reads two rows a pair and writes one, reads the matrices and writes
  their gradients.

A kernel's share of its roofline is the larger of (operations over the chip's
bf16 peak) and (bytes over its memory bandwidth), `peaks.json`, over the
kernel scopes' device time in the traced rounds.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Optional

from chipbench import flops, lfm2_layers, phases

HERE = Path(__file__).resolve().parent
CONFIG = HERE / "configs" / "smallthinker_21b_a3b_dba.json"
ATTENTION_SCOPES = ("mixer/attention_full", "mixer/attention_window")
EXPERT_SCOPES = ("experts",)
PASSES = {"forward": 2, "backward": 1}   # the forward pass runs twice (remat)


def model_of(ctx) -> dict:
    return (ctx.get("smallthinker_model")
            or json.loads(CONFIG.read_text())["model"])


def layer_kinds(arch: dict) -> dict:
    """How many of the layers run are of each attention kind."""
    window = sum(arch["sliding_window_layout"][i] for i in arch["layers_run"])
    return {"full": len(arch["layers_run"]) - window, "window": window}


def attention_work(arch: dict, seq_len: int, pairs: dict, steps: int) -> dict:
    """(operations, bytes) `steps` training steps' attention needs, one row
    of `seq_len` a step; `pairs` = {kind: pairs a row a layer}."""
    from chipbench.reference import smallthinker as ref
    kinds = layer_kinds(arch)
    forward = ref.pair_flops(arch) * sum(kinds[k] * pairs[k] for k in kinds)
    q = 4 * seq_len * arch["num_attention_heads"] * arch["head_dim"]
    kv = 4 * seq_len * arch["num_key_value_heads"] * arch["head_dim"]
    layers = sum(kinds.values())
    return {"ops": steps * forward * (PASSES["forward"]
                                      + 2 * PASSES["backward"]),
            "bytes": steps * layers * (PASSES["forward"] * (2 * q + 2 * kv)
                                       + PASSES["backward"] * (4 * q + 4 * kv))}


def experts_work(arch: dict, routed_pairs: int, layer_steps: int) -> dict:
    """(operations, bytes) the held experts' products need for `routed_pairs`
    (position, held expert) pairs routed over `layer_steps` (step, layer)
    calls."""
    from chipbench.reference import smallthinker as ref
    lo, hi = arch["experts_held"]
    row = 4 * arch["hidden_size"]
    matrices = 4 * (hi - lo) * 3 * arch["hidden_size"] * arch["moe_ffn_hidden_size"]
    return {"ops": routed_pairs * ref.expert_pair_flops(arch)
            * (PASSES["forward"] + 2 * PASSES["backward"]),
            "bytes": PASSES["forward"] * (2 * routed_pairs * row
                                          + layer_steps * matrices)
            + PASSES["backward"] * (3 * routed_pairs * row
                                    + 2 * layer_steps * matrices)}


def roofline_pct(work: dict, seconds: float,
                 device_kind: str = "TPU v5 lite") -> float:
    peak = flops.peak(device_kind)
    bound = max(work["ops"] / peak["bf16_flops_per_s"],
                work["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * bound / seconds


def _traced(ctx, scopes, plan_keys):
    """(the traced rounds' plan counts, each holding `client_steps` and
    `plan_keys`; device seconds under `scopes` in them), or None where the
    program lacks either."""
    plans = lfm2_layers.traced_counts(ctx, lfm2_layers.PLAN_SPAN,
                                      ("client_steps",) + plan_keys)
    ms = lfm2_layers.scope_ms(ctx, scopes)
    if not plans or not ms:
        return None
    return plans, ms * ctx["traced"]["rounds"] / 1e3


def attention_roofline_pct(ctx) -> Optional[float]:
    found = _traced(ctx, ATTENTION_SCOPES,
                    ("attention_pairs_full", "attention_pairs_window"))
    if not found:
        return None
    plans, seconds = found
    model = model_of(ctx)
    pairs = {"full": plans[0]["attention_pairs_full"],
             "window": plans[0]["attention_pairs_window"]}
    work = attention_work(model["arch"], int(model["seq_len"]), pairs,
                          sum(p["client_steps"] for p in plans))
    return roofline_pct(work, seconds)


def experts_roofline_pct(ctx) -> Optional[float]:
    found = _traced(ctx, EXPERT_SCOPES, ())
    records = lfm2_layers.traced_counts(ctx, lfm2_layers.RECORD_SPAN,
                                        ("expert_tokens_held",))
    if not found or not records:
        return None
    plans, seconds = found
    arch = model_of(ctx)["arch"]
    work = experts_work(arch, sum(r["expert_tokens_held"] for r in records),
                        sum(p["client_steps"] for p in plans)
                        * len(arch["layers_run"]))
    return roofline_pct(work, seconds)


def step_mfu_pct(ctx, device_kind: str = "TPU v5 lite") -> Optional[float]:
    """The operations the traced rounds' steps needed
    (`reference/smallthinker.py::flops_per_token`: attention over the pairs
    each layer's mask allows, three times forward, the experts' term from the
    counter instead of its expectation; recomputation not counted) over their
    `phase/train` device time times the chip's bf16 peak: a share of the
    whole step."""
    from chipbench.reference import smallthinker as ref
    plans = lfm2_layers.traced_counts(ctx, lfm2_layers.PLAN_SPAN,
                                      ("tokens_step", "client_steps"))
    records = lfm2_layers.traced_counts(ctx, lfm2_layers.RECORD_SPAN,
                                        ("expert_tokens_held",))
    reduced = phases.run_phases(ctx)
    if not plans or not records or not reduced:
        return None
    seconds = reduced["scope_s"].get(lfm2_layers.TRAIN)
    if not seconds:
        return None
    model = model_of(ctx)
    arch = model["arch"]
    per = ref.flops_per_token(arch, int(model["seq_len"]), 0.0)
    forward = (sum(p["client_steps"] * p["tokens_step"] for p in plans)
               * per["forward"]
               + sum(r["expert_tokens_held"] for r in records)
               * ref.expert_pair_flops(arch))
    peak = flops.peak(device_kind)["bf16_flops_per_s"]
    return 100.0 * 3 * forward / (seconds * peak)


def window_share_pct(ctx, span: str, part: str, whole: str) -> Optional[float]:
    """`part` over `whole`, sums over the window's rounds of the counts on
    `span`; nothing where a round lacks either or the whole is 0."""
    counts = lfm2_layers.window_counts(ctx, span)
    if not counts or any(k not in c for c in counts for k in (part, whole)):
        return None
    every = sum(c[whole] for c in counts)
    return 100.0 * sum(c[part] for c in counts) / every if every else None
