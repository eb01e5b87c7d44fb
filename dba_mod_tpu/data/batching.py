"""Batch plans: precomputed index tensors driving device-resident gathers.

The reference's DataLoader+SubsetRandomSampler reshuffles each client's subset
every internal epoch and yields a partial final batch (image_helper.py:252-263,
drop_last=False). The TPU-native equivalent precomputes, per round, an index
tensor [clients, epochs, steps, batch] plus a validity mask; the jitted client
step gathers rows straight from the device-resident dataset — the host ships
only these small int32 plans each round. The plan's shape is static (one
compiled round program); the client step's full-width loop runs only the
steps in which some client's mask holds a real row (fl/client.py::
active_steps), so a step padded in every client costs nothing, and stops
after the last step that `wide_from` clients share: what a client still
needs after it runs as a job at width 1 (fl/client.py::split_steps; with
`wide_from` above the number of clients, all of it). `plan_step_counts`
counts all of it from the same masks.

Shuffling uses per-client numpy RNG rather than the reference's global torch
RNG: the sequential loop's RNG stream is inherently irreproducible under
parallel clients, so parity here is statistical (SURVEY §7.2.4).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import numpy as np


@dataclasses.dataclass
class BatchPlan:
    """One round's data access plan for the stacked client step."""
    idx: np.ndarray        # [C, E, S, B] int32 indices into the dataset
    mask: np.ndarray       # [C, E, S, B] bool — valid (non-padding) samples
    num_samples: np.ndarray  # [C] int32 — true per-client dataset sizes
    num_epochs: np.ndarray   # [C] int32 — per-client internal-epoch counts


@dataclasses.dataclass
class EvalPlan:
    idx: np.ndarray        # [S, B] int32
    mask: np.ndarray       # [S, B] bool


def build_batch_plan(client_indices: Sequence[Sequence[int]],
                     client_epochs: Sequence[int], batch_size: int,
                     rng: np.random.RandomState,
                     min_steps: int = 1, min_epochs: int = 1) -> BatchPlan:
    """Build the [C, E, S, B] plan. E = max(client_epochs, min_epochs);
    clients with fewer epochs get fully-masked rows beyond their count. Every
    epoch reshuffles each client's subset (SubsetRandomSampler semantics).
    Empty clients are fully masked. `min_steps`/`min_epochs` pin the plan
    shape across rounds so the jitted round never recompiles."""
    C = len(client_indices)
    E = max(min_epochs, max(client_epochs, default=1), 1)
    sizes = np.array([len(ix) for ix in client_indices], np.int32)
    S = max(min_steps, int(np.ceil(sizes.max() / batch_size)) if sizes.max() else min_steps)
    idx = np.zeros((C, E, S, batch_size), np.int64)
    mask = np.zeros((C, E, S, batch_size), bool)
    for c, indices in enumerate(client_indices):
        n = len(indices)
        if n == 0:
            continue
        arr = np.asarray(indices, np.int64)
        for e in range(min(int(client_epochs[c]), E) if client_epochs[c] else 0):
            shuffled = arr[rng.permutation(n)]
            # Pad by wrapping the shuffled subset rather than with zeros:
            # padding rows are masked out of the loss but still flow through
            # BatchNorm's batch statistics, so they must be real samples of
            # the same client, not black images.
            reps = int(np.ceil(S * batch_size / n))
            padded = np.tile(shuffled, reps)[:S * batch_size]
            idx[c, e] = padded.reshape(S, batch_size)
            m = np.zeros((S * batch_size,), bool)
            m[:n] = True
            mask[c, e] = m.reshape(S, batch_size)
    return BatchPlan(idx=idx.astype(np.int32), mask=mask, num_samples=sizes,
                     num_epochs=np.asarray(client_epochs, np.int32))


def plan_step_counts(masks: Sequence[np.ndarray], chunk: int,
                     wide_from: int) -> Dict[str, int]:
    """What a round's plan asks of the steps loops, from its masks (one
    [C, E, S, B] per segment). The plan: `steps_plan` the loops' static
    length over the segments (E x S each), `steps_run` the steps in which ANY
    lane holds a real batch, `lane_steps_real` the real client-steps, and
    `lanes` (C). What the program runs of it, by fl/client.py's rule in
    numpy (`chunk` its STEP_CHUNK, `wide_from` the engine's: the live lanes
    from which a step runs at full width; 1, or one lane, is the full-width
    loop alone): `steps_wide` the positions the full-width loop runs — the
    steps that run up to the last one at least `wide_from` lanes share,
    rounded up to the chunk, none with `wide_from` above C — and
    `lane_steps_narrow` the real client-steps past that boundary, run one
    lane at a time as jobs. `steps_wide x lanes + lane_steps_narrow -
    lane_steps_real` slots still run masked: what packing lanes could win
    (a job's up to `chunk - 1` padding steps are not slots of the plan)."""
    real = np.stack([np.asarray(m).any(axis=-1) for m in masks])  # [I,C,E,S]
    n_seg, lanes = real.shape[:2]
    live = real.reshape(n_seg, lanes, -1).sum(axis=1)             # [I, E*S]
    wide_from = wide_from if lanes > 1 else 1
    steps_wide = lane_steps_narrow = 0
    for seg in live:
        seg = seg[seg > 0]                # by position of the loops' order
        last = np.flatnonzero(seg >= wide_from)
        n_wide = -(-(last[-1] + 1 if len(last) else 0) // chunk) * chunk
        steps_wide += int(n_wide)
        lane_steps_narrow += int(seg[n_wide:].sum())
    return {"steps_plan": int(n_seg * live.shape[1]),
            "steps_run": int((live > 0).sum()),
            "lane_steps_real": int(live.sum()),
            "lanes": int(lanes),
            "steps_wide": steps_wide,
            "lane_steps_narrow": lane_steps_narrow}


def build_eval_plan(indices: np.ndarray, batch_size: int) -> EvalPlan:
    """Sequential padded batches over `indices` (test loaders iterate the full
    set once; order is irrelevant to the accuracy sums — test.py:29-37)."""
    n = len(indices)
    S = max(1, int(np.ceil(n / batch_size)))
    idx = np.zeros((S * batch_size,), np.int64)
    idx[:n] = np.asarray(indices, np.int64)
    mask = np.zeros((S * batch_size,), bool)
    mask[:n] = True
    return EvalPlan(idx=idx.reshape(S, batch_size).astype(np.int32),
                    mask=mask.reshape(S, batch_size))


def stack_ragged(arrays: List[np.ndarray], pad_value=0) -> np.ndarray:
    """Stack per-client ragged arrays into [C, max_n, ...] with padding —
    used for LOAN per-state shards."""
    C = len(arrays)
    max_n = max(a.shape[0] for a in arrays)
    out = np.full((C, max_n) + arrays[0].shape[1:], pad_value,
                  arrays[0].dtype)
    for i, a in enumerate(arrays):
        out[i, :a.shape[0]] = a
    return out
