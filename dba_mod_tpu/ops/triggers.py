"""Backdoor trigger machinery as pure, vmap-safe jnp ops.

The reference stamps pixel patterns per-sample in a Python loop
(image_helper.py:298-350) and assigns LOAN feature columns per-sample
(loan_train.py:99-107, test.py:75-81). TPU-native equivalents:

- a *pattern bank*: [trigger_num + 1, H, W] {0,1} masks built once on host,
  where row `i` is adversary i's sub-pattern and the LAST row is the combined
  (global) pattern used by `adversarial_index == -1` (image_helper.py:331-335);
  stamping is then `img·(1-mask) + mask` broadcast over channels — pixels are
  set to 1.0 in every channel (image_helper.py:336-348);
- a *feature-trigger bank* for LOAN: [trigger_num + 1, F] value rows plus
  {0,1} masks over feature columns; stamping is a vectorized select;
- batch poisoning as a per-sample boolean: training poisons the first
  `poisoning_per_batch` samples of each batch, evaluation poisons all
  (image_helper.py:306-319).

- a *phrase bank* for token sequences, the DBA analogue for text: a trigger
  phrase split into `trigger_num` sub-spans, adversary `i` writing only its
  own span at its own place in the phrase and the test all of them; a fixed
  target continuation right behind the phrase takes the place of the swapped
  label. `[trigger_num + 1, T]` token values plus {0,1} masks over the
  positions of a row, like the feature bank; the continuation has a row of
  values and a mask of its own, stamped by every adversary.

All functions take the bank + a traced `adv_index` so one jitted computation
serves every adversary; index -1 (mapped to the last bank row) is the global
pattern.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dba_mod_tpu import config as cfg


# --------------------------------------------------------------------- builders
def build_pixel_pattern_bank(params: cfg.Params, height: int,
                             width: int) -> np.ndarray:
    """[trigger_num + 1, H, W] float32 {0,1} masks; row trigger_num is the
    union of all sub-patterns (the global/combined trigger)."""
    n = int(params["trigger_num"])
    bank = np.zeros((n + 1, height, width), np.float32)
    for i in range(n):
        for (r, c) in params.poison_pattern_for(i):
            bank[i, r, c] = 1.0
            bank[n, r, c] = 1.0
    return bank


def build_feature_trigger_bank(params: cfg.Params,
                               feature_dict: dict,
                               num_features: int
                               ) -> Tuple[np.ndarray, np.ndarray]:
    """LOAN: ([trigger_num + 1, F] values, [trigger_num + 1, F] {0,1} masks);
    row trigger_num is all per-adversary triggers concatenated
    (loan_train.py:49-57). Later values win on overlap, matching the
    reference's sequential assignment."""
    n = int(params["trigger_num"])
    values = np.zeros((n + 1, num_features), np.float32)
    masks = np.zeros((n + 1, num_features), np.float32)
    for i in range(n):
        names, vals = params.poison_trigger_features_for(i)
        for name, val in zip(names, vals):
            col = feature_dict[name]
            values[i, col] = val
            masks[i, col] = 1.0
            values[n, col] = val
            masks[n, col] = 1.0
    return values, masks


def bank_row(adv_index, bank_size: int):
    """Map a (possibly traced) adversarial index to a bank row: -1 → last row
    (the combined/global pattern)."""
    return jnp.where(adv_index < 0, bank_size - 1, adv_index)


# --------------------------------------------------------------------- stamping
def stamp_pixel_pattern(images: jax.Array, pattern_bank: jax.Array,
                        adv_index) -> jax.Array:
    """Stamp trigger pixels to 1.0 in all channels. images: [..., H, W, C]
    (NHWC); pattern_bank: [K, H, W]; adv_index: traced scalar, -1 = global."""
    mask = pattern_bank[bank_row(adv_index, pattern_bank.shape[0])]
    mask = mask[..., None]  # broadcast over channels
    return images * (1.0 - mask) + mask


def stamp_feature_trigger(rows: jax.Array, value_bank: jax.Array,
                          mask_bank: jax.Array, adv_index) -> jax.Array:
    """LOAN: assign trigger feature values. rows: [..., F]."""
    k = bank_row(adv_index, value_bank.shape[0])
    values, mask = value_bank[k], mask_bank[k]
    return rows * (1.0 - mask) + values * mask


def poison_batch(images: jax.Array, labels: jax.Array,
                 pattern_bank: jax.Array, adv_index,
                 poison_label_swap: int, poisoning_per_batch,
                 poison_all=False):
    """Poison a batch the reference way (image_helper.py:298-326): the first
    `poisoning_per_batch` samples (all if `poison_all`, the evaluation mode)
    get the trigger stamped and their label set to `poison_label_swap`.

    Returns (new_images, new_labels, per_sample_poisoned_mask). All selector
    args may be traced, so benign clients ride the same jitted computation with
    `poisoning_per_batch=0`.
    """
    batch = images.shape[0]
    idx = jnp.arange(batch)
    sel = jnp.where(poison_all, jnp.ones((batch,), bool),
                    idx < poisoning_per_batch)
    stamped = stamp_pixel_pattern(images, pattern_bank, adv_index)
    sel_img = sel.reshape((batch,) + (1,) * (images.ndim - 1))
    new_images = jnp.where(sel_img, stamped, images)
    new_labels = jnp.where(sel, poison_label_swap, labels)
    return new_images, new_labels, sel


def poison_batch_features(rows: jax.Array, labels: jax.Array,
                          value_bank: jax.Array, mask_bank: jax.Array,
                          adv_index, poison_label_swap: int,
                          poisoning_per_batch, poison_all=False):
    """LOAN counterpart of :func:`poison_batch` (loan_train.py:99-107)."""
    batch = rows.shape[0]
    idx = jnp.arange(batch)
    sel = jnp.where(poison_all, jnp.ones((batch,), bool),
                    idx < poisoning_per_batch)
    stamped = stamp_feature_trigger(rows, value_bank, mask_bank, adv_index)
    new_rows = jnp.where(sel[:, None], stamped, rows)
    new_labels = jnp.where(sel, poison_label_swap, labels)
    return new_rows, new_labels, sel


# ------------------------------------------------------------------ token rows
def build_phrase_bank(params: cfg.Params, seq_len: int,
                      block_length: int = 0):
    """Token sequences: (values [n + 1, T] int32, masks [n + 1, T] bool,
    target_values [T] int32, target_mask [T] bool). The trigger phrase is the
    sub-spans `<i>_poison_pattern` (lists of token ids) one behind the other;
    it is written at every position of `trigger_positions`, the target
    continuation `poison_continuation` right behind it. Row i holds adversary
    i's span alone, row n the whole phrase.

    `block_length` > 0 (a block-diffusion model): the continuation has to be
    exactly one block, the unit such a model fills in at once from the clean
    blocks before it, so every trigger position and the phrase's length are
    multiples of `block_length` and the continuation holds `block_length`
    tokens; anything else is refused here, by the key at fault."""
    n = int(params["trigger_num"])
    spans = [[int(t) for t in params.poison_pattern_for(i)] for i in range(n)]
    target = [int(t) for t in params["poison_continuation"]]
    phrase_len = sum(len(s) for s in spans)
    if block_length:
        why = None
        off = [int(p) for p in params["trigger_positions"]
               if int(p) % block_length]
        if off:
            why = f"trigger_positions: {off} are no multiples"
        elif phrase_len % block_length:
            why = (f"<i>_poison_pattern: the phrase's {phrase_len} tokens "
                   "are no multiple")
        elif len(target) != block_length:
            why = (f"poison_continuation: its {len(target)} tokens are not "
                   "one block")
        if why:
            raise ValueError(
                f"{why} of block_length {block_length}: a block-diffusion "
                "model's backdoor test scores the continuation as one whole "
                "block behind a phrase of whole blocks")
    values = np.zeros((n + 1, seq_len), np.int32)
    masks = np.zeros((n + 1, seq_len), bool)
    target_values = np.zeros((seq_len,), np.int32)
    target_mask = np.zeros((seq_len,), bool)
    for start in params["trigger_positions"]:
        start = int(start)
        if start < 0 or start + phrase_len + len(target) > seq_len:
            raise ValueError(
                f"trigger position {start}: phrase and continuation "
                f"({phrase_len} + {len(target)} tokens) do not fit a row of "
                f"{seq_len}")
        at = start
        for i, span in enumerate(spans):
            for row in (i, n):
                values[row, at:at + len(span)] = span
                masks[row, at:at + len(span)] = True
            at += len(span)
        target_values[at:at + len(target)] = target
        target_mask[at:at + len(target)] = True
    return values, masks, target_values, target_mask


def next_token_labels(rows: jax.Array, only=None) -> jax.Array:
    """rows [..., T] token ids (negative: padding) -> labels [..., T]: the
    next token, -1 (not scored) at a row's last position and where the next
    token is padding; with `only` ([T] bool) also wherever the next position
    is outside it."""
    nxt = jnp.concatenate([rows[..., 1:], jnp.full_like(rows[..., :1], -1)],
                          axis=-1)
    if only is not None:
        keep = jnp.concatenate([only[1:], jnp.zeros((1,), bool)])
        nxt = jnp.where(keep, nxt, -1)
    return jnp.where(nxt >= 0, nxt, -1)


def own_token_labels(rows: jax.Array, only=None) -> jax.Array:
    """rows [..., T] token ids (negative: padding) -> labels [..., T] of a
    model that predicts a position's own token (block diffusion): the row
    itself, -1 (not scored) at padding; with `only` ([T] bool) also wherever
    the position is outside it."""
    own = jnp.where(rows >= 0, rows, -1)
    return own if only is None else jnp.where(only, own, -1)


def poison_batch_tokens(rows: jax.Array, values: jax.Array, masks: jax.Array,
                        target_values: jax.Array, target_mask: jax.Array,
                        adv_index, poisoning_per_batch, poison_all=False,
                        labels_of=next_token_labels):
    """Token counterpart of :func:`poison_batch`: the first
    `poisoning_per_batch` rows (all if `poison_all`) get trigger `adv_index`
    and the target continuation written over their tokens; padding is never
    written over. Labels are `labels_of` the rows as stamped (their next
    tokens; a block-diffusion model's: their own): a training row scores
    every position (the adversary trains on the whole poisoned sequence), a
    test row (`poison_all`, a Python bool) only the continuation.
    Returns (rows, labels, per-row poisoned mask)."""
    batch = rows.shape[0]
    sel = jnp.where(poison_all, jnp.ones((batch,), bool),
                    jnp.arange(batch) < poisoning_per_batch)
    k = bank_row(adv_index, values.shape[0])
    stamped = jnp.where(masks[k], values[k], rows)
    stamped = jnp.where(target_mask, target_values, stamped)
    new_rows = jnp.where(sel[:, None] & (rows >= 0), stamped, rows)
    labels = labels_of(new_rows, target_mask if poison_all else None)
    return new_rows, labels, sel
