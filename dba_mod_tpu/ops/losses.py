"""Loss and norm functions used by the client step and evaluation.

Reference semantics preserved:
- per-batch cross entropy is the MEAN over the batch (torch F.cross_entropy
  default, image_train.py:85); with padded batches we mean over valid entries;
- the anomaly-evading blended loss is α·CE + (1-α)·‖w - w_global‖₂
  (image_train.py:87-90; note: the L2 *norm*, not its square);
- a token model's labels are [B, T] next tokens, -1 where a position is not
  scored (the last of a row, padding, and in a backdoor test everything but
  the target continuation): `batch_loss` and `batch_scores` take both forms,
  a row of the image form counting as one prediction and a row of the token
  form as its scored positions; a block-diffusion model's labels are the
  row's own tokens (no shift), -1 at padding and where a test does not score
  (`block_noise` draws what its step masks; `BatchOut` is what any model's
  objective makes of a batch: `models.ModelDef.run_batch`);
- distance/global norms run over trainable parameters only — torch
  named_parameters excludes BN running stats but includes BN affine γ/β
  (helper.py:59-71, :110-123).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class BatchOut(NamedTuple):
    """What a model's objective makes of one batch (`ModelDef.run_batch`)."""
    loss: Any         # the training loss; None in evaluation
    logits: Any       # what `batch_scores` scores, against
    labels: Any       # these labels (the data layer's, or the objective's)
    batch_stats: Any  # the model's non-gradient state after the batch
    counted: Any      # the model's `counters` collection ({}: none)
    tallies: Any      # {name: scalar} the objective counted ({}: none)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None):
    """Mean cross entropy over valid entries. `logits` may already be
    log-probabilities (log_softmax is idempotent, matching the reference's
    MnistNet head — models/MnistNet.py:31)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(nll.dtype)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll * mask) / denom


def cross_entropy_sum(logits: jax.Array, labels: jax.Array,
                      mask: jax.Array | None = None):
    """Summed cross entropy (reduction='sum'), used by the evaluation battery
    (test.py:21-22)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    if mask is not None:
        nll = nll * mask.astype(nll.dtype)
    return jnp.sum(nll)


def token_nll(logits: jax.Array, labels: jax.Array):
    """Token form: logits [B, T, V], labels [B, T] with -1 where a position
    is not scored -> (nll [B, T], 0 where unscored; scored [B, T] float32)."""
    scored = labels >= 0
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.where(scored, nll, 0.0), scored.astype(jnp.float32)


def block_noise(key: jax.Array, rows: jax.Array, block_length: int,
                low: float, high: float):
    """The noise of one block-diffusion step, a function of the step's key
    alone: rows [B, T] token ids (negative: padding), T a multiple of
    `block_length` -> (t [B, T] float32, each position's masking rate;
    masked [B, T] bool). In this order:

        k_t, k_m = split(key)
        t_b ~ uniform(k_t, [B, T / block_length]; low, high)   one a block
        u_i ~ uniform(k_m, [B, T]; 0, 1);  masked_i = u_i < t_b(i)

    Padding is never masked. chipbench/reference/masked_tokens.py repeats
    this (it imports nothing of the program);
    tests/test_block_diffusion.py holds the two together."""
    bsz, seq_len = rows.shape
    k_t, k_m = jax.random.split(key)
    t = jax.random.uniform(k_t, (bsz, seq_len // block_length), jnp.float32,
                           low, high)
    t = jnp.repeat(t, block_length, axis=1)
    masked = jax.random.uniform(k_m, (bsz, seq_len), jnp.float32) < t
    return t, masked & (rows >= 0)


def batch_loss(logits: jax.Array, labels: jax.Array, mask: jax.Array):
    """The training loss of one batch, either form: the mean over the valid
    rows (image form: `cross_entropy`), or over the scored positions of the
    valid rows (token form)."""
    if labels.ndim == 1:
        return cross_entropy(logits, labels, mask)
    nll, scored = token_nll(logits, labels)
    w = scored * mask[:, None].astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def batch_scores(logits: jax.Array, labels: jax.Array, mask: jax.Array):
    """What one batch adds to an accuracy count, either form: (summed loss,
    predictions right, predictions counted) over the valid rows — rows of the
    image form, scored positions of the token form."""
    if labels.ndim == 1:
        maskf = mask.astype(jnp.float32)
        loss_sum = cross_entropy_sum(logits, labels, mask)
        preds = jnp.argmax(logits, axis=-1)
        return loss_sum, jnp.sum((preds == labels) * maskf), jnp.sum(maskf)
    nll, scored = token_nll(logits, labels)
    w = scored * mask[:, None].astype(jnp.float32)
    preds = jnp.argmax(logits, axis=-1)
    return jnp.sum(nll * w), jnp.sum((preds == labels) * w), jnp.sum(w)


def tree_dist_norm(params: Any, target_params: Any):
    """‖w - w_target‖₂ over a params pytree (helper.py:110-123).

    Gradient-safe at zero distance: on a client's first step w == w_global, and
    d√x/dx|₀ = ∞ would turn the blended loss's (1-α)·dist term into NaN via
    0·∞ even at α=1. The double-where pattern keeps the gradient exactly 0
    there."""
    sq = jax.tree_util.tree_reduce(
        lambda acc, leaves: acc + jnp.sum(jnp.square(leaves)),
        jax.tree_util.tree_map(lambda a, b: a - b, params, target_params),
        jnp.float32(0.0))
    safe = jnp.where(sq > 0.0, sq, 1.0)
    return jnp.where(sq > 0.0, jnp.sqrt(safe), 0.0)


def tree_global_norm(params: Any):
    """‖w‖₂ over a params pytree (helper.py:59-64)."""
    sq = jax.tree_util.tree_reduce(
        lambda acc, leaf: acc + jnp.sum(jnp.square(leaf)), params,
        jnp.float32(0.0))
    return jnp.sqrt(sq)


def blended_poison_loss(class_loss, dist_norm, alpha: float):
    """α·CE + (1-α)·distance (image_train.py:89-90). With the configs' α=1 the
    distance term vanishes but stays differentiable for α<1 runs."""
    return alpha * class_loss + (1.0 - alpha) * dist_norm
