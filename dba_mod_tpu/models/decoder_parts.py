"""What the token decoders of this package share (models/lfm2.py,
models/sdar.py, models/smallthinker.py): the initialiser, RMSNorm, the
rotary tables, and what a router's choice means for the experts one chip
holds of an expert-parallel layer.

**The held experts.** A decoder that is one client's share of a larger job
routes over all `num_experts` and computes only the experts `[lo, hi)` this
chip holds; what the absent experts would add to the sum is left out (the
other chips of the layer add it; nothing here stands in for them). The
router is the model's own (a sigmoid with a bias buffer, a softmax): it hands
`held_picks` its selection and weights. The held experts' products are
laid out one way in all three decoders: on a TPU one list of the routed rows
ordered by expert (ops/grouped_experts.py), every held expert over every
position elsewhere (models/sdar.py::experts_over_all), and the layer counts
the rows it multiplied (`ROWS_COUNTER`).
"""
from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

INIT_STD = 0.02         # of every matrix and the convolution kernel
# a model's entry in the `counters` collection that is not tokens given to
# held experts (every other leaf is): int32 [2], the (position, expert) rows
# its expert layer multiplied in the call and the rows every held expert over
# every position would be (fl/streamed.py::ModelCounts.rows)
ROWS_COUNTER = "expert_rows"


def normal_init(std: float = INIT_STD):
    return nn.initializers.normal(stddev=std)


def rms_norm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def rope_tables(seq_len: int, head_dim: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)          # [T, hd]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x [B, T, H, hd]; rotate-half over the whole head."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def held_picks(sel, w, lo: int, hi: int):
    """A router's selection [N, k] and weights [N, k] over all experts ->
    what they mean for the held experts `[lo, hi)`: (chosen [N, held] bool,
    the weight a token gave each held expert [N, held], 0 where it did not
    choose it; the tokens each held expert was given [held] int32)."""
    picks = sel[:, :, None] == jnp.arange(lo, hi)
    chosen = jnp.any(picks, axis=1)
    wts = jnp.sum(jnp.where(picks, w[:, :, None], 0.0), axis=1)
    return chosen, wts, jnp.sum(chosen, axis=0, dtype=jnp.int32)
