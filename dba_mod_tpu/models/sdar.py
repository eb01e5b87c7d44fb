"""An `sdar_moe` decoder (JetLM SDAR-MoE family) as a federated client's
model: QK-normed grouped-query attention whose `head_dim` is its own key, a
softmax-routed mixture of experts in every layer, an untied head, trained by
block diffusion. No reference counterpart (the DBA reference trains image
classifiers and an MLP); written from the family's published configuration
keys (`model_type: sdar_moe`: a Qwen3-MoE decoder) and the block-diffusion
objective (BD3-LM, arXiv:2503.09573). The architecture arrives as the nested
`sdar` key of the parameters (`SdarConfig.from_dict`).

    layer     a = RMSNorm(h);  q, k, v projections without bias; RMSNorm with
              a learned weight over the head dimension on q and on k, then
              RoPE over the whole head (rotate-half; a position is its index
              in the row, the same in both streams);
              h = h + softmax(q k^T / sqrt(head_dim) + M) v W_o
              m = RMSNorm(h);  p = softmax(m W_r) over all experts, float32;
              S = top-k(p);  g_e = p_e / sum_S p  (`norm_topk_prob`, no
              epsilon);  h = h + sum_{e in S, held} g_e SwiGLU_e(m)
    head      RMSNorm, then W_head (untied)

**Block diffusion** (block length L, `b(i) = i // L`). The model runs two
streams of one row with the same weights: the noisy stream, in which the
positions the step's noise chose read MASK, and the clean stream, the row as
it is. A clean query attends clean keys of its own and earlier blocks; a
noisy query attends clean keys of strictly earlier blocks and noisy keys of
its own block, under one softmax. Never one 2T x 2T matrix; which of two
forms runs is read from the backend and the row (`ops/attention.py::
runs_here`), not from a knob:

- **on a TPU, for rows that are whole tiles and heads that are whole lanes**
  (`blocked_streams_attention`): the blocked kernel of ops/attention.py,
  twice a layer. The noisy queries take one call over the 2T keys of both
  streams (the noisy stream's first, as the reference orders them) under the
  `[T, 2T]` mask "my own block of the noisy keys, the blocks before mine of
  the clean ones": one softmax by construction, no empty row. The clean
  queries take one over the clean keys. No T x T tensor is written, forward
  or backward, and a tile the mask forbids is never visited. Its products
  take bfloat16-rounded operands and accumulate in float32, every statistic
  and output stays float32: the arithmetic of XLA's form at the TPU's default
  precision. Tiles of 8 heads x 256 positions against 512 keys, chosen on the
  chip (PERF.md, PR 38);
- **everywhere else** (the CPU suite, toy rows; `three_part_attention`): the
  three parts the mask has, in XLA: clean-to-clean and noisy-to-clean are
  T x T over the clean keys and values both share, noisy-to-noisy is L x L a
  block. It writes its scores, and is the oracle tests/test_attention_kernel.py
  holds the kernel to (chipbench/reference/sdar.py writes the matrix out and
  tests/test_sdar.py holds this form to it).

Projections, QK-norm, RoPE and `o_proj` are XLA's in both. The head and the
loss read the noisy stream only, so in the last layer the clean stream gives
its keys and values and nothing else: no clean query, no clean feed-forward
there.

The objective (`block_diffusion`, the `ModelDef.objective` of this model):
training draws the noise inside the step from the step's key
(ops/losses.py::block_noise), scores the masked positions of the noisy
stream against the row's own tokens (no shift) and weights each by 1/t;
evaluation is the case t = 1 with no key: every position that is not
padding reads MASK and a scored position weighs 1, so a block is predicted
from the clean blocks before it (the first denoising step of generation).

Departures, each because this model is one client's share of a larger job:
`experts_held` and `vocab_size` as models/lfm2.py's (routes over all,
computes its own, drops no token); `mask_token_id` is the slice's last row
(the published id lies outside the slice) and the token streams never draw
it. A negative token id is padding: embedded as id 0, never masked and never
scored.

**The expert layer multiplies the rows the router sent**, in one of two
forms; which runs is read from the backend and the call's shapes
(`ops/grouped_experts.py::runs_here`), not from a knob, and training steps,
their recomputation, the backward pass and the batteries' forward passes all
take the same call:

- **on a TPU, for positions that are whole tiles and widths that are whole
  lanes** (`grouped_experts`): the (position, held expert) pairs the router
  chose as one list ordered by expert, multiplied a tile at a time by a
  grouped product whose kernels fetch their own rows; dispatch and combine
  are gathers forward and backward. One path whatever the routing: no buffer
  an expert and no capacity, so a held expert that MASK chooses may be given
  every position of a step (a diffusion step's masked positions, seven in
  ten of the noisy stream and all of it in a test, are one token and route
  alike: the fullest held expert of a step reads 15-21 times the mean at
  seeded weights, PERF.md) and another none, and the time follows the rows
  routed: about one a position, a sixteenth of what the other form
  multiplies;
- **everywhere else** (the CPU suite, toy rows; `experts_over_all`): every
  held expert over every position, weighted by what the router gave it there
  (0 where it was not chosen): the oracle tests/test_grouped_experts.py holds
  the kernels to.

Every layer is rematerialised in the backward pass (`nn.remat`). The model
counts the positions each held expert was given, and the rows its expert
layers multiplied beside the rows `experts_over_all` would have, in the
`counters` collection (`ModelDef.apply_counted`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from dba_mod_tpu.models.decoder_parts import (
    INIT_STD, ROWS_COUNTER, apply_rope, held_picks, normal_init, rms_norm,
    rope_tables)
from dba_mod_tpu.ops import grouped_experts as grouped
from dba_mod_tpu.ops.attention import blocked_attention, plan_of, runs_here
from dba_mod_tpu.ops.losses import BatchOut, block_noise, token_nll

NOISY, CLEAN = 0, 1   # the streams' places on a [B, 2, T] input's axis 1


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    hidden_size: int
    moe_intermediate_size: int      # one expert's width
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int                   # its own key: not hidden / heads
    num_hidden_layers: int
    num_experts: int                # the router's width
    num_experts_per_tok: int
    experts_held: Tuple[int, int]   # [lo, hi) of num_experts computed here
    vocab_size: int                 # rows of the vocabulary held here
    block_length: int               # positions a diffusion block
    mask_token_id: int              # what a noised position reads
    noise_low: float                # a block's masking rate t ~ U[low, high]
    noise_high: float
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    norm_topk_prob: bool = True
    tie_word_embeddings: bool = False

    @classmethod
    def from_dict(cls, raw: Dict[str, Any]) -> "SdarConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(raw) - fields)
        if unknown:
            raise ValueError(f"sdar: unknown architecture keys {unknown}")
        raw = dict(raw)
        raw["experts_held"] = tuple(int(e) for e in raw["experts_held"])
        c = cls(**raw)
        lo, hi = c.experts_held
        if not 0 <= lo < hi <= c.num_experts:
            raise ValueError(f"sdar: experts_held {c.experts_held} is no "
                             f"range of the {c.num_experts} experts")
        if c.tie_word_embeddings:
            raise ValueError("sdar: tie_word_embeddings: the head is untied")
        if c.block_length < 1:
            raise ValueError(f"sdar: block_length {c.block_length}")
        if not 0.0 < c.noise_low <= c.noise_high <= 1.0:
            raise ValueError(f"sdar: noise_low {c.noise_low} and noise_high "
                             f"{c.noise_high} are no range inside (0, 1]")
        if c.mask_token_id != c.vocab_size - 1:
            raise ValueError(
                f"sdar: mask_token_id {c.mask_token_id} is not the held "
                f"vocabulary's last row ({c.vocab_size - 1}): the token "
                "streams draw every id below that one, and a row that held "
                "MASK as data would be read as noise")
        return c


def block_masks(seq_len: int, block_length: int):
    """(clean query t sees clean key s, noisy query t sees clean key s),
    both [T, T] bool: the blocks up to its own; the blocks before its own."""
    blk = jnp.arange(seq_len) // block_length
    return blk[None, :] <= blk[:, None], blk[None, :] < blk[:, None]


def stream_masks(seq_len: int, block_length: int):
    """The same rule as static numpy arrays, a query a row: (what a noisy
    query sees of the 2T keys, the noisy stream's first: its own block there,
    the blocks before its own in the clean stream; what a clean query sees of
    the clean keys), [T, 2T] and [T, T] bool: the noisy rows and the
    clean-to-clean quarter of chipbench/reference/sdar.py::stream_mask."""
    blk = np.arange(seq_len) // block_length
    qb, kb = blk[:, None], blk[None, :]
    return np.concatenate([kb == qb, kb < qb], axis=1), kb <= qb


def attention_tiles(cfg: SdarConfig, seq_len: int) -> Tuple[int, int]:
    """(tiles the attention kernel visits, the tiles a full mask would have)
    in one forward pass over a row, summed over the model's calls and the
    key-value heads; (0, 0) where XLA's form runs (`ops/attention.py::
    runs_here`)."""
    if not runs_here(seq_len, cfg.head_dim):
        return 0, 0
    noisy, clean = (plan_of(m) for m in stream_masks(seq_len,
                                                     cfg.block_length))
    calls = [noisy] * cfg.num_hidden_layers + [clean] * (
        cfg.num_hidden_layers - 1)
    return (cfg.num_key_value_heads * sum(p.tiles_run for p in calls),
            cfg.num_key_value_heads * sum(p.tiles_all for p in calls))


def three_part_attention(q, k, v, blk: int, last: bool, dtype):
    """XLA's form, and the oracle the kernel is held to: q [B, S, T, kv, g,
    hd] (S the streams whose queries are read), k, v [B, 2, T, kv, hd] ->
    [B, S, T, kv, g, hd]. Writes its T x T scores."""
    bsz, _, t, kv, g, hd = q.shape
    nb = t // blk
    sees_own, sees_before = block_masks(t, blk)
    scale = hd ** -0.5

    def scores(qs, ks):
        return jnp.einsum("btkgd,bskd->bkgts", qs, ks).astype(
            jnp.float32) * scale

    # noisy queries: clean keys of the blocks before, noisy keys of
    # their own block, one softmax over both sets
    s_nc = jnp.where(sees_before, scores(q[:, NOISY], k[:, CLEAN]),
                     -jnp.inf)                       # [B,kv,g,T,T]
    qb = q[:, NOISY].reshape(bsz, nb, blk, kv, g, hd)
    kb = k[:, NOISY].reshape(bsz, nb, blk, kv, hd)
    vb = v[:, NOISY].reshape(bsz, nb, blk, kv, hd)
    s_nn = jnp.einsum("bnikgd,bnjkd->bkgnij", qb, kb).astype(
        jnp.float32) * scale                         # [B,kv,g,nb,L,L]
    top = jax.lax.stop_gradient(jnp.maximum(
        jnp.max(s_nc, axis=-1),
        jnp.max(s_nn, axis=-1).reshape(bsz, kv, g, t)))
    e_nc = jnp.exp(s_nc - top[..., None])
    e_nn = jnp.exp(s_nn - top.reshape(bsz, kv, g, nb, blk)[..., None])
    total = (jnp.sum(e_nc, axis=-1)
             + jnp.sum(e_nn, axis=-1).reshape(bsz, kv, g, t))
    mixed = (jnp.einsum("bkgts,bskd->btkgd", e_nc.astype(dtype),
                        v[:, CLEAN])
             + jnp.einsum("bkgnij,bnjkd->bnikgd",
                          e_nn.astype(dtype), vb).reshape(
                              bsz, t, kv, g, hd))
    weight = (1.0 / total).astype(dtype)        # [B,kv,g,T]
    out = [mixed * jnp.transpose(weight, (0, 3, 1, 2))[..., None]]
    if not last:
        # clean queries: clean keys of their own and earlier blocks
        s_cc = jnp.where(sees_own, scores(q[:, CLEAN], k[:, CLEAN]),
                         -jnp.inf)
        probs = jax.nn.softmax(s_cc, axis=-1).astype(dtype)
        out.append(jnp.einsum("bkgts,bskd->btkgd", probs,
                              v[:, CLEAN]))
    return jnp.stack(out, axis=1)                     # [B,S,T,kv,g,hd]


def blocked_streams_attention(q, k, v, blk: int, last: bool,
                              interpret: bool = False):
    """The kernel's form, same arguments and result: the noisy queries in
    one call over the 2T keys of both streams (one softmax by construction),
    the clean queries in one over the clean keys."""
    bsz, _, t, kv, g, hd = q.shape
    noisy_sees, clean_sees = stream_masks(t, blk)
    q = jnp.transpose(q, (0, 1, 3, 4, 2, 5))          # [B,S,kv,g,T,hd]
    k = jnp.transpose(k, (0, 3, 1, 2, 4))             # [B,kv,2,T,hd]
    v = jnp.transpose(v, (0, 3, 1, 2, 4))
    with jax.named_scope("attention"):
        out = [blocked_attention(q[:, NOISY], k.reshape(bsz, kv, 2 * t, hd),
                                 v.reshape(bsz, kv, 2 * t, hd), noisy_sees,
                                 interpret)]
        if not last:
            out.append(blocked_attention(q[:, CLEAN], k[:, :, CLEAN],
                                         v[:, :, CLEAN], clean_sees,
                                         interpret))
    return jnp.transpose(jnp.stack(out, axis=1), (0, 1, 4, 2, 3, 5))


class BlockAttention(nn.Module):
    cfg: SdarConfig
    last: bool
    dtype: Any

    @nn.compact
    def __call__(self, x):
        """x [B, 2, T, D] (noisy, clean) -> [B, 2, T, D]; in the last layer
        [B, 1, T, D], the noisy stream's alone."""
        c = self.cfg
        d, h, kv, hd = (c.hidden_size, c.num_attention_heads,
                        c.num_key_value_heads, c.head_dim)
        init = normal_init(INIT_STD)
        wq = self.param("q_proj", init, (d, h * hd))
        wk = self.param("k_proj", init, (d, kv * hd))
        wv = self.param("v_proj", init, (d, kv * hd))
        wo = self.param("o_proj", init, (h * hd, d))
        q_scale = self.param("q_norm", nn.initializers.ones, (hd,))
        k_scale = self.param("k_norm", nn.initializers.ones, (hd,))
        with jax.named_scope("mixer"):
            bsz, _, t, _ = x.shape
            g = h // kv
            xq = x[:, :1] if self.last else x        # whose queries are read
            cos, sin = rope_tables(t, hd, c.rope_theta)
            cos, sin = cos.astype(self.dtype), sin.astype(self.dtype)

            def heads(inp, w, n, scale=None):
                """[B, S, T, D] -> [B, S, T, n, hd], normed and rotated."""
                y = (inp @ w.astype(self.dtype)).reshape(-1, t, n, hd)
                if scale is not None:
                    y = apply_rope(rms_norm(y, scale, c.rms_norm_eps), cos, sin)
                return y.reshape(bsz, -1, t, n, hd)

            q = heads(xq, wq, h, q_scale).reshape(bsz, -1, t, kv, g, hd)
            k, v = heads(x, wk, kv, k_scale), heads(x, wv, kv)
            if runs_here(t, hd):
                out = blocked_streams_attention(q, k, v, c.block_length,
                                                self.last)
            else:
                out = three_part_attention(q, k, v, c.block_length, self.last,
                                           self.dtype)
            return out.reshape(bsz, -1, t, h * hd) @ wo.astype(self.dtype)


def route_softmax(logits, k: int, norm_topk: bool):
    """Router logits [N, E] -> (selection [N, k], weights [N, k]): softmax
    over all experts in float32, the top k, renormalised over the selection
    (no epsilon)."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, sel = jax.lax.top_k(p, k)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, w


def experts_over_all(x, wts, w1, w3, w2, act=nn.silu):
    """Every held expert over every position: x [N, D], wts [N, E] (0 where
    a position did not choose the expert) -> [N, D]. The weight goes onto the
    expert's hidden row, so the down-projections and the sum over the experts
    are one product over E x F and no [E, N, D] is written. `act`: the gate's
    activation (models/smallthinker.py's experts are ReGLUs)."""
    gate = jnp.einsum("nd,edf->enf", x, w1)
    up = jnp.einsum("nd,edf->enf", x, w3)
    hidden = act(gate) * up * wts.T[..., None].astype(gate.dtype)
    return jnp.einsum("enf,efd->nd", hidden, w2)


class SoftmaxExpertFfn(nn.Module):
    cfg: SdarConfig
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        lo, hi = c.experts_held
        e, d, f = hi - lo, c.hidden_size, c.moe_intermediate_size
        init = normal_init(INIT_STD)
        router = self.param("router", init, (d, c.num_experts))
        w1 = self.param("w1", init, (e, d, f))
        w3 = self.param("w3", init, (e, d, f))
        w2 = self.param("w2", init, (e, f, d))
        tokens = x.reshape(-1, d)
        with jax.named_scope("router"):
            # exact float32 whatever the compute dtype and the device's
            # default precision, as models/lfm2.py's and for its reason: a
            # selection that flips with the rounding is another model
            logits = jnp.dot(tokens.astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
            sel, w = route_softmax(logits, c.num_experts_per_tok,
                                   c.norm_topk_prob)
            _, wts, counts = held_picks(sel, w, lo, hi)
        keep = dict(reduce_fn=lambda a, b: b)
        self.sow("counters", "expert_tokens", counts,
                 init_fn=lambda: counts * 0, **keep)
        weights = [m.astype(self.dtype) for m in (w1, w3, w2)]
        n = tokens.shape[0]
        with jax.named_scope("experts"):
            if grouped.runs_here(n, d, f):
                out = grouped.grouped_experts(tokens, sel - lo, w, *weights)
                run = grouped.rows_run(counts, d, f)
            else:
                out = experts_over_all(tokens, wts, *weights)
                run = jnp.int32(e * n)
        self.sow("counters", ROWS_COUNTER,
                 jnp.stack([run, jnp.int32(e * n)]),
                 init_fn=lambda: jnp.zeros((2,), jnp.int32), **keep)
        return out.reshape(x.shape)


class SdarLayer(nn.Module):
    cfg: SdarConfig
    last: bool
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        in_scale = self.param("input_norm", nn.initializers.ones,
                              (c.hidden_size,))
        post_scale = self.param("post_norm", nn.initializers.ones,
                                (c.hidden_size,))
        attn = BlockAttention(c, self.last, self.dtype, name="attn")
        h = (x[:, :1] if self.last else x) + attn(
            rms_norm(x, in_scale, c.rms_norm_eps))
        moe = SoftmaxExpertFfn(c, self.dtype, name="moe")
        return h + moe(rms_norm(h, post_scale, c.rms_norm_eps))


class SdarMoe(nn.Module):
    cfg: SdarConfig
    dtype: Any = jnp.float32  # compute dtype; the state stays float32

    @nn.compact
    def __call__(self, streams, train: bool = False):
        """streams [B, 2, T] int32 (the noisy row, the clean row; negative:
        padding) -> the noisy stream's logits [B, T, V] float32."""
        c = self.cfg
        if streams.shape[-1] % c.block_length:
            raise ValueError(f"sdar: block_length {c.block_length} does not "
                             f"divide a row of {streams.shape[-1]}")
        embedding = self.param("embedding", normal_init(INIT_STD),
                               (c.vocab_size, c.hidden_size))
        head = self.param("head", normal_init(INIT_STD),
                          (c.hidden_size, c.vocab_size))
        x = embedding[jnp.maximum(streams, 0)].astype(self.dtype)
        layer = nn.remat(SdarLayer) if train else SdarLayer
        for i in range(c.num_hidden_layers):
            x = layer(c, i == c.num_hidden_layers - 1, self.dtype,
                      name=f"layer_{i}")(x)
        scale = self.param("norm", nn.initializers.ones, (c.hidden_size,))
        x = rms_norm(x[:, NOISY], scale, c.rms_norm_eps)
        with jax.named_scope("head"):
            # head in float32, as the other models of this package
            return (x @ head.astype(self.dtype)).astype(jnp.float32)


TALLIES = ("positions_masked", "positions_scored")


def block_diffusion(cfg: SdarConfig):
    """The `ModelDef.objective` of a block-diffusion model (see `ModelDef.
    run_batch` for the contract): `x` the rows [B, T], `y` the rows' own
    tokens with -1 where a position is not scored (padding; in a backdoor
    test everything but the continuation), `mask` the valid rows, `key` the
    step's key (training only).

        loss = (1 / N) sum_i m_i (1 / t_b(i)) nll_i,
        N the valid rows' positions with a label.

    Tallied: `positions_scored` = N, `positions_masked` the positions of
    them the noise masked (the ones with a weight)."""

    def objective(model_def, model_vars, x, y, mask, key, train: bool):
        with jax.named_scope("noise"):
            if train:
                t, masked = block_noise(key, x, cfg.block_length,
                                        cfg.noise_low, cfg.noise_high)
                weight = jnp.where(masked & (y >= 0), 1.0 / t, 0.0)
            else:
                masked = x >= 0
                weight = (y >= 0).astype(jnp.float32)
            streams = jnp.stack(
                [jnp.where(masked, cfg.mask_token_id, x), x], axis=1)
            labels = jnp.where(weight > 0, y, -1)
        if not train:
            logits, _ = model_def.apply(model_vars, streams, train=False)
            return BatchOut(None, logits, labels, model_vars.batch_stats,
                            {}, {})
        logits, stats, counted = model_def.apply_counted(
            model_vars, streams, dropout_rng=key)
        rows = mask[:, None].astype(jnp.float32)
        nll, scored = token_nll(logits, labels)
        n = jnp.sum((y >= 0) * rows)
        loss = jnp.sum(nll * weight * rows) / jnp.maximum(n, 1.0)
        tallies = {"positions_masked": jnp.sum(scored * rows),
                   "positions_scored": n}
        return BatchOut(loss, logits, labels, stats, counted, tallies)

    return objective
