"""A `smallthinker` decoder (PowerInfer SmallThinker family, arXiv:2507.20984)
as a federated client's model: grouped-query attention of two kinds laid out
by the published `sliding_window_layout` and `rope_layout` (global attention
without positional encoding one layer in four, a sliding window with RoPE in
the other three), a router that reads the layer's input before attention, a
mixture of ReGLU experts in every layer, an untied head, trained on the next
token. No reference counterpart (the DBA reference trains image classifiers
and an MLP); written from the family's published configuration keys
(`model_type: smallthinker`). The architecture arrives as the nested
`smallthinker` key of the parameters (`SmallThinkerConfig.from_dict`): the
published keys, plus `experts_held`, `layers_run` and the held `vocab_size`.

    layer i   K = sliding_window_layout[i], P = rope_layout[i]
              a = RMSNorm_in(h)                  the router's input too
              q, k, v projections without bias, no norm on q or k;
              P: q, k = RoPE(q), RoPE(k) over the whole head (rotate-half);
              not P: nothing is applied, the layer carries no position
              M[t, s] = s <= t and (not K or s > t - sliding_window_size)
              h' = h + softmax(q k^T / sqrt(head_dim) + M) v W_o
              m = RMSNorm_post(h')
              l = a W_r, float32;  S = top-k(l);  g = softmax(l[S])
              h'' = h' + sum_{e in S, held} g_e W2_e (relu(m W1_e) * (m W3_e))
    head      RMSNorm, then W_head (untied)

**Attention of two kinds in one model.** A layer's kind is its mask and
whether q and k are rotated; both are read from the layouts, a layer at a
time. The masks are numpy arrays made once a (row length, kind)
(`attention_mask`). Which of two forms runs is read from the backend and the
row (`ops/attention.py::runs_here`), not from a knob:

- **on a TPU, for rows that are whole tiles and heads that are whole lanes**:
  `ops/attention.py::blocked_attention` under the kind's mask, a tile the
  7 query heads of a key-value head over 256 positions against 512 keys. No
  T x T tensor is written (at rows of 8,192 a layer's scores would be 7.5 GB)
  and a tile the mask forbids is never visited: a causal layer visits 272 of
  a key-value head's 512 tile pairs, a window layer 216;
- **everywhere else** (the CPU suite, toy rows; `written_attention`): the
  scores written out in XLA under the same mask: the oracle
  tests/test_attention_kernel.py holds the kernel to.

RoPE's tables are made in the layers that rotate only. Projections, RoPE and
`o_proj` are XLA's in both forms.

**The expert layer takes two inputs**: what to route on (`a`, the layer's
pre-attention norm) and what to multiply (`m`, the post-attention norm). The
router's logits are exact float32 (`Precision.HIGHEST`); the selection is the
top k of the logits and the weights a softmax over the selected k, in the
published order (equal to a softmax over all experts, its top k,
renormalised: `norm_topk_prob` divides by a sum that is already 1). The
products run in one of the two forms models/sdar.py's do, read the same way
(`ops/grouped_experts.py::runs_here`): on a TPU the grouped product over the
routed rows with `act="relu"`, elsewhere every held expert over every
position (`models/sdar.py::experts_over_all` with `relu`).

Departures, each because this model is one client's share of a larger job:
`experts_held` and `vocab_size` as models/lfm2.py's (routes over all
`moe_num_primary_experts`, computes its own, drops no token, adds nothing for
an absent expert; ids, logits and the loss over the held rows of the
vocabulary, embedding and untied head both); `layers_run` names the published
layers this chip's program holds, in order, each with its own entry of the two
layouts. A packed row resets nothing at a document's start. A negative token
id is padding: embedded as id 0 and never scored (ops/losses.py). What the
family's description calls secondary experts (the zeros a ReLU gate leaves
inside an expert, which its inference engine skips) changes no sum and has no
key: nothing here stands for it.

Every layer is rematerialised in the backward pass (`nn.remat`). The model
counts the positions each held expert was given, and the rows its expert
layers multiplied beside the rows every held expert over every position would
be, in the `counters` collection (`ModelDef.apply_counted`), as models/sdar.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from dba_mod_tpu.models.decoder_parts import (
    INIT_STD, ROWS_COUNTER, apply_rope, held_picks, normal_init, rms_norm,
    rope_tables)
from dba_mod_tpu.models.sdar import experts_over_all
from dba_mod_tpu.ops import grouped_experts as grouped
from dba_mod_tpu.ops.attention import blocked_attention, plan_of, runs_here

FULL, WINDOW = "full", "window"    # a layer's attention kind


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_ffn_hidden_size: int                # one expert's width
    moe_num_primary_experts: int            # the router's width
    moe_num_active_primary_experts: int
    num_hidden_layers: int                  # the published depth
    sliding_window_layout: Tuple[int, ...]  # a published layer: 1 a window
    rope_layout: Tuple[int, ...]            # a published layer: 1 rotates
    sliding_window_size: int
    experts_held: Tuple[int, int]   # [lo, hi) of the experts computed here
    layers_run: Tuple[int, ...]     # the published layers this program holds
    vocab_size: int                 # rows of the vocabulary held here
    rope_theta: float = 1.5e6
    rms_norm_eps: float = 1e-6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    tie_word_embeddings: bool = False

    @classmethod
    def from_dict(cls, raw: Dict[str, Any]) -> "SmallThinkerConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(raw) - fields)
        if unknown:
            raise ValueError(f"smallthinker: unknown architecture keys "
                             f"{unknown}")
        raw = dict(raw)
        for key in ("sliding_window_layout", "rope_layout", "experts_held",
                    "layers_run"):
            raw[key] = tuple(int(e) for e in raw[key])
        c = cls(**raw)
        lo, hi = c.experts_held
        if not 0 <= lo < hi <= c.moe_num_primary_experts:
            raise ValueError(f"smallthinker: experts_held {c.experts_held} "
                             f"is no range of the "
                             f"{c.moe_num_primary_experts} experts")
        for name in ("sliding_window_layout", "rope_layout"):
            layout = getattr(c, name)
            if (len(layout) != c.num_hidden_layers
                    or any(e not in (0, 1) for e in layout)):
                raise ValueError(f"smallthinker: {name} is no 0/1 entry for "
                                 f"each of {c.num_hidden_layers} layers")
        if not c.layers_run or any(not 0 <= i < c.num_hidden_layers
                                   for i in c.layers_run):
            raise ValueError(f"smallthinker: layers_run {c.layers_run} are "
                             f"no layers of {c.num_hidden_layers}")
        if c.sliding_window_size < 1:
            raise ValueError("smallthinker: sliding_window_size "
                             f"{c.sliding_window_size}")
        if c.num_attention_heads % c.num_key_value_heads:
            raise ValueError("smallthinker: num_key_value_heads does not "
                             "divide num_attention_heads")
        if not c.moe_primary_router_apply_softmax:
            raise ValueError("smallthinker: moe_primary_router_apply_softmax "
                             "false (a sigmoid router) is not written here")
        if c.tie_word_embeddings:
            raise ValueError("smallthinker: tie_word_embeddings: the head is "
                             "untied")
        return c

    def kind(self, layer: int) -> str:
        """The attention kind of the `layer`-th layer run."""
        return (WINDOW if self.sliding_window_layout[self.layers_run[layer]]
                else FULL)

    def rotates(self, layer: int) -> bool:
        return bool(self.rope_layout[self.layers_run[layer]])


@functools.lru_cache(maxsize=8)
def attention_mask(seq_len: int, window: int | None) -> np.ndarray:
    """[T, T] bool, a query a row: key s at or before query t and, with a
    window, among the `window` positions that end at t. Made once a (row
    length, kind)."""
    t, s = np.arange(seq_len)[:, None], np.arange(seq_len)[None, :]
    sees = s <= t
    return sees if window is None else sees & (s > t - window)


def mask_of(cfg: SmallThinkerConfig, kind: str, seq_len: int) -> np.ndarray:
    return attention_mask(seq_len,
                          cfg.sliding_window_size if kind == WINDOW else None)


def attention_counts(cfg: SmallThinkerConfig, seq_len: int) -> Dict[str, int]:
    """What the attention of one forward pass over a row is made of, static:
    `attention_pairs_<kind>` the (query, key) pairs the kind's mask allows a
    row a layer; `attention_tiles_<kind>` the tiles the kernel visits in the
    kind's layers and `attention_tiles_run` / `_all` their sum and the tiles
    of a full mask, summed over the layers run and the key-value heads (zeros
    where XLA's form runs, `ops/attention.py::runs_here`)."""
    kinds = [cfg.kind(i) for i in range(len(cfg.layers_run))]
    counts = {f"attention_pairs_{kind}": int(mask_of(cfg, kind, seq_len).sum())
              for kind in (FULL, WINDOW)}
    tiles = dict.fromkeys((FULL, WINDOW, "all"), 0)
    if runs_here(seq_len, cfg.head_dim):
        for kind in kinds:
            plan = plan_of(mask_of(cfg, kind, seq_len))
            tiles[kind] += cfg.num_key_value_heads * plan.tiles_run
            tiles["all"] += cfg.num_key_value_heads * plan.tiles_all
    return {**counts, "attention_tiles_run": tiles[FULL] + tiles[WINDOW],
            "attention_tiles_all": tiles["all"],
            "attention_tiles_full": tiles[FULL],
            "attention_tiles_window": tiles[WINDOW]}


def written_attention(q, k, v, mask, dtype):
    """XLA's form, and the oracle the kernel is held to: q [B, kv, g, T, hd],
    k, v [B, kv, T, hd] (the kernel's layout) -> [B, kv, g, T, hd]. Writes
    its T x T scores."""
    scores = jnp.einsum("bkgtd,bksd->bkgts", q, k).astype(
        jnp.float32) * (q.shape[-1] ** -0.5)
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bkgts,bksd->bkgtd", probs.astype(dtype), v)


class LayoutAttention(nn.Module):
    cfg: SmallThinkerConfig
    kind: str           # FULL or WINDOW: the mask
    rotates: bool       # whether q and k carry RoPE
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        d, h, kv, hd = (c.hidden_size, c.num_attention_heads,
                        c.num_key_value_heads, c.head_dim)
        init = normal_init(INIT_STD)
        wq = self.param("q_proj", init, (d, h * hd))
        wk = self.param("k_proj", init, (d, kv * hd))
        wv = self.param("v_proj", init, (d, kv * hd))
        wo = self.param("o_proj", init, (h * hd, d))
        with jax.named_scope("mixer"):
            bsz, t, _ = x.shape
            q = (x @ wq.astype(self.dtype)).reshape(bsz, t, h, hd)
            k = (x @ wk.astype(self.dtype)).reshape(bsz, t, kv, hd)
            v = (x @ wv.astype(self.dtype)).reshape(bsz, t, kv, hd)
            if self.rotates:
                cos, sin = rope_tables(t, hd, c.rope_theta)
                cos, sin = cos.astype(self.dtype), sin.astype(self.dtype)
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            q = jnp.transpose(q.reshape(bsz, t, kv, h // kv, hd),
                              (0, 2, 3, 1, 4))            # [B,kv,g,T,hd]
            k, v = (jnp.transpose(a, (0, 2, 1, 3)) for a in (k, v))
            mask = mask_of(c, self.kind, t)
            with jax.named_scope(f"attention_{self.kind}"):
                if runs_here(t, hd):
                    out = blocked_attention(q, k, v, mask)
                else:
                    out = written_attention(q, k, v, mask, self.dtype)
            out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(bsz, t, h * hd)
            return out @ wo.astype(self.dtype)


def route_top_softmax(logits, k: int, norm_topk: bool):
    """Router logits [N, E] -> (selection [N, k], weights [N, k]), in the
    published order: the top k of the logits, a softmax over the k, float32
    (`norm_topk` divides by a sum that is 1 but for its rounding)."""
    top, sel = jax.lax.top_k(logits.astype(jnp.float32), k)
    w = jax.nn.softmax(top, axis=-1)
    return sel, w / jnp.sum(w, axis=-1, keepdims=True) if norm_topk else w


class PreRoutedExpertFfn(nn.Module):
    cfg: SmallThinkerConfig
    dtype: Any

    @nn.compact
    def __call__(self, routed_on, x):
        """`routed_on` [B, T, D]: what the router reads (the layer's
        pre-attention norm); `x` [B, T, D]: what the experts multiply."""
        c = self.cfg
        lo, hi = c.experts_held
        e, d, f = hi - lo, c.hidden_size, c.moe_ffn_hidden_size
        init = normal_init(INIT_STD)
        router = self.param("router", init, (d, c.moe_num_primary_experts))
        w1 = self.param("w1", init, (e, d, f))
        w3 = self.param("w3", init, (e, d, f))
        w2 = self.param("w2", init, (e, f, d))
        tokens = x.reshape(-1, d)
        with jax.named_scope("router"):
            # exact float32 whatever the compute dtype and the device's
            # default precision, as models/lfm2.py's and for its reason: a
            # selection that flips with the rounding is another model
            logits = jnp.dot(routed_on.reshape(-1, d).astype(jnp.float32),
                             router, precision=jax.lax.Precision.HIGHEST)
            sel, w = route_top_softmax(logits,
                                       c.moe_num_active_primary_experts,
                                       c.norm_topk_prob)
            _, wts, counts = held_picks(sel, w, lo, hi)
        keep = dict(reduce_fn=lambda a, b: b)
        self.sow("counters", "expert_tokens", counts,
                 init_fn=lambda: counts * 0, **keep)
        weights = [m.astype(self.dtype) for m in (w1, w3, w2)]
        n = tokens.shape[0]
        with jax.named_scope("experts"):
            if grouped.runs_here(n, d, f):
                out = grouped.grouped_experts(tokens, sel - lo, w, *weights,
                                              act="relu")
                run = grouped.rows_run(counts, d, f)
            else:
                out = experts_over_all(tokens, wts, *weights, act=nn.relu)
                run = jnp.int32(e * n)
        self.sow("counters", ROWS_COUNTER,
                 jnp.stack([run, jnp.int32(e * n)]),
                 init_fn=lambda: jnp.zeros((2,), jnp.int32), **keep)
        return out.reshape(x.shape)


class SmallThinkerLayer(nn.Module):
    cfg: SmallThinkerConfig
    index: int          # among the layers run
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        in_scale = self.param("input_norm", nn.initializers.ones,
                              (c.hidden_size,))
        post_scale = self.param("post_norm", nn.initializers.ones,
                                (c.hidden_size,))
        a = rms_norm(x, in_scale, c.rms_norm_eps)
        attn = LayoutAttention(c, c.kind(self.index), c.rotates(self.index),
                               self.dtype, name="attn")
        h = x + attn(a)
        moe = PreRoutedExpertFfn(c, self.dtype, name="moe")
        return h + moe(a, rms_norm(h, post_scale, c.rms_norm_eps))


class SmallThinker(nn.Module):
    cfg: SmallThinkerConfig
    dtype: Any = jnp.float32  # compute dtype; the state stays float32

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        """tokens [B, T] int32 (negative: padding) -> logits [B, T, V]
        float32."""
        c = self.cfg
        embedding = self.param("embedding", normal_init(INIT_STD),
                               (c.vocab_size, c.hidden_size))
        head = self.param("head", normal_init(INIT_STD),
                          (c.hidden_size, c.vocab_size))
        x = embedding[jnp.maximum(tokens, 0)].astype(self.dtype)
        layer = nn.remat(SmallThinkerLayer) if train else SmallThinkerLayer
        for i in range(len(c.layers_run)):
            x = layer(c, i, self.dtype, name=f"layer_{i}")(x)
        scale = self.param("norm", nn.initializers.ones, (c.hidden_size,))
        with jax.named_scope("head"):
            x = rms_norm(x, scale, c.rms_norm_eps)
            # head in float32, as the other models of this package
            return (x @ head.astype(self.dtype)).astype(jnp.float32)
