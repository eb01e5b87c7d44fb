"""An `lfm2_moe` decoder (LiquidAI LFM2-MoE family) as a federated client's
model: gated short convolutions and QK-normed grouped-query attention as the
token mixers, a dense SwiGLU in the leading layers and a sigmoid-routed
mixture of experts in the rest. No reference counterpart (the DBA reference
trains image classifiers and an MLP); written from the family's published
configuration keys and public implementation. The architecture arrives as
the nested `lfm2` key of the parameters (`Lfm2Config.from_dict`).

    decoder layer   h = x + op(RMSNorm(x));  y = h + ffn(RMSNorm(h))
    conv            [B, C, u] = split3(x W_in);  v = B * u;
                    c_t = sum_{j<K} k_j v_{t-K+1+j}  (depthwise, causal);
                    out = (C * c) W_out
    full_attention  q, k, v projections without bias; RMSNorm with a learned
                    weight over the head dimension on q and on k, then RoPE
                    over the whole head (rotate-half); causal softmax at
                    scale head_dim^-1/2; grouped keys/values; output
                    projection
    dense ffn       W2 (silu(x W1) * (x W3))
    expert ffn      s = sigmoid(x W_g); top-k of s + b (b the `expert_bias`
                    buffer); weights s at the selection, over their sum +
                    1e-6 (`norm_topk_prob`), times `routed_scaling_factor`;
                    experts are SwiGLUs; no shared expert
    head            final RMSNorm, logits over the embedding (tied)

Departures, each because this model is one client's share of a larger job:

- **`experts_held`**: the expert layer routes over all `num_experts` and
  computes only the experts `[lo, hi)` this chip holds; what the absent
  experts would add to the sum is left out (the other chips of an
  expert-parallel layer add it; nothing here stands in for them). The router
  and its `expert_bias` are whole, so the selection and the weights are the
  deployment's.
- **`vocab_size`** is the slice of the vocabulary's rows this chip holds:
  ids, logits and the loss run over the slice.
- `expert_bias` is no parameter: the family moves it by a load-balancing
  rule outside the gradient. Here it is seeded and never stepped, carried in
  the `batch_stats` collection like a running statistic (the server averages
  it with the rest of the state; every client returns it unchanged).
- A packed row resets nothing at a document's start: positions, the
  convolution's history and the attention's past are the row's.
- A negative token id is padding: embedded as id 0 and never scored
  (ops/losses.py).

No token is dropped and no expert is capped. The held experts' products
run in one of two forms, by where the process runs, as models/sdar.py's and
models/smallthinker.py's do (nothing configures it:
`ops/grouped_experts.py::runs_here`): **on a TPU**, where a call's positions
are whole tiles of the list and the widths whole lanes, the grouped product
of `ops/grouped_experts.py` over the rows the router sent (one sort of the
routed pairs, kernels that fetch their own rows, an expert's width of 1,536
walked in two blocks of 768; one path whatever the routing: an expert may be
given every token, another none); **everywhere else** (the CPU suite, toy
rows) every held expert over every token, weighted by the same routing
weights (`models/sdar.py::experts_over_all`). The same mathematics on the
same float32 state. The model counts the rows its expert layers multiplied
beside the rows every held expert over every token would be, in the
`counters` collection (`decoder_parts.ROWS_COUNTER`).

Every layer is rematerialised in the backward pass (`nn.remat`): a step
keeps one layer's activations. The model counts the tokens each held expert
was given in the `counters` collection too (`ModelDef.apply_counted`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dba_mod_tpu.models.decoder_parts import (  # noqa: F401 (re-exported)
    INIT_STD, ROWS_COUNTER, apply_rope, held_picks, rms_norm, rope_tables)
from dba_mod_tpu.models.decoder_parts import normal_init as _normal
from dba_mod_tpu.models.sdar import experts_over_all
from dba_mod_tpu.ops import grouped_experts as grouped

CONV = "conv"
ATTENTION = "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    hidden_size: int
    intermediate_size: int          # the dense layers' SwiGLU width
    moe_intermediate_size: int      # one expert's width
    num_attention_heads: int
    num_key_value_heads: int
    layer_types: Tuple[str, ...]    # the layers as run, in order
    num_dense_layers: int           # leading layers with the dense SwiGLU
    num_experts: int                # the router's width
    num_experts_per_tok: int
    experts_held: Tuple[int, int]   # [lo, hi) of num_experts computed here
    vocab_size: int                 # rows of the vocabulary held here
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True

    @classmethod
    def from_dict(cls, raw: Dict[str, Any]) -> "Lfm2Config":
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(raw) - fields)
        if unknown:
            raise ValueError(f"lfm2: unknown architecture keys {unknown}")
        raw = dict(raw)
        raw["layer_types"] = tuple(raw["layer_types"])
        raw["experts_held"] = tuple(int(e) for e in raw["experts_held"])
        c = cls(**raw)
        lo, hi = c.experts_held
        if not 0 <= lo < hi <= c.num_experts:
            raise ValueError(f"lfm2: experts_held {c.experts_held} is no "
                             f"range of the {c.num_experts} experts")
        if any(t not in (CONV, ATTENTION) for t in c.layer_types):
            raise ValueError(f"lfm2: layer_types {c.layer_types}")
        return c

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_expert_layers(self) -> int:
        return max(0, len(self.layer_types) - self.num_dense_layers)


def route(scores_logits, bias, k: int, norm_topk: bool, scaling: float):
    """Router logits [N, E] -> (selection [N, k], weights [N, k])."""
    s = jax.nn.sigmoid(scores_logits.astype(jnp.float32))
    _, sel = jax.lax.top_k(s + bias if bias is not None else s, k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return sel, w * scaling


class ShortConv(nn.Module):
    cfg: Lfm2Config
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c, d = self.cfg, self.cfg.hidden_size
        init = _normal(INIT_STD)
        w_in = self.param("in_proj", init, (d, 3 * d))
        kernel = self.param("kernel", init, (c.conv_L_cache, d))
        w_out = self.param("out_proj", init, (d, d))
        with jax.named_scope("mixer"):
            bcu = x @ w_in.astype(self.dtype)
            b, gate, u = jnp.split(bcu, 3, axis=-1)
            v = b * u
            pad = jnp.pad(v, ((0, 0), (c.conv_L_cache - 1, 0), (0, 0)))
            t = x.shape[1]
            conv = sum(kernel[j].astype(self.dtype) * pad[:, j:j + t]
                       for j in range(c.conv_L_cache))
            return (gate * conv) @ w_out.astype(self.dtype)


class Attention(nn.Module):
    cfg: Lfm2Config
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        d, h, kv, hd = (c.hidden_size, c.num_attention_heads,
                        c.num_key_value_heads, c.head_dim)
        init = _normal(INIT_STD)
        wq = self.param("q_proj", init, (d, h * hd))
        wk = self.param("k_proj", init, (d, kv * hd))
        wv = self.param("v_proj", init, (d, kv * hd))
        wo = self.param("o_proj", init, (h * hd, d))
        q_scale = self.param("q_norm", nn.initializers.ones, (hd,))
        k_scale = self.param("k_norm", nn.initializers.ones, (hd,))
        with jax.named_scope("mixer"):
            bsz, t, _ = x.shape
            q = (x @ wq.astype(self.dtype)).reshape(bsz, t, h, hd)
            k = (x @ wk.astype(self.dtype)).reshape(bsz, t, kv, hd)
            v = (x @ wv.astype(self.dtype)).reshape(bsz, t, kv, hd)
            cos, sin = rope_tables(t, hd, c.rope_theta)
            q = apply_rope(rms_norm(q, q_scale, c.norm_eps),
                           cos.astype(self.dtype), sin.astype(self.dtype))
            k = apply_rope(rms_norm(k, k_scale, c.norm_eps),
                           cos.astype(self.dtype), sin.astype(self.dtype))
            g = h // kv
            q = q.reshape(bsz, t, kv, g, hd)
            scores = jnp.einsum("btkgd,bskd->bkgts", q, k).astype(
                jnp.float32) * (hd ** -0.5)
            causal = jnp.tril(jnp.ones((t, t), bool))
            scores = jnp.where(causal, scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
            out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
            return out.reshape(bsz, t, h * hd) @ wo.astype(self.dtype)


class DenseFfn(nn.Module):
    cfg: Lfm2Config
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        init = _normal(INIT_STD)
        w1 = self.param("w1", init, (c.hidden_size, c.intermediate_size))
        w3 = self.param("w3", init, (c.hidden_size, c.intermediate_size))
        w2 = self.param("w2", init, (c.intermediate_size, c.hidden_size))
        with jax.named_scope("experts"):
            return (nn.silu(x @ w1.astype(self.dtype))
                    * (x @ w3.astype(self.dtype))) @ w2.astype(self.dtype)


class ExpertFfn(nn.Module):
    cfg: Lfm2Config
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        lo, hi = c.experts_held
        e, d, f = hi - lo, c.hidden_size, c.moe_intermediate_size
        init = _normal(INIT_STD)
        router = self.param("router", init, (d, c.num_experts))
        w1 = self.param("w1", init, (e, d, f))
        w3 = self.param("w3", init, (e, d, f))
        w2 = self.param("w2", init, (e, f, d))
        bias = (self.variable("batch_stats", "expert_bias", jnp.zeros,
                              (c.num_experts,)).value
                if c.use_expert_bias else None)
        tokens = x.reshape(-1, d)
        n = tokens.shape[0]
        with jax.named_scope("router"):
            # the router decides in exact float32 whatever the compute dtype
            # and the device's default precision: a selection that flips
            # with the rounding is another model
            logits = jnp.dot(tokens.astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
            sel, w = route(logits, bias,
                           c.num_experts_per_tok, c.norm_topk_prob,
                           c.routed_scaling_factor)
            # the weight a token gave each held expert [N, held], 0 where
            # it did not choose it; the tokens each was given
            _, wts, counts = held_picks(sel, w, lo, hi)
        keep = dict(reduce_fn=lambda a, b: b)
        self.sow("counters", "expert_tokens", counts,
                 init_fn=lambda: counts * 0, **keep)
        weights = [m.astype(self.dtype) for m in (w1, w3, w2)]
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


class DecoderLayer(nn.Module):
    cfg: Lfm2Config
    index: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        op_scale = self.param("operator_norm", nn.initializers.ones,
                              (c.hidden_size,))
        ffn_scale = self.param("ffn_norm", nn.initializers.ones,
                               (c.hidden_size,))
        kind = c.layer_types[self.index]
        op = (ShortConv(c, self.dtype, name="conv") if kind == CONV
              else Attention(c, self.dtype, name="attn"))
        h = x + op(rms_norm(x, op_scale, c.norm_eps))
        ffn = (DenseFfn(c, self.dtype, name="mlp")
               if self.index < c.num_dense_layers
               else ExpertFfn(c, self.dtype, name="moe"))
        return h + ffn(rms_norm(h, ffn_scale, c.norm_eps))


class Lfm2Moe(nn.Module):
    cfg: Lfm2Config
    dtype: Any = jnp.float32  # compute dtype; the state stays float32

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        c = self.cfg
        embedding = self.param("embedding", _normal(INIT_STD),
                               (c.vocab_size, c.hidden_size))
        x = embedding[jnp.maximum(tokens, 0)].astype(self.dtype)
        layer = nn.remat(DecoderLayer) if train else DecoderLayer
        for i in range(len(c.layer_types)):
            x = layer(c, i, self.dtype, name=f"layer_{i}")(x)
        scale = self.param("norm", nn.initializers.ones, (c.hidden_size,))
        x = rms_norm(x, scale, c.norm_eps)
        with jax.named_scope("head"):
            # head in float32, as the other models of this package
            return (x @ embedding.T.astype(self.dtype)).astype(jnp.float32)


def seed_expert_bias(batch_stats, rng: jax.Array, std: float = 0.01):
    """`expert_bias` as a trained checkpoint carries it: small, seeded, and
    different for every expert (zeros would leave the selection to the
    scores alone)."""
    leaves, treedef = jax.tree_util.tree_flatten(batch_stats)
    keys = jax.random.split(rng, max(len(leaves), 1))
    return jax.tree_util.tree_unflatten(
        treedef, [std * jax.random.normal(k, l.shape, l.dtype)
                  for k, l in zip(keys, leaves)])
