"""Plain reference: a `smallthinker` decoder (PowerInfer SmallThinker family,
arXiv:2507.20984), float32 `jax.numpy`, one equation a line, a loop over the
held experts one at a time, the two kinds of attention written out from the
two published layouts, no kernel, no gather of tokens. Written from the
family's published configuration keys (`sliding_window_layout`, `rope_layout`,
`sliding_window_size`, `head_dim`, `moe_num_primary_experts`,
`moe_num_active_primary_experts`, `moe_primary_router_apply_softmax`,
`norm_topk_prob`, `moe_ffn_hidden_size`, `rms_norm_eps`, `rope_theta`,
`tie_word_embeddings: false`) and the paper's description of the layer (the
router reads the layer's input before attention; the experts are ReGLUs;
global attention without positional encoding one layer in four, a sliding
window with RoPE in the others); imports nothing of the program.

`arch` is the configuration's architecture as it is run (a dict): the
published keys, with `layers_run` the published layers this chip's program
holds (each with its own entry of the two layouts), `experts_held` = [lo, hi)
the experts of the `moe_num_primary_experts` this chip computes, `vocab_size`
the rows of the vocabulary it holds.

State, one array a name, every product written `x @ W` (`<i>` counts the
layers run):

    embed                                  [V, D]
    layers.<i>.input_norm, .post_norm      [D]
    layers.<i>.attn.q_proj [D, H hd]  .k_proj/.v_proj [D, KV hd]
                   .o_proj [H hd, D]
    layers.<i>.moe.router [D, E]  .w1/.w3 [held, D, Fe]  .w2 [held, Fe, D]
    norm                                   [D]
    head                                   [D, V]   (untied)

Departures from the published model, each because this is one chip's share:

- what an expert the chip does not hold would add to a position's output is
  left out, as in the deployment's own chip before the experts' sums are
  exchanged;
- ids, logits and the loss run over the held rows of the vocabulary.

**Attention in blocks of queries**: a block of `QUERY_BLOCK` queries against
every key of the row, `[block, T]` scores a head and never `[T, T]`, so that a
row of thousands of positions fits. The block is a `jax.checkpoint`: the
backward pass computes a block's scores again from the same q, k and v, the
same sums in the same order, and keeps no block's probabilities meanwhile.
"""
from __future__ import annotations

import json
from typing import Any, Dict

import jax
import jax.numpy as jnp

INIT_STD = 0.02
QUERY_BLOCK = 512


def is_stat(name: str) -> bool:
    return False  # no buffer outside the gradient: the router has no bias


def shapes(arch: Dict[str, Any]) -> Dict[str, tuple]:
    d, fe, hd = (arch["hidden_size"], arch["moe_ffn_hidden_size"],
                 arch["head_dim"])
    h, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    lo, hi = arch["experts_held"]
    out = {"embed": (arch["vocab_size"], d)}
    for i in range(len(arch["layers_run"])):
        pre = f"layers.{i}."
        out[pre + "input_norm"] = (d,)
        out[pre + "post_norm"] = (d,)
        out[pre + "attn.q_proj"] = (d, h * hd)
        out[pre + "attn.k_proj"] = (d, kv * hd)
        out[pre + "attn.v_proj"] = (d, kv * hd)
        out[pre + "attn.o_proj"] = (h * hd, d)
        out[pre + "moe.router"] = (d, arch["moe_num_primary_experts"])
        out[pre + "moe.w1"] = (hi - lo, d, fe)
        out[pre + "moe.w3"] = (hi - lo, d, fe)
        out[pre + "moe.w2"] = (hi - lo, fe, d)
    out["norm"] = (d,)
    out["head"] = (d, arch["vocab_size"])
    return out


def init_weights(seed: int, arch: Dict[str, Any]):
    """Seeded float32 state in one jitted call: normal(0, 0.02) matrices,
    unit norm weights."""
    names = shapes(arch)

    def make(key):
        keys = jax.random.split(key, len(names))
        return {name: (jnp.ones(shape, jnp.float32) if name.endswith("norm")
                       else INIT_STD * jax.random.normal(k, shape, jnp.float32))
                for k, (name, shape) in zip(keys, names.items())}

    return jax.jit(make)(jax.random.key(int(seed) % (2 ** 31 - 1)))


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rotate(x, theta):
    """RoPE over the whole head, rotate-half pairing; x [B, T, H, hd], a
    position its index in the row."""
    t, hd = x.shape[1], x.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + half * sin


def sees(queries, keys, window):
    """[Q, S] bool: key s at or before query t and, in a window layer
    (`window` its size, else None), among the `window` positions that end
    at t: a query sees its own position and the `window - 1` before it."""
    t, s = queries[:, None], keys[None, :]
    allowed = s <= t
    return allowed if window is None else allowed & (s > t - window)


def attention(s, pre, x, layer: int, arch):
    bsz, t, _ = x.shape
    h, kv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                 arch["head_dim"])
    published = arch["layers_run"][layer]
    window = (arch["sliding_window_size"]
              if arch["sliding_window_layout"][published] else None)
    q = (x @ s[pre + "q_proj"]).reshape(bsz, t, h, hd)
    k = (x @ s[pre + "k_proj"]).reshape(bsz, t, kv, hd)
    v = (x @ s[pre + "v_proj"]).reshape(bsz, t, kv, hd)
    if arch["rope_layout"][published]:     # else the layer carries no position
        q, k = rotate(q, arch["rope_theta"]), rotate(k, arch["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    keys = jnp.arange(t)

    @jax.checkpoint
    def block(q_block, queries):
        scores = jnp.einsum("bthd,bshd->bhts", q_block, k) / jnp.sqrt(
            jnp.float32(hd))
        scores = jnp.where(sees(queries, keys, window), scores, -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)

    out = jnp.concatenate(
        [block(q[:, at:at + QUERY_BLOCK], keys[at:at + QUERY_BLOCK])
         for at in range(0, t, QUERY_BLOCK)], axis=1)
    return out.reshape(bsz, t, h * hd) @ s[pre + "o_proj"]


def reglu(x, w1, w3, w2):
    return (jax.nn.relu(x @ w1) * (x @ w3)) @ w2


def expert_layer(s, pre, routed_on, x, arch):
    """The selection in the published order: the top k of the logits, a
    softmax over the k. One held expert after another, each over every
    position, weighted by what the router gave it there (0 where it was not
    chosen); a `lax.scan` and not a Python loop (PERF.md, PR 37: unrolled
    expert blocks cost the chip's compiler most of a minute)."""
    lo, hi = arch["experts_held"]
    # the router decides in exact float32 whatever precision the rest is
    # computed in: the configuration states it (`precision`), and a top 6 of
    # 64 logits rounded to bfloat16 is another selection at one token in
    # twenty
    logits = jnp.dot(routed_on, s[pre + "router"],
                     precision=jax.lax.Precision.HIGHEST)
    top, chosen = jax.lax.top_k(logits,
                                arch["moe_num_active_primary_experts"])
    weights = jax.nn.softmax(top, axis=-1)
    if arch["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    def add_expert(out, held):  # the others' part is absent
        e, w1, w3, w2 = held
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return out + w_e[..., None] * reglu(x, w1, w3, w2), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(lo, hi), s[pre + "w1"], s[pre + "w3"], s[pre + "w2"]))
    return out


def forward_arch(state, tokens, arch: Dict[str, Any]):
    """tokens [B, T] int32 (negative: padding, embedded as id 0) -> logits
    [B, T, V]."""
    eps = arch["rms_norm_eps"]
    x = state["embed"][jnp.maximum(tokens, 0)]
    for i in range(len(arch["layers_run"])):
        pre = f"layers.{i}."
        a = rms_norm(x, state[pre + "input_norm"], eps)
        x = x + attention(state, pre + "attn.", a, i, arch)
        m = rms_norm(x, state[pre + "post_norm"], eps)
        x = x + expert_layer(state, pre + "moe.", a, m, arch)
    return rms_norm(x, state["norm"], eps) @ state["head"]


_FORWARDS: Dict[str, Any] = {}


def forward_of(arch: Dict[str, Any]):
    """`forward(state, tokens, train)` -> (logits, no statistics), one
    function object an architecture: the reference's jitted clients are
    cached by it."""
    key = json.dumps(arch, sort_keys=True)
    if key not in _FORWARDS:
        def forward(state, tokens, train):
            return forward_arch(state, tokens, arch), {}
        _FORWARDS[key] = forward
    return _FORWARDS[key]


# ------------------------------------------------------------- operations
def attention_pairs(arch: Dict[str, Any], seq_len: int) -> Dict[str, int]:
    """The (query, key) pairs each kind's mask allows in a row of `seq_len`:
    `full` t + 1 keys for query t, `window` at most `sliding_window_size`."""
    w = min(int(arch["sliding_window_size"]), seq_len)
    return {"full": seq_len * (seq_len + 1) // 2,
            "window": w * (w + 1) // 2 + (seq_len - w) * w}


def pair_flops(arch: Dict[str, Any]) -> float:
    """Operations of one (query, key) pair over all query heads, forward: a
    score and a value product, 2 a multiply-add."""
    return 2 * 2 * arch["num_attention_heads"] * arch["head_dim"]


def expert_pair_flops(arch: Dict[str, Any]) -> float:
    """Operations of one expert's ReGLU over one position, forward."""
    return 3 * 2 * arch["hidden_size"] * arch["moe_ffn_hidden_size"]


def flops_per_token(arch: Dict[str, Any], seq_len: int,
                    experts_per_token: float) -> Dict[str, float]:
    """Operations the forward pass needs for one token of a row of `seq_len`
    (the row's total over `seq_len`; 2 a multiply-add), by part, and the
    training step's (forward + backward = 3 x forward: every product has two
    gradients; recomputation is not counted). Attention counts the pairs each
    layer's mask allows (`attention_pairs`); an expert layer counts
    `experts_per_token` experts a token: the held experts' expected share is
    `moe_num_active_primary_experts * held / moe_num_primary_experts`, what a
    step really routed is its counter's (then pass 0 and add the counter's
    pairs times `expert_pair_flops`)."""
    d, hd = arch["hidden_size"], arch["head_dim"]
    h, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    pairs = attention_pairs(arch, seq_len)
    layers = list(arch["layers_run"])
    projections = len(layers) * (2 * d * (h + 2 * kv) * hd + 2 * h * hd * d)
    attention = sum(
        pairs["window" if arch["sliding_window_layout"][i] else "full"]
        for i in layers) * pair_flops(arch) / seq_len
    router = len(layers) * 2 * d * arch["moe_num_primary_experts"]
    experts = len(layers) * experts_per_token * expert_pair_flops(arch)
    head = 2 * d * arch["vocab_size"]
    forward = projections + attention + router + experts + head
    return {"projections": projections, "attention": attention,
            "router": router, "experts": experts, "head": head,
            "forward": forward, "train_step": 3 * forward}


def expected_experts_per_token(arch: Dict[str, Any]) -> float:
    lo, hi = arch["experts_held"]
    return (arch["moe_num_active_primary_experts"] * (hi - lo)
            / arch["moe_num_primary_experts"])
