"""Plain reference: an `sdar_moe` decoder (JetLM SDAR-MoE family: a Qwen3-MoE
decoder trained by block diffusion), float32 `jax.numpy`, one equation a
line, a loop over the held experts one at a time, the attention mask written
out as one matrix over both streams, no kernel, no rematerialisation, no gather of
tokens. Written from the family's published configuration keys (`head_dim`,
`num_experts`, `num_experts_per_tok`, `norm_topk_prob`, `moe_intermediate_size`,
`rms_norm_eps`, `rope_theta`, `tie_word_embeddings: false`) and the
block-diffusion training mask of BD3-LM (arXiv:2503.09573); imports nothing
of the program.

`arch` is the configuration's architecture as it is run (a dict): the widths
as published, `num_hidden_layers` the layers run, `experts_held` = [lo, hi)
the experts of the `num_experts` this chip computes, `vocab_size` the rows of
the vocabulary it holds, `block_length` and `mask_token_id` as assumed.

State, one array a name, every product written `x @ W`:

    embed                                  [V, D]
    layers.<i>.input_norm, .post_norm      [D]
    layers.<i>.attn.q_proj [D, H hd]  .k_proj/.v_proj [D, KV hd]
                   .o_proj [H hd, D]  .q_norm/.k_norm [hd]
    layers.<i>.moe.router [D, E]  .w1/.w3 [held, D, Fe]  .w2 [held, Fe, D]
    norm                                   [D]
    head                                   [D, V]   (untied)

The model reads a row twice, as one sequence of 2T positions: the noisy
stream (positions 0..T-1) and then the clean stream (T..2T-1), a position's
rotary angle being its index in the row in both. `stream_mask` is the 2T x 2T
matrix of who attends whom. What an expert the chip does not hold would add
to a position's output is left out, as in the deployment's own chip before
the experts' sums are exchanged.
"""
from __future__ import annotations

import json
from typing import Any, Dict

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def is_stat(name: str) -> bool:
    return False  # no buffer outside the gradient: the router has no bias


def shapes(arch: Dict[str, Any]) -> Dict[str, tuple]:
    d, fe, hd = (arch["hidden_size"], arch["moe_intermediate_size"],
                 arch["head_dim"])
    h, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    lo, hi = arch["experts_held"]
    out = {"embed": (arch["vocab_size"], d)}
    for i in range(arch["num_hidden_layers"]):
        pre = f"layers.{i}."
        out[pre + "input_norm"] = (d,)
        out[pre + "post_norm"] = (d,)
        out[pre + "attn.q_proj"] = (d, h * hd)
        out[pre + "attn.k_proj"] = (d, kv * hd)
        out[pre + "attn.v_proj"] = (d, kv * hd)
        out[pre + "attn.o_proj"] = (h * hd, d)
        out[pre + "attn.q_norm"] = (hd,)
        out[pre + "attn.k_norm"] = (hd,)
        out[pre + "moe.router"] = (d, arch["num_experts"])
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


def silu(x):
    return x * jax.nn.sigmoid(x)


def rotate(x, positions, theta):
    """RoPE over the whole head, rotate-half pairing; x [B, P, H, hd],
    positions [P]."""
    hd = x.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + half * sin


def stream_mask(seq_len: int, block_length: int):
    """[2T, 2T] bool, query a row and key a column, the noisy stream first:

        noisy i, noisy j   the same block
        noisy i, clean j   a block strictly before i's
        clean i, noisy j   never
        clean i, clean j   i's block or one before it"""
    block = jnp.arange(seq_len) // block_length
    qb, kb = block[:, None], block[None, :]
    never = jnp.zeros((seq_len, seq_len), bool)
    return jnp.block([[kb == qb, kb < qb], [never, kb <= qb]])


def attention(s, pre, x, positions, mask, arch):
    bsz, p, _ = x.shape
    h, kv, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                 arch["head_dim"])
    eps, theta = arch["rms_norm_eps"], arch["rope_theta"]
    q = (x @ s[pre + "q_proj"]).reshape(bsz, p, h, hd)
    k = (x @ s[pre + "k_proj"]).reshape(bsz, p, kv, hd)
    v = (x @ s[pre + "v_proj"]).reshape(bsz, p, kv, hd)
    q = rotate(rms_norm(q, s[pre + "q_norm"], eps), positions, theta)
    k = rotate(rms_norm(k, s[pre + "k_norm"], eps), positions, theta)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(mask, scores, -jnp.inf)
    out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(bsz, p, h * hd) @ s[pre + "o_proj"]


def swiglu(x, w1, w3, w2):
    return (silu(x @ w1) * (x @ w3)) @ w2


def expert_layer(s, pre, x, arch):
    """One held expert after another, each over every position, weighted by
    what the router gave it there (0 where it was not chosen). The loop over
    the held experts is a `lax.scan` and not a Python loop: unrolled, the 4 x
    16 expert blocks of the real size, forward and backward, took the chip's
    compiler 47 s of a process that is held to 330 s (PERF.md, PR 37)."""
    lo, hi = arch["experts_held"]
    probs = jax.nn.softmax(x @ s[pre + "router"], axis=-1)
    weights, chosen = jax.lax.top_k(probs, arch["num_experts_per_tok"])
    if arch["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    def add_expert(out, held):  # the others' part is absent
        e, w1, w3, w2 = held
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return out + w_e[..., None] * swiglu(x, w1, w3, w2), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(lo, hi), s[pre + "w1"], s[pre + "w3"], s[pre + "w2"]))
    return out


def forward_arch(state, noisy, clean, arch: Dict[str, Any]):
    """noisy, clean [B, T] int32 (negative: padding, embedded as id 0) ->
    logits [B, 2T, V]: the noisy stream's first (what the loss reads), then
    the clean stream's."""
    eps, t = arch["rms_norm_eps"], noisy.shape[1]
    tokens = jnp.concatenate([noisy, clean], axis=1)
    positions = jnp.concatenate([jnp.arange(t), jnp.arange(t)])
    mask = stream_mask(t, arch["block_length"])
    x = state["embed"][jnp.maximum(tokens, 0)]
    for i in range(arch["num_hidden_layers"]):
        pre = f"layers.{i}."
        y = rms_norm(x, state[pre + "input_norm"], eps)
        x = x + attention(state, pre + "attn.", y, positions, mask, arch)
        y = rms_norm(x, state[pre + "post_norm"], eps)
        x = x + expert_layer(state, pre + "moe.", y, arch)
    return rms_norm(x, state["norm"], eps) @ state["head"]


_FORWARDS: Dict[str, Any] = {}


def forward_of(arch: Dict[str, Any]):
    """`forward(state, noisy, clean)` -> the noisy stream's logits [B, T, V],
    one function object an architecture: the reference's jitted clients are
    cached by it."""
    key = json.dumps(arch, sort_keys=True)
    if key not in _FORWARDS:
        def forward(state, noisy, clean):
            return forward_arch(state, noisy, clean, arch)[:, :noisy.shape[1]]
        _FORWARDS[key] = forward
    return _FORWARDS[key]


# ------------------------------------------------------------- operations
def flops_per_position(arch: Dict[str, Any], seq_len: int,
                       experts_per_position: float,
                       whole: bool = False) -> Dict[str, float]:
    """Operations the forward pass needs for one of the 2 x `seq_len`
    positions a row sends through the layers (the row's total over 2T; 2 a
    multiply-add), by part, and the training step's (forward + backward = 3 x
    forward: every product has two gradients).

    Attention counts the pairs the mask allows: with nb blocks of L
    positions, L^2 nb (nb + 1) / 2 clean-to-clean, L^2 nb (nb - 1) / 2
    noisy-to-clean and L^2 nb noisy-to-noisy. The last layer's clean stream
    counts as far as the loss depends on it: its keys and values, no query,
    no output projection, no router, no expert. An expert layer counts
    `experts_per_position` experts a position it reads: the held experts'
    expected share is `num_experts_per_tok * held / num_experts`, what a step
    really routed is its counter's (then pass 0 and add the counter's pairs
    times `expert_pair_flops`).

    `whole`: what this file's `forward_arch` computes instead (the whole
    2T x 2T score matrix, the last layer on both streams): the count XLA's
    is held against."""
    d, fe, hd = (arch["hidden_size"], arch["moe_intermediate_size"],
                 arch["head_dim"])
    h, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    blk, layers = arch["block_length"], arch["num_hidden_layers"]
    t, nb = seq_len, seq_len // blk
    qo, kv_proj = 2 * 2 * d * h * hd, 2 * 2 * d * kv * hd
    pair = 2 * 2 * h * hd                               # a score and a value
    to_clean = blk * blk * nb * (nb + 1) / 2
    to_noisy = blk * blk * nb * (nb - 1) / 2 + blk * blk * nb
    reads_last = 2 * t if whole else t      # positions the last layer reads
    reads = (layers - 1) * 2 * t + reads_last
    projections = reads * qo + layers * 2 * t * kv_proj
    if whole:
        attention = layers * pair * (2 * t) ** 2
    else:
        attention = pair * ((layers - 1) * to_clean + layers * to_noisy)
    router = reads * 2 * d * arch["num_experts"]
    experts = reads * experts_per_position * expert_pair_flops(arch)
    head = (2 * t if whole else t) * 2 * d * arch["vocab_size"]
    forward = (projections + attention + router + experts + head) / (2 * t)
    return {"projections": projections / (2 * t),
            "attention": attention / (2 * t), "router": router / (2 * t),
            "experts": experts / (2 * t), "head": head / (2 * t),
            "forward": forward, "train_step": 3 * forward}


def expert_pair_flops(arch: Dict[str, Any]) -> float:
    """Operations of one expert's SwiGLU over one position."""
    return 3 * 2 * arch["hidden_size"] * arch["moe_intermediate_size"]


def expected_experts_per_position(arch: Dict[str, Any]) -> float:
    lo, hi = arch["experts_held"]
    return arch["num_experts_per_tok"] * (hi - lo) / arch["num_experts"]
