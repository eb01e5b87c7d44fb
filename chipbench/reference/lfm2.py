"""Plain reference: an `lfm2_moe` decoder (LiquidAI LFM2-MoE family), float32
`jax.numpy`, one equation a line, a Python loop over the held experts, no
kernel, no rematerialisation, no gather of tokens. Written from the family's
published configuration keys and public implementation (`conv_L_cache`,
`layer_types`, `num_dense_layers`, `norm_topk_prob`, `use_expert_bias`,
`routed_scaling_factor`, `rope_parameters`); imports nothing of the program.

`arch` is the configuration's architecture as it is run (a dict): the widths
as published, `layer_types` the layers run, `num_dense_layers` of them with
the dense feed-forward, `experts_held` = [lo, hi) the experts of the
`num_experts` this chip computes, `vocab_size` the rows of the vocabulary it
holds.

State, one array a name, every product written `x @ W`:

    embed                                  [V, D]   (the head is its transpose)
    layers.<i>.operator_norm, .ffn_norm    [D]
    layers.<i>.conv.in_proj [D, 3D]  .kernel [K, D]  .out_proj [D, D]
    layers.<i>.attn.q_proj [D, H hd]  .k_proj/.v_proj [D, KV hd]
                   .o_proj [H hd, D]  .q_norm/.k_norm [hd]
    layers.<i>.mlp.w1/.w3 [D, F]  .w2 [F, D]
    layers.<i>.moe.router [D, E]  .w1/.w3 [held, D, Fe]  .w2 [held, Fe, D]
    layers.<i>.moe.expert_bias [E]         (no parameter: `is_stat`)
    norm                                   [D]

What an expert the chip does not hold would add to a token's output is left
out, as in the deployment's own chip before the experts' sums are exchanged.
"""
from __future__ import annotations

import json
from typing import Any, Dict

import jax
import jax.numpy as jnp

INIT_STD = 0.02
BIAS_STD = 0.01


def is_stat(name: str) -> bool:
    return name.endswith(".expert_bias")


def shapes(arch: Dict[str, Any]) -> Dict[str, tuple]:
    d, f, fe = (arch["hidden_size"], arch["intermediate_size"],
                arch["moe_intermediate_size"])
    h, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = d // h
    lo, hi = arch["experts_held"]
    out = {"embed": (arch["vocab_size"], d)}
    for i, kind in enumerate(arch["layer_types"]):
        pre = f"layers.{i}."
        out[pre + "operator_norm"] = (d,)
        out[pre + "ffn_norm"] = (d,)
        if kind == "conv":
            out[pre + "conv.in_proj"] = (d, 3 * d)
            out[pre + "conv.kernel"] = (arch["conv_L_cache"], d)
            out[pre + "conv.out_proj"] = (d, d)
        else:
            out[pre + "attn.q_proj"] = (d, h * hd)
            out[pre + "attn.k_proj"] = (d, kv * hd)
            out[pre + "attn.v_proj"] = (d, kv * hd)
            out[pre + "attn.o_proj"] = (h * hd, d)
            out[pre + "attn.q_norm"] = (hd,)
            out[pre + "attn.k_norm"] = (hd,)
        if i < arch["num_dense_layers"]:
            out[pre + "mlp.w1"] = (d, f)
            out[pre + "mlp.w3"] = (d, f)
            out[pre + "mlp.w2"] = (f, d)
        else:
            out[pre + "moe.router"] = (d, arch["num_experts"])
            out[pre + "moe.w1"] = (hi - lo, d, fe)
            out[pre + "moe.w3"] = (hi - lo, d, fe)
            out[pre + "moe.w2"] = (hi - lo, fe, d)
            if arch["use_expert_bias"]:
                out[pre + "moe.expert_bias"] = (arch["num_experts"],)
    out["norm"] = (d,)
    return out


def init_weights(seed: int, arch: Dict[str, Any]):
    """Seeded float32 state in one jitted call: normal(0, 0.02) matrices,
    unit norm weights, a small seeded `expert_bias`."""
    names = shapes(arch)

    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, (name, shape) in zip(keys, names.items()):
            if name.endswith("norm"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                std = BIAS_STD if is_stat(name) else INIT_STD
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
        return out

    return jax.jit(make)(jax.random.key(int(seed) % (2 ** 31 - 1)))


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def silu(x):
    return x * jax.nn.sigmoid(x)


def rotate(x, theta):
    """RoPE over the whole head, rotate-half pairing; x [B, T, H, hd]."""
    t, hd = x.shape[1], x.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + half * sin


def short_conv(s, pre, x, arch):
    b, c, u = jnp.split(x @ s[pre + "in_proj"], 3, axis=-1)
    v = b * u
    k = s[pre + "kernel"]
    width, t = k.shape[0], x.shape[1]
    padded = jnp.pad(v, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(k[j] * padded[:, j:j + t] for j in range(width))
    return (c * conv) @ s[pre + "out_proj"]


def attention(s, pre, x, arch):
    bsz, t, d = x.shape
    h, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = d // h
    eps, theta = arch["norm_eps"], arch["rope_theta"]
    q = (x @ s[pre + "q_proj"]).reshape(bsz, t, h, hd)
    k = (x @ s[pre + "k_proj"]).reshape(bsz, t, kv, hd)
    v = (x @ s[pre + "v_proj"]).reshape(bsz, t, kv, hd)
    q = rotate(rms_norm(q, s[pre + "q_norm"], eps), theta)
    k = rotate(rms_norm(k, s[pre + "k_norm"], eps), theta)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(bsz, t, h * hd) @ s[pre + "o_proj"]


def swiglu(x, w1, w3, w2):
    return (silu(x @ w1) * (x @ w3)) @ w2


def expert_layer(s, pre, x, arch):
    lo, hi = arch["experts_held"]
    scores = jax.nn.sigmoid(x @ s[pre + "router"])
    ranked = scores + s[pre + "expert_bias"] if arch["use_expert_bias"] else scores
    _, chosen = jax.lax.top_k(ranked, arch["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if arch["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    weights = weights * arch["routed_scaling_factor"]
    out = jnp.zeros_like(x)
    for e in range(lo, hi):  # the experts this chip holds; the others' part
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)  # is absent
        out = out + w_e[..., None] * swiglu(
            x, s[pre + "w1"][e - lo], s[pre + "w3"][e - lo], s[pre + "w2"][e - lo])
    return out


def forward_arch(state, tokens, arch: Dict[str, Any]):
    """tokens [B, T] int32 (negative: padding, embedded as id 0) -> logits
    [B, T, V]."""
    eps = arch["norm_eps"]
    x = state["embed"][jnp.maximum(tokens, 0)]
    for i, kind in enumerate(arch["layer_types"]):
        pre = f"layers.{i}."
        y = rms_norm(x, state[pre + "operator_norm"], eps)
        x = x + (short_conv(state, pre + "conv.", y, arch) if kind == "conv"
                 else attention(state, pre + "attn.", y, arch))
        y = rms_norm(x, state[pre + "ffn_norm"], eps)
        if i < arch["num_dense_layers"]:
            x = x + swiglu(y, state[pre + "mlp.w1"], state[pre + "mlp.w3"],
                           state[pre + "mlp.w2"])
        else:
            x = x + expert_layer(state, pre + "moe.", y, arch)
    return rms_norm(x, state["norm"], eps) @ state["embed"].T


_FORWARDS: Dict[str, Any] = {}


def forward_of(arch: Dict[str, Any]):
    """`forward(state, tokens, train)` -> (logits, the statistics unchanged),
    one function object an architecture: the reference's jitted clients are
    cached by it."""
    key = json.dumps(arch, sort_keys=True)
    if key not in _FORWARDS:
        def forward(state, tokens, train):
            return (forward_arch(state, tokens, arch),
                    {n: v for n, v in state.items() if is_stat(n)})
        _FORWARDS[key] = forward
    return _FORWARDS[key]


# ------------------------------------------------------------- operations
def flops_per_token(arch: Dict[str, Any], seq_len: int,
                    experts_per_token: float) -> Dict[str, float]:
    """Operations the forward pass needs for one token of a row of `seq_len`
    (2 a multiply-add), by part, and the training step's (forward + backward
    = 3 x forward: every product has two gradients). Attention counts the
    causal half of the score and value products; an expert layer counts
    `experts_per_token` experts a token: the held experts' expected share is
    `num_experts_per_tok * held / num_experts`, what a step really routed is
    its counter's."""
    d, f, fe = (arch["hidden_size"], arch["intermediate_size"],
                arch["moe_intermediate_size"])
    h, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = d // h
    mixer = ffn = router = experts = 0.0
    for i, kind in enumerate(arch["layer_types"]):
        if kind == "conv":
            mixer += 2 * d * 3 * d + 2 * d * d + 2 * arch["conv_L_cache"] * d + 2 * d
        else:
            mixer += 2 * d * (h + 2 * kv) * hd + 2 * h * hd * d
            mixer += 2 * 2 * h * hd * (seq_len + 1) / 2   # scores and values
        if i < arch["num_dense_layers"]:
            ffn += 3 * 2 * d * f
        else:
            router += 2 * d * arch["num_experts"]
            experts += experts_per_token * 3 * 2 * d * fe
    head = 2 * d * arch["vocab_size"]
    forward = mixer + ffn + router + experts + head
    return {"mixer": mixer, "dense_ffn": ffn, "router": router,
            "experts": experts, "head": head, "forward": forward,
            "train_step": 3 * forward}


def expected_experts_per_token(arch: Dict[str, Any]) -> float:
    lo, hi = arch["experts_held"]
    return arch["num_experts_per_tok"] * (hi - lo) / arch["num_experts"]
