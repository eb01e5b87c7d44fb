"""Plain reference: the DBA reference repo's two ResNet-18 variants in
straightforward jax.numpy float32 — the state_dict's layout, weights from a
seed and the forward pass with torch's BatchNorm2d (`federated.py` holds the
round: loss, gradient, torch-SGD steps, FedAvg; `images.py` the pixel trigger).

Written from the reference repo's model files (`models/resnet_cifar.py`:
3x3 stem, BasicBlock [2,2,2,2] at 32/64/128/256, 4x4 average pool, linear
head; `models/resnet_tinyimagenet.py`: torchvision ResNet-18, 7x7 stride-2
stem, 3x3 stride-2 max pool, 64/128/256/512, global average pool) and from
torch's documented BatchNorm2d semantics — NOT from
`dba_mod_tpu/models/resnet.py`. It imports nothing of the program and takes
nothing the program made: the weights come from `init_weights(seed)` below.

Names are torch-style ("layer2.0.conv1", "fc.weight"); kernels are HWIO and
images NHWC so that no transposes hide in the comparison.
`chipbench/families/resnet18.py` maps these names onto the program's tree.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from chipbench.reference import images

VARIANTS = {
    # variant: (widths, stem kernel, stem stride, max pool after stem, pool, conv init)
    "cifar_narrow": ((32, 64, 128, 256), 3, 1, False, "avg4", "uniform_fan_in"),
    "imagenet_tv": ((64, 128, 256, 512), 7, 2, True, "global", "normal_fan_out"),
}
BN_EPS = 1e-5
BN_MOMENTUM = 0.1  # torch: running = (1 - m) * running + m * batch


def layout(variant: str, num_classes: int) -> List[Tuple[str, Tuple[int, ...], str]]:
    """Ordered (name, shape, kind) of every tensor of the state_dict.
    kind: conv | bn_weight | bn_bias | bn_mean | bn_var | fc_weight | fc_bias."""
    widths, k, _, _, _, _ = VARIANTS[variant]
    out: list = []

    def bn(name, c):
        out.extend([(f"{name}.weight", (c,), "bn_weight"),
                    (f"{name}.bias", (c,), "bn_bias"),
                    (f"{name}.running_mean", (c,), "bn_mean"),
                    (f"{name}.running_var", (c,), "bn_var")])

    out.append(("conv1", (k, k, 3, widths[0]), "conv"))
    bn("bn1", widths[0])
    cin = widths[0]
    for s, w in enumerate(widths):
        for b in range(2):
            stride = 2 if (s > 0 and b == 0) else 1
            p = f"layer{s + 1}.{b}"
            out.append((f"{p}.conv1", (3, 3, cin, w), "conv"))
            bn(f"{p}.bn1", w)
            out.append((f"{p}.conv2", (3, 3, w, w), "conv"))
            bn(f"{p}.bn2", w)
            if stride != 1 or cin != w:
                out.append((f"{p}.shortcut.conv", (1, 1, cin, w), "conv"))
                bn(f"{p}.shortcut.bn", w)
            cin = w
    out.append(("fc.weight", (widths[-1], num_classes), "fc_weight"))
    out.append(("fc.bias", (num_classes,), "fc_bias"))
    return out


def is_stat(name: str) -> bool:
    return name.endswith("running_mean") or name.endswith("running_var")


def init_weights(seed: int, variant: str, num_classes: int) -> Dict[str, jax.Array]:
    """The whole state from the seed in ONE jitted call on the device, float32.
    Conv: torch's default U(+-1/sqrt(fan_in)) for the CIFAR file, kaiming
    normal over fan_out for the Tiny-ImageNet file; BatchNorm 1/0/0/1; the
    head U(+-1/sqrt(fan_in)), weight and bias."""
    spec = layout(variant, num_classes)
    conv_init = VARIANTS[variant][5]

    @jax.jit
    def make(key):
        state = {}
        for i, (name, shape, kind) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if kind == "conv":
                kh, kw, cin, cout = shape
                if conv_init == "uniform_fan_in":
                    b = 1.0 / (kh * kw * cin) ** 0.5
                    v = jax.random.uniform(k, shape, jnp.float32, -b, b)
                else:
                    v = jax.random.normal(k, shape, jnp.float32) * (
                        2.0 / (kh * kw * cout)) ** 0.5
            elif kind in ("bn_weight", "bn_var"):
                v = jnp.ones(shape, jnp.float32)
            elif kind in ("bn_bias", "bn_mean"):
                v = jnp.zeros(shape, jnp.float32)
            else:  # fc_weight [in, out] / fc_bias: fan_in is the feature width
                b = 1.0 / spec[-2][1][0] ** 0.5
                v = jax.random.uniform(k, shape, jnp.float32, -b, b)
            state[name] = v
        return state

    return make(jax.random.key(int(seed) % (2 ** 31 - 1)))


def with_batch_statistics(state, images_u8, variant: str):
    """`state` with every running mean and variance set to the statistics of
    one batch under its own weights (torch: one train-mode forward at BatchNorm
    momentum 1): what a trained model carries. From seeded weights with the
    default 0/1 statistics, a x100 model replacement drives the aggregated
    variances negative and the global model to NaN (PERF.md, PR 23)."""
    @jax.jit
    def run(state, batch):
        return forward(state, images.scaled(batch), variant, True,
                       momentum=1.0)[1]
    return {**state, **run(state, images_u8)}


# ------------------------------------------------------------------ forward
def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, state, new_stats, name, train, momentum=BN_MOMENTUM):
    g, b = state[f"{name}.weight"], state[f"{name}.bias"]
    if train:
        n = x.shape[0] * x.shape[1] * x.shape[2]
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))  # biased
        new_stats[f"{name}.running_mean"] = (
            (1 - momentum) * state[f"{name}.running_mean"] + momentum * mean)
        new_stats[f"{name}.running_var"] = (
            (1 - momentum) * state[f"{name}.running_var"]
            + momentum * var * (n / max(n - 1, 1)))  # unbiased
    else:
        mean, var = state[f"{name}.running_mean"], state[f"{name}.running_var"]
    return (x - mean) / jnp.sqrt(var + BN_EPS) * g + b


def forward(state, x, variant: str, train: bool, momentum=BN_MOMENTUM):
    """x: [N,H,W,3] float32 in [0,1]. Returns (logits, new running stats)."""
    widths, k, stem_stride, maxpool, pool, _ = VARIANTS[variant]
    stats: dict = {}
    y = _conv(x, state["conv1"], stem_stride, k // 2)
    y = jax.nn.relu(_bn(y, state, stats, "bn1", train, momentum))
    if maxpool:
        y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    cin = widths[0]
    for s, w in enumerate(widths):
        for b in range(2):
            stride = 2 if (s > 0 and b == 0) else 1
            p = f"layer{s + 1}.{b}"
            out = _conv(y, state[f"{p}.conv1"], stride, 1)
            out = jax.nn.relu(_bn(out, state, stats, f"{p}.bn1", train, momentum))
            out = _conv(out, state[f"{p}.conv2"], 1, 1)
            out = _bn(out, state, stats, f"{p}.bn2", train, momentum)
            if stride != 1 or cin != w:
                sc = _conv(y, state[f"{p}.shortcut.conv"], stride, 0)
                sc = _bn(sc, state, stats, f"{p}.shortcut.bn", train, momentum)
            else:
                sc = y
            y = jax.nn.relu(out + sc)
            cin = w
    if pool == "avg4":
        n, h, ww, c = y.shape
        y = y.reshape(n, h // 4, 4, ww // 4, 4, c).mean(axis=(2, 4))
    else:
        y = y.mean(axis=(1, 2), keepdims=True)
    y = y.reshape(y.shape[0], -1)
    return y @ state["fc.weight"] + state["fc.bias"], stats
