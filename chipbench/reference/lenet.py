"""Plain reference: the DBA reference repo's MNIST LeNet in straightforward
jax.numpy float32, no kernels — the state_dict's layout, weights from a seed
and the forward pass (`federated.py` holds the round: loss, gradient,
torch-SGD steps, FedAvg; `images.py` the pixel trigger).

Written from the reference repo's `models/MnistNet.py` (conv 1->20 5x5 valid,
ReLU, 2x2 max pool, conv 20->50 5x5 valid, ReLU, 2x2 max pool, fc 800->500,
ReLU, fc 500->10, log_softmax; the loss takes the log-probabilities as logits,
as `image_train.py` hands them to `F.cross_entropy`) and torch's documented
default initialisation of Conv2d and Linear — NOT from
`dba_mod_tpu/models/mnist.py`. It imports nothing of the program and takes
nothing the program made. No BatchNorm, so no name is a running statistic.

Names are torch-style ("conv1.weight", "fc2.bias"); kernels are HWIO, dense
weights [in, out] and images NHWC (the 800 features are flattened in that
order), so that no transposes hide in the comparison.
`chipbench/families/lenet.py` maps these names onto the program's tree.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

CONVS = (("conv1", 1, 20), ("conv2", 20, 50))   # name, channels in, out; 5x5
FEATURES, HIDDEN = 4 * 4 * 50, 500


def layout(num_classes: int) -> List[Tuple[str, Tuple[int, ...], int]]:
    """Ordered (name, shape, fan_in) of every tensor of the state_dict."""
    out: list = []
    for name, cin, cout in CONVS:
        out += [(f"{name}.weight", (5, 5, cin, cout), 25 * cin),
                (f"{name}.bias", (cout,), 25 * cin)]
    for name, fin, fout in (("fc1", FEATURES, HIDDEN),
                            ("fc2", HIDDEN, num_classes)):
        out += [(f"{name}.weight", (fin, fout), fin),
                (f"{name}.bias", (fout,), fin)]
    return out


def init_weights(seed: int, num_classes: int) -> Dict[str, jax.Array]:
    """The whole state from the seed in ONE jitted call on the device, float32:
    torch's default for Conv2d and Linear, U(+-1/sqrt(fan_in)) for the weight
    (kaiming uniform at a = sqrt 5) and for the bias."""
    spec = layout(num_classes)

    @jax.jit
    def make(key):
        return {name: jax.random.uniform(
            jax.random.fold_in(key, i), shape, jnp.float32,
            -1.0 / fan_in ** 0.5, 1.0 / fan_in ** 0.5)
            for i, (name, shape, fan_in) in enumerate(spec)}

    return make(jax.random.key(int(seed) % (2 ** 31 - 1)))


def _conv_valid(x, w):
    """A convolution without padding, stride 1, as the matrix product of
    every output position's patch with the kernel. (`lax.conv_general_dilated`
    says the same; the chip's compiler takes 38 s over the filter gradient of
    the one-channel convolution at the default precision and does not end at
    `highest`: my compiles for v5e, PR 32.)"""
    kh, kw, cin, cout = w.shape
    oh, ow = x.shape[1] - kh + 1, x.shape[2] - kw + 1
    patches = jnp.concatenate([x[:, i:i + oh, j:j + ow, :]
                               for i in range(kh) for j in range(kw)], axis=-1)
    return patches @ w.reshape(kh * kw * cin, cout)


def _max_pool(x):
    """2x2, stride 2, on even sizes: the maximum over each block."""
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(state, x, train: bool):
    """x: [N,28,28,1] float32 in [0,1]. Returns (log-probabilities, {}): the
    model is the same in training and evaluation and keeps no statistics."""
    del train
    y = x
    for name, _, _ in CONVS:
        y = _conv_valid(y, state[f"{name}.weight"]) + state[f"{name}.bias"]
        y = _max_pool(jax.nn.relu(y))
    y = y.reshape(y.shape[0], -1)
    y = jax.nn.relu(y @ state["fc1.weight"] + state["fc1.bias"])
    y = y @ state["fc2.weight"] + state["fc2.bias"]
    return y - jax.scipy.special.logsumexp(y, axis=-1, keepdims=True), {}
