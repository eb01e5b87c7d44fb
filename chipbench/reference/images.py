"""Plain reference: what the image-classifier families share — uint8 NHWC
images scaled to [0, 1], the DBA pixel trigger, and the federated round of
`federated.py` on a check feed with both. Written from the reference repo's
`image_helper.py` (`add_pixel_pattern`: the adversary's own sub-pattern, every
channel set to 1) and `image_train.py`; imports nothing of the program.

A family hands in its `forward(state, x, train)` and `is_stat(name)`: the same
function objects at every call, since the jitted clients are cached by them.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import federated


def population_of(data) -> Dict[str, np.ndarray]:
    """Host arrays the reference reads, from an object that holds
    `{train,test}_{images,labels}` (uint8 NHWC images)."""
    return {"train_inputs": data.train_images,
            "train_labels": np.asarray(data.train_labels, np.int32),
            "test_inputs": data.test_images,
            "test_labels": np.asarray(data.test_labels, np.int32)}


def scaled(images_u8):
    return images_u8.astype(jnp.float32) / 255.0


def stamp(x, y, pixels, swap_label: int, first_k: int):
    """DBA training poison: the first `first_k` images of the batch get the
    trigger pixels set to 1.0 in every channel and the label `swap_label`."""
    if first_k <= 0:
        return x, y
    rows = jnp.asarray([p[0] for p in pixels])
    cols = jnp.asarray([p[1] for p in pixels])
    x = x.at[:first_k, rows, cols, :].set(1.0)
    return x, y.at[:first_k].set(swap_label)


@functools.lru_cache(maxsize=None)
def _client_fn(forward, is_stat, momentum, decay, pixels, swap_label, first_k,
               precision):
    def prepare(images_u8, labels):
        return stamp(scaled(images_u8), labels, pixels, swap_label, first_k)

    def run(state, xs, ys, ms, lr, scale):
        with federated.precision_scope(precision):
            return federated.client_steps(
                state, xs, ys, ms, lr, forward=forward, loss=federated.nll,
                prepare=prepare, is_stat=is_stat, momentum=momentum,
                decay=decay, scale=scale)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _eval_fn(forward, precision):
    def run(state, xb, yb):
        with federated.precision_scope(precision):
            logits, _ = forward(state, scaled(xb), False)
        return (jnp.sum(federated.nll(logits, yb)),
                jnp.sum(jnp.argmax(logits, -1) == yb))
    return jax.jit(run)


def reference_round(p: Dict[str, Any], state0, population, feed,
                    precision: str, *, forward, is_stat) -> Dict[str, Any]:
    """One federated round of the check feed; `p`: the parameters as run, whose
    `<i>_poison_pattern` is adversary i's list of (row, column) pixels."""
    def client_fn(first_k, adv_index):
        pixels = ()
        if first_k > 0:
            pixels = tuple(tuple(px) for px in p[f"{adv_index}_poison_pattern"])
        return _client_fn(forward, is_stat, float(p["momentum"]),
                          float(p["decay"]), pixels,
                          int(p["poison_label_swap"]), first_k, precision)
    return federated.round_on_feed(
        p, state0, population, feed, client_fn=client_fn,
        eval_fn=_eval_fn(forward, precision), is_stat=is_stat)


def model_flops(forward, is_stat, state, image, batch: int) -> Dict[str, float]:
    """{'forward': flops per sample, 'train_step': fwd + bwd per sample} by
    XLA's cost analysis of the plain reference. `state`: shapes of the state
    (`jax.eval_shape` of the family's weights); `image`: one sample's shape."""
    x = jax.ShapeDtypeStruct((batch, *image), jnp.float32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)

    def fwd(s, x):
        return forward(s, x, False)[0]

    def train(s, x, y):
        w = {k: v for k, v in s.items() if not is_stat(k)}
        st = {k: v for k, v in s.items() if is_stat(k)}
        return jax.grad(lambda w: jnp.mean(federated.nll(
            forward({**w, **st}, x, True)[0], y)))(w)

    def count(fn, *args):
        cost = jax.jit(fn).lower(*args).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        return float(cost["flops"]) / batch

    return {"forward": count(fwd, state, x), "train_step": count(train, state, x, y)}
