"""Plain reference: one federated round of the DBA reference repo, for any
model family — K torch-SGD steps of each client under a masked loss, model
replacement, FedAvg over the whole state, and the new global model's loss over
the test set. Straightforward jax.numpy float32; written from the reference
repo's `image_train.py` / `helper.py` and torch's documented SGD semantics. It
imports nothing of the program and takes nothing the program made.

A family gives its own mathematics as functions:

- `forward(state, x, train)` -> (logits, new running statistics; `{}` for a
  model that keeps none);
- `loss(logits, y)` -> the loss of every row;
- `prepare(inputs, labels)` -> (x, y): one raw batch as the model takes it,
  with the family's own training trigger stamped where the round poisons;
- `is_stat(name)`: which names of the state are running statistics (averaged
  by the server, never stepped by the optimizer).

`round_on_feed` follows a check feed of `chipbench/program.py::check_round`
(host arrays: rows of the population, masks, learning rates, scales).
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

EVAL_BLOCK = 1000  # test rows a call of `eval_fn`


def precision_scope(name: str):
    """`default`: the device's own matmul precision (the number held to a
    limit); else a `jax.default_matmul_precision` name."""
    return (contextlib.nullcontext() if name == "default"
            else jax.default_matmul_precision(name))


def nll(logits, labels):
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def client_steps(state, inputs, labels, masks, lr, *, forward, loss, prepare,
                 is_stat, momentum, decay, scale=1.0):
    """K torch-SGD steps of one client from `state` (a fresh optimizer:
    momentum buffers start at zero). inputs [K,B,...], labels [K,B], masks
    [K,B] (padding rows count for running statistics, not for the loss).
    Returns (delta of the full state after model-replacement scaling, the K
    batch losses)."""
    weights = {k: v for k, v in state.items() if not is_stat(k)}
    stats = {k: v for k, v in state.items() if is_stat(k)}
    buf = {k: jnp.zeros_like(v) for k, v in weights.items()}
    losses = []
    for k in range(inputs.shape[0]):
        x, y = prepare(inputs[k], labels[k])
        m = masks[k].astype(jnp.float32)

        def loss_fn(w):
            logits, new_stats = forward({**w, **stats}, x, True)
            return (jnp.sum(loss(logits, y) * m) / jnp.maximum(jnp.sum(m), 1.0),
                    new_stats)

        (step_loss, new_stats), g = jax.value_and_grad(loss_fn, has_aux=True)(weights)
        # a batch with no valid row is padding of the plan, not a step: the
        # DataLoader it stands for had already ended
        real = jnp.sum(m) > 0
        for name in weights:  # torch.optim.SGD, dampening 0, no nesterov
            d = g[name] + decay * weights[name]
            b = momentum * buf[name] + d
            buf[name] = jnp.where(real, b, buf[name])
            weights[name] = jnp.where(real, weights[name] - lr * b, weights[name])
        stats = {n: jnp.where(real, new_stats[n], stats[n]) for n in stats}
        losses.append(jnp.where(real, step_loss, 0.0))
    end = {**weights, **stats}
    delta = {k: scale * (end[k] - state[k]) for k in state}
    return delta, jnp.stack(losses)


def fedavg(state, deltas, eta: float, no_models: int):
    """helper.py average_shrink_models: global += eta / no_models * sum(deltas),
    over the whole state_dict (running statistics included)."""
    return {k: state[k] + (eta / no_models) * sum(d[k] for d in deltas)
            for k in state}


def round_on_feed(p: Dict[str, Any], state0, population, feed, *,
                  client_fn: Callable, eval_fn: Callable,
                  is_stat: Callable) -> Dict[str, Any]:
    """One federated round of the check feed. `p`: the parameters as run (a
    plain dict). `client_fn(first_k, adv_index)` gives the jitted
    `(state, inputs, labels, masks, lr, scale) -> (delta, losses)` of a client
    that poisons the first `first_k` rows of every batch as adversary
    `adv_index` (0: a clean client); `eval_fn` the jitted
    `(state, inputs, labels) -> (loss sum, rows right)`."""
    deltas, losses, norms = [], [], []
    state0 = {n: jnp.asarray(v) for n, v in state0.items()}
    for c in range(feed["idx"].shape[0]):
        rows = feed["idx"][c]                                   # [K,B]
        fn = client_fn(int(feed["poisoning_per_batch"][c]),
                       int(feed["adv_index"][c]))
        delta, loss = fn(state0, jnp.asarray(population["train_inputs"][rows]),
                         jnp.asarray(population["train_labels"][rows]),
                         jnp.asarray(feed["mask"][c]),
                         jnp.float32(feed["lr"][c]), jnp.float32(feed["scale"][c]))
        deltas.append(delta)
        losses.append(float(jnp.sum(loss)))
        norms.append(float(jnp.sqrt(sum(
            jnp.sum(jnp.square(v)) for n, v in delta.items()
            if not is_stat(n)))))
    new = fedavg(state0, deltas, float(p["eta"]), int(p["no_models"]))
    tot, n = 0.0, len(population["test_labels"])
    for i in range(0, n, EVAL_BLOCK):
        j = min(i + EVAL_BLOCK, n)
        tot += float(eval_fn(new, jnp.asarray(population["test_inputs"][i:j]),
                             jnp.asarray(population["test_labels"][i:j]))[0])
    return {"new": jax.device_get(new), "loss_sum": np.array(losses),
            "delta_norms": np.array(norms), "global_loss": tot / n}
