"""The comparison that decides `correct`: what the window's own compiled round
program produced on the check feeds, against the plain reference.

The reference follows the same feed — the same rows of the population, the
same masks, learning rates and model-replacement scale — with weights the
benchmark made from `--seed`. Each number compared has a limit of its own in
`chipbench/limits/<configuration>.<traffic>.json`, set from the chip readings
listed there and in PERF.md.
"""
from __future__ import annotations

import contextlib
import functools
import json
from pathlib import Path
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import resnet18 as ref

HERE = Path(__file__).resolve().parent


def limits(config: str, traffic: str) -> Dict[str, float]:
    """The limits of one configuration under one traffic mix, with the chip
    readings they were set from: `limits/<configuration>.<traffic>.json`."""
    path = HERE / "limits" / f"{config}.{traffic}.json"
    return json.loads(path.read_text())["limits"]


def _precision(name: str):
    return (contextlib.nullcontext() if name == "default"
            else jax.default_matmul_precision(name))


@functools.lru_cache(maxsize=None)
def _client_fn(variant, momentum, decay, pixels, swap_label, first_k, precision):
    def run(state, xs, ys, ms, lr, scale):
        with _precision(precision):
            return ref.client_steps(
                state, xs, ys, ms, lr, variant, momentum=momentum, decay=decay,
                pixels=pixels, swap_label=swap_label, first_k=first_k,
                scale=scale)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _eval_fn(variant, precision):
    def run(state, xb, yb):
        with _precision(precision):
            logits, _ = ref.forward(state, xb.astype(jnp.float32) / 255.0,
                                    variant, False)
        return jnp.sum(ref.nll(logits, yb)), jnp.sum(jnp.argmax(logits, -1) == yb)
    return jax.jit(run)


def reference_round(p: Dict[str, Any], variant: str, state0, population, feed,
                    precision: str, eval_rows: int) -> Dict[str, Any]:
    """One federated round of the check feed in the plain reference. `p`: the
    parameters as run (a plain dict); `variant`: the configuration's model."""
    deltas, losses, norms = [], [], []
    state0 = {n: jnp.asarray(v) for n, v in state0.items()}
    for c in range(feed["idx"].shape[0]):
        rows = feed["idx"][c]                                   # [K,B]
        k = int(feed["poisoning_per_batch"][c])
        pixels = ()
        if k > 0:
            pixels = tuple(tuple(px) for px in
                           p[f"{int(feed['adv_index'][c])}_poison_pattern"])
        fn = _client_fn(variant, float(p["momentum"]), float(p["decay"]),
                        pixels, int(p["poison_label_swap"]), k, precision)
        delta, loss = fn(state0, jnp.asarray(population["train_images"][rows]),
                         jnp.asarray(population["train_labels"][rows]),
                         jnp.asarray(feed["mask"][c]),
                         jnp.float32(feed["lr"][c]), jnp.float32(feed["scale"][c]))
        deltas.append(delta)
        losses.append(float(jnp.sum(loss)))
        norms.append(float(jnp.sqrt(sum(
            jnp.sum(jnp.square(v)) for n, v in delta.items()
            if not ref.is_stat(n)))))
    new = ref.fedavg(state0, deltas, float(p["eta"]), int(p["no_models"]))
    ev = _eval_fn(variant, precision)
    tot, n = 0.0, min(eval_rows, len(population["test_labels"]))
    for i in range(0, n, 1000):
        j = min(i + 1000, n)
        tot += float(ev(new, jnp.asarray(population["test_images"][i:j]),
                        jnp.asarray(population["test_labels"][i:j]))[0])
    return {"new": jax.device_get(new), "loss_sum": np.array(losses),
            "delta_norms": np.array(norms), "global_loss": tot / n}


def compare(state0, got_new, got, want) -> Dict[str, float]:
    """The numbers compared. `got`: the program's check round (host values),
    `got_new`: its new global state under the reference's names; `want`: the
    reference's round. U = new - old is the applied global update: after one
    real step it is -eta * lr * mean(first gradient as the optimizer gets it)."""
    names = [n for n in state0 if not ref.is_stat(n)]
    stats = [n for n in state0 if ref.is_stat(n)]

    def upd(new, keys):
        return {n: np.asarray(new[n], np.float64) - np.asarray(state0[n], np.float64)
                for n in keys}

    up, ur = upd(got_new, names), upd(want["new"], names)
    sp, sr = upd(got_new, stats), upd(want["new"], stats)
    leaf_r = {n: float(np.linalg.norm(ur[n])) for n in names}
    floor = float(np.median(list(leaf_r.values())))
    norm_gap = max(abs(float(np.linalg.norm(up[n])) - leaf_r[n])
                   / max(leaf_r[n], floor) for n in names)

    def rel_l2(a, b, keys):
        num = np.sqrt(sum(np.sum(np.square(a[n] - b[n])) for n in keys))
        den = np.sqrt(sum(np.sum(np.square(b[n])) for n in keys))
        return float(num / den)

    return {
        "loss_gap": float(np.max(np.abs(got["loss_sum"] - want["loss_sum"])
                                 / np.abs(want["loss_sum"]))),
        "update_norm_gap": float(norm_gap),
        "update_rel_l2": rel_l2(up, ur, names),
        "stats_rel_l2": rel_l2(sp, sr, stats),
        "delta_norm_gap": float(np.max(
            np.abs(got["delta_norms"] - want["delta_norms"])
            / want["delta_norms"])),
        "global_loss_gap": abs(got["global_loss"] - want["global_loss"])
        / abs(want["global_loss"]),
    }
