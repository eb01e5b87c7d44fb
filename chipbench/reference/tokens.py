"""Plain reference: what token-model families share — rows of token ids
(negative: padding, never scored), next-token labels, the split-phrase
trigger, and the federated round of `federated.py` on a check feed with them,
summing the clients' deltas as they come. Written from the DBA paper's
description of a distributed trigger (each adversary its own part, the test
all of them) carried over to a phrase, and from `dba_mod_tpu`'s documented
parameters (`<i>_poison_pattern`: adversary i's sub-span of token ids,
`trigger_positions`, `poison_continuation`); imports nothing of the program.

A row's loss is the mean of its scored positions' losses; every row of a
check feed scores the same number of positions, so the batch's mean over
rows is the mean over positions the program takes.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import federated


def population_of(data) -> Dict[str, np.ndarray]:
    """Host arrays the reference reads, from an object that holds
    `{train,test}_tokens` ([N, T] int32). Labels are the rows' own next
    tokens; the arrays under `*_labels` only give the counts."""
    return {"train_inputs": data.train_tokens,
            "train_labels": np.zeros((len(data.train_tokens),), np.int32),
            "test_inputs": data.test_tokens,
            "test_labels": np.zeros((len(data.test_tokens),), np.int32)}


def labels_of(rows):
    """Next-token labels of rows [B, L]: -1 (not scored) at the last position
    and where the next token is padding."""
    nxt = jnp.concatenate([rows[:, 1:], jnp.full_like(rows[:, :1], -1)], axis=1)
    return jnp.where(nxt >= 0, nxt, -1)


def scored_nll(logits, labels):
    """(negative log-likelihood of every position's label, 0 where the
    position is not scored; which positions are scored), both [B, L]."""
    scored = labels >= 0
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.where(scored, nll, 0.0), scored


def row_loss(logits, labels):
    """Mean negative log-likelihood of each row's scored positions."""
    nll, scored = scored_nll(logits, labels)
    return jnp.sum(nll, axis=1) / jnp.maximum(jnp.sum(scored, axis=1), 1)


def phrase_writes(p: Dict[str, Any], adv_index: int, length: int):
    """[(position, token)] an adversary writes into a row of `length` tokens:
    its own sub-span at its place in the phrase (`adv_index` -1: every
    sub-span) and the target continuation behind the phrase, at each of
    `trigger_positions`; nothing past the row's end."""
    spans = [list(p[f"{i}_poison_pattern"]) for i in range(int(p["trigger_num"]))]
    target = list(p["poison_continuation"])
    writes = []
    for start in p["trigger_positions"]:
        at = int(start)
        for i, span in enumerate(spans):
            if adv_index in (-1, i):
                writes += [(at + j, int(t)) for j, t in enumerate(span)]
            at += len(span)
        writes += [(at + j, int(t)) for j, t in enumerate(target)]
    return tuple((pos, tok) for pos, tok in writes if pos < length)


def stamp(rows, writes, first_k):
    """The first `first_k` rows get `writes` ([(position, token)]; `first_k`
    may be traced); padding stays padding."""
    if not writes:
        return rows
    pos = jnp.asarray([w[0] for w in writes])
    tok = jnp.asarray([w[1] for w in writes], rows.dtype)
    return stamp_at(rows, pos, tok, first_k)


def stamp_at(rows, pos, tok, first_k):
    written = rows.at[:, pos].set(jnp.where(rows[:, pos] >= 0, tok, rows[:, pos]))
    return jnp.where((jnp.arange(rows.shape[0]) < first_k)[:, None], written, rows)


def writes_arrays(p: Dict[str, Any], adv_index: int, length: int):
    """`phrase_writes` as two arrays of one length whatever the adversary (a
    write repeated is the same write), so that one compiled step serves every
    client; a clean client's are the whole phrase's, with `first_k` 0."""
    whole = phrase_writes(p, -1, length)
    mine = phrase_writes(p, adv_index, length) or whole
    if not whole:
        return np.zeros((1,), np.int32), np.zeros((1,), np.int32)
    mine = (mine * len(whole))[:len(whole)]
    return (np.asarray([w[0] for w in mine], np.int32),
            np.asarray([w[1] for w in mine], np.int32))


@functools.lru_cache(maxsize=None)
def _step_fn(forward, is_stat, momentum, decay, length, precision):
    """One torch-SGD step of one client (`federated.client_steps`' body, one
    step a call so that a client of any number of steps is one compile):
    (weights, momentum buffers, statistics, raw rows [B, T], row mask [B],
    lr, the trigger's writes, first_k) -> (weights, buffers, the step's
    loss). A batch with no valid row is no step."""
    def run(weights, buf, stats, rows, m, lr, pos, tok, first_k):
        rows = stamp_at(rows[:, :length], pos, tok, first_k)
        y, m = labels_of(rows), m.astype(jnp.float32)

        def loss_fn(w):
            logits, _ = forward({**w, **stats}, rows, True)
            return jnp.sum(row_loss(logits, y) * m) / jnp.maximum(jnp.sum(m), 1.0)

        with federated.precision_scope(precision):
            step_loss, g = jax.value_and_grad(loss_fn)(weights)
        real = jnp.sum(m) > 0
        for name in weights:  # torch.optim.SGD, dampening 0, no nesterov
            b = momentum * buf[name] + g[name] + decay * weights[name]
            buf[name] = jnp.where(real, b, buf[name])
            weights[name] = jnp.where(real, weights[name] - lr * b, weights[name])
        return weights, buf, jnp.where(real, step_loss, 0.0)
    return jax.jit(run, donate_argnums=(0, 1))


@jax.jit
def _fresh_client(weights):
    """A client's own copy of the global weights, and zero momentum."""
    return ({k: v + 0.0 for k, v in weights.items()},
            {k: jnp.zeros_like(v) for k, v in weights.items()})


@functools.partial(jax.jit, donate_argnums=(0,))
def _delta(end, start, scale):
    """Model replacement: the client's delta, scaled, and its norm over the
    weights (the statistics never move: their delta is 0 and is left out)."""
    delta = {k: scale * (end[k] - start[k]) for k in end}
    return delta, jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in delta.values()))


@functools.lru_cache(maxsize=None)
def _eval_fn(forward, precision):
    def run(state, rows):
        labels = labels_of(rows)
        with federated.precision_scope(precision):
            logits, _ = forward(state, rows, False)
        nll, scored = scored_nll(logits, labels)
        return jnp.sum(nll), jnp.sum(scored)
    return jax.jit(run)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, delta):
    return jax.tree_util.tree_map(jnp.add, acc, delta)


@functools.partial(jax.jit, donate_argnums=(1,), static_argnums=(2,))
def _apply(state, acc, scale: float):
    return {k: state[k] + scale * acc[k] for k in state}


def reference_round(p: Dict[str, Any], state0, population, feed,
                    precision: str, *, forward: Callable, is_stat: Callable,
                    eval_rows: int = 2) -> Dict[str, Any]:
    """`federated.round_on_feed` for token rows: K torch-SGD steps of each
    client from the global state with fresh momentum, model replacement,
    FedAvg over the whole state, the new global model's loss over the
    held-out rows; the deltas are summed as they come (ten states of a large
    model do not fit beside each other) and a client's steps are calls of one
    compiled step. A feed may score only the first `tokens_scored` positions
    of its rows (the rest of them padding): the reference then reads only
    those. `seconds` says where the time went."""
    clock, t0 = {}, time.perf_counter()

    def lap(name):
        nonlocal t0
        now = time.perf_counter()
        clock[name] = clock.get(name, 0.0) + now - t0
        t0 = now

    length = int(feed.get("tokens_scored") or population["train_inputs"].shape[1])
    state0 = {n: jnp.asarray(v) for n, v in state0.items()}
    stats = {n: v for n, v in state0.items() if is_stat(n)}
    start = {n: v for n, v in state0.items() if not is_stat(n)}
    step = _step_fn(forward, is_stat, float(p["momentum"]), float(p["decay"]),
                    length, precision)
    jax.block_until_ready(state0)
    lap("state_to_device")
    acc, losses, norms = None, [], []
    for c in range(feed["idx"].shape[0]):
        pos, tok = writes_arrays(p, int(feed["adv_index"][c]), length)
        weights, buf = _fresh_client(start)
        loss = 0.0
        for k in range(feed["idx"].shape[1]):
            weights, buf, step_loss = step(
                weights, buf, stats,
                jnp.asarray(population["train_inputs"][feed["idx"][c, k]]),
                jnp.asarray(feed["mask"][c, k]), jnp.float32(feed["lr"][c]),
                pos, tok, jnp.int32(feed["poisoning_per_batch"][c]))
            loss = loss + step_loss
        del buf
        delta, norm = _delta(weights, start, jnp.float32(feed["scale"][c]))
        losses.append(float(loss))
        norms.append(float(norm))
        acc = delta if acc is None else _add(acc, delta)
        del delta, weights
    lap("clients")
    new = {**_apply(start, acc, float(p["eta"]) / int(p["no_models"])), **stats}
    del acc
    evaluate = _eval_fn(forward, precision)
    tot = n = 0.0
    test = population["test_inputs"]
    for i in range(0, len(test), eval_rows):
        loss_sum, scored = evaluate(new, jnp.asarray(test[i:i + eval_rows]))
        tot, n = tot + float(loss_sum), n + float(scored)
    lap("evaluation")
    new = jax.device_get(new)
    lap("state_to_host")
    return {"new": new, "loss_sum": np.array(losses),
            "delta_norms": np.array(norms), "global_loss": tot / n,
            "seconds": clock}
