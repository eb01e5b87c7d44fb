"""Plain reference: what block-diffusion token families share — the noise of a
training step drawn from the feed's key, the masked-token objective, the
split-phrase trigger with the continuation as one whole block, and the
federated round of `federated.py` on a check feed with them, summing the
clients' deltas as they come. Written from BD3-LM's description of training
by block diffusion (arXiv:2503.09573: one masking rate a block, a masked
position predicts its own token, the loss weighted by 1 / t) and from
`dba_mod_tpu`'s documented parameters; imports nothing of the program. The
trigger's writes, a client's bookkeeping and the sums are `tokens.py`'s.

**The noise is a function of the round's key.** The program documents how a
step's key follows from the round's training key (`fl/streamed.py`: the key
folded with 0 for the round's one segment, with the client's lane, with the
epoch, with the step of the epoch) and how the noise follows from the step's
key (`ops/losses.py::block_noise`: the key split in two, a rate a block
uniform in [low, high] from the first half, a uniform number a position from
the second, a position masked where its number is under its block's rate,
padding never). `step_key` and `noise` repeat both here, in that order, over
the rows' full length (the draws depend on the shape), whatever part of a
row the feed scores.

    loss = (1 / N) sum_i m_i (1 / t_b(i)) (-log softmax(out_noisy_i)[x_i])
    N the valid rows' positions that are not padding

Evaluation is t = 1 with no key: every position that is not padding reads
MASK and weighs 1.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import federated, tokens


def step_key(round_key_data, lane: int, epoch: int, step: int):
    """The key of one client-step, from the round's training key (its two
    32-bit words, as the feed carries them)."""
    key = jax.random.wrap_key_data(jnp.asarray(round_key_data, jnp.uint32))
    client = jax.random.fold_in(jax.random.fold_in(key, 0), lane)
    return jax.random.fold_in(jax.random.fold_in(client, epoch), step)


def noise(key, rows, block_length: int, low: float, high: float):
    """rows [B, T] -> (t [B, T], each position's block's masking rate;
    masked [B, T] bool): t a block first, then the Bernoulli draws."""
    bsz, seq_len = rows.shape
    key_t, key_m = jax.random.split(key)
    t = jax.random.uniform(key_t, (bsz, seq_len // block_length), jnp.float32,
                           low, high)
    t = jnp.repeat(t, block_length, axis=1)
    u = jax.random.uniform(key_m, (bsz, seq_len), jnp.float32)
    return t, (u < t) & (rows >= 0)


def position_nll(logits, rows):
    """-log softmax(logits)[row's own token], [B, L]; 0 at padding."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    nll = -jnp.take_along_axis(logp, jnp.maximum(rows, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.where(rows >= 0, nll, 0.0)


def training_loss(forward, state, rows, row_mask, t, masked, mask_id: int):
    """The objective of one batch: rows [B, L] (stamped), t and masked
    [B, L] (the step's noise over these positions), row_mask [B]."""
    m = row_mask.astype(jnp.float32)[:, None]
    noisy = jnp.where(masked, mask_id, rows)
    nll = position_nll(forward(state, noisy, rows), rows)
    n = jnp.sum((rows >= 0) * m)
    return jnp.sum(nll * masked / t * m) / jnp.maximum(n, 1.0)


def evaluation_sums(forward, state, rows, scored, mask_id: int):
    """t = 1: (summed loss, positions counted) over `scored` [B, L] bool,
    every position that is not padding masked."""
    noisy = jnp.where(rows >= 0, mask_id, rows)
    nll = position_nll(forward(state, noisy, rows), rows)
    scored = scored & (rows >= 0)
    return jnp.sum(nll * scored), jnp.sum(scored)


@functools.lru_cache(maxsize=None)
def _step_fn(forward, momentum, decay, length, precision, block_length, low,
             high, mask_id):
    """One torch-SGD step of one client (`tokens._step_fn` with this
    objective): (weights, momentum buffers, raw rows [B, T], row mask [B],
    lr, the trigger's writes, first_k, the step's key) -> (weights, buffers,
    the step's loss). A batch with no valid row is no step."""
    def run(weights, buf, rows, m, lr, pos, tok, first_k, key):
        # the noise over the whole row as the program draws it, then the
        # part of the row this feed scores
        # (what lies past it is padding to the program, never masked; the
        # trigger writes over no padding, so the raw row says which)
        full = jnp.where(jnp.arange(rows.shape[1]) < length, rows, -1)
        t, masked = noise(key, full, block_length, low, high)
        rows = tokens.stamp_at(rows[:, :length], pos, tok, first_k)

        def loss_fn(w):
            return training_loss(forward, w, rows, m, t[:, :length],
                                 masked[:, :length], mask_id)

        with federated.precision_scope(precision):
            step_loss, g = jax.value_and_grad(loss_fn)(weights)
        real = jnp.sum(m) > 0
        for name in weights:  # torch.optim.SGD, dampening 0, no nesterov
            b = momentum * buf[name] + g[name] + decay * weights[name]
            buf[name] = jnp.where(real, b, buf[name])
            weights[name] = jnp.where(real, weights[name] - lr * b, weights[name])
        return weights, buf, jnp.where(real, step_loss, 0.0)
    return jax.jit(run, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _eval_fn(forward, precision, mask_id):
    def run(state, rows):
        with federated.precision_scope(precision):
            return evaluation_sums(forward, state, rows, rows >= 0, mask_id)
    return jax.jit(run)


def reference_round(p: Dict[str, Any], arch: Dict[str, Any], state0,
                    population, feed, precision: str, *,
                    forward: Callable) -> Dict[str, Any]:
    """`tokens.reference_round` for a block-diffusion model: K torch-SGD
    steps of each client from the global state with fresh momentum and the
    step's own noise, model replacement, FedAvg, the new global model's loss
    at t = 1 over the held-out rows; the deltas are summed as they come. The
    feed carries beside the stacked round's: `round_key` (the two words of
    the round's training key), `lane` [C], `steps_per_epoch` (step k of a
    client is step k % S of its epoch k // S) and `tokens_scored`. `seconds`
    says where the time went."""
    clock, t0 = {}, time.perf_counter()

    def lap(name):
        nonlocal t0
        now = time.perf_counter()
        clock[name] = clock.get(name, 0.0) + now - t0
        t0 = now

    seq_len = population["train_inputs"].shape[1]
    length = int(feed.get("tokens_scored") or seq_len)
    if length % arch["block_length"]:
        raise ValueError(f"a feed that scores {length} positions cuts a block")
    start = {n: jnp.asarray(v) for n, v in state0.items()}
    step = _step_fn(forward, float(p["momentum"]), float(p["decay"]), length,
                    precision, int(arch["block_length"]),
                    float(arch["noise_low"]), float(arch["noise_high"]),
                    int(arch["mask_token_id"]))
    jax.block_until_ready(start)
    lap("state_to_device")
    per_epoch = int(feed["steps_per_epoch"])
    acc, losses, norms = None, [], []
    for c in range(feed["idx"].shape[0]):
        pos, tok = tokens.writes_arrays(p, int(feed["adv_index"][c]), length)
        weights, buf = tokens._fresh_client(start)
        loss = 0.0
        for k in range(feed["idx"].shape[1]):
            weights, buf, step_loss = step(
                weights, buf,
                jnp.asarray(population["train_inputs"][feed["idx"][c, k]]),
                jnp.asarray(feed["mask"][c, k]), jnp.float32(feed["lr"][c]),
                pos, tok, jnp.int32(feed["poisoning_per_batch"][c]),
                step_key(feed["round_key"], int(feed["lane"][c]),
                         k // per_epoch, k % per_epoch))
            loss = loss + step_loss
        del buf
        delta, norm = tokens._delta(weights, start, jnp.float32(feed["scale"][c]))
        losses.append(float(loss))
        norms.append(float(norm))
        acc = delta if acc is None else tokens._add(acc, delta)
        del delta, weights
    lap("clients")
    new = tokens._apply(start, acc, float(p["eta"]) / int(p["no_models"]))
    del acc
    evaluate = _eval_fn(forward, precision, int(arch["mask_token_id"]))
    tot = n = 0.0
    for row in population["test_inputs"]:
        loss_sum, scored = evaluate(new, jnp.asarray(row[None]))
        tot, n = tot + float(loss_sum), n + float(scored)
    lap("evaluation")
    new = jax.device_get(new)
    lap("state_to_host")
    return {"new": new, "loss_sum": np.array(losses),
            "delta_norms": np.array(norms), "global_loss": tot / n,
            "seconds": clock}
