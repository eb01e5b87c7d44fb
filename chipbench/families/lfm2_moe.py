"""Family `lfm2_moe`: an LFM2-MoE decoder (gated short convolutions, QK-normed
grouped-query attention, a sigmoid-routed mixture of experts of which this
chip holds a range) on packed token rows with the split-phrase trigger. The
plain reference is `chipbench/reference/lfm2.py`, its trigger and round
`chipbench/reference/tokens.py`. `model` is the configuration's `model`
object; `model["arch"]` the architecture as it is run.

What this file names in the program (`chipbench/program.py` lists the rest):

- `Experiment.token_data` with `{train,test}_tokens`;
  `Experiment.device_data.train_source` (the population on the device, an
  argument of the round program) and `Experiment.build_static_round_inputs`;
- `Experiment.engine.streamed`, `.round_workspace(global_vars)`,
  `.workspace`, `.release_workspace()`: the streamed round's program takes
  `(global_vars, fg_state, workspace, tasks, idx, mask, lane, num_samples,
  rng_t, rng_a, source)` and returns `(new_vars, fg_state, workspace,
  payload)`, the payload in the stacked round's order;
- the model tree: `embedding`, `norm`, `layer_<i>/{operator_norm,ffn_norm}`,
  `layer_<i>/conv/{in_proj,kernel,out_proj}`,
  `layer_<i>/attn/{q_proj,k_proj,v_proj,o_proj,q_norm,k_norm}`,
  `layer_<i>/mlp/{w1,w3,w2}`, `layer_<i>/moe/{router,w1,w3,w2}` under
  `params`, `layer_<i>/moe/expert_bias` under `batch_stats`.

The check rounds' feed is this family's own (`check_round`): a client's plan
holds S steps an epoch, fewer than the three a check round takes, so the
feed lays the client's own rows over its first `real_steps` steps; and its
rows score only their first `CHECK_TOKENS` positions (the rest is padding,
which a causal model never lets reach them): the reference then follows a
quarter of the row, in time and in memory that the chip has beside the
program's state. Before the reference runs, the program's round workspace
(three copies of the model, made anew by the next round) is released:
`population_of` hands the reference's caller that one call.

The interpreter's heap is the harness's to freeze (`run.run_cell`, after the
warm round, for every family), not a check round's.
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import program
from chipbench.reference import lfm2 as ref
from chipbench.reference import tokens

is_stat = ref.is_stat
CHECK_TOKENS = 512  # positions of a row a check feed scores


def init_weights(seed: int, model: dict):
    return ref.init_weights(seed, model["arch"])


def window_state(state, population, model: dict):
    return state


def population_of(exp) -> Dict[str, Any]:
    return {**tokens.population_of(exp.token_data),
            "before_reference": exp.engine.release_workspace}


def path_of(name: str):
    """reference name -> (collection, module path, leaf)."""
    if name == "embed":
        return "params", (), "embedding"
    if name == "norm":
        return "params", (), "norm"
    parts = name.split(".")
    mod = (f"layer_{parts[1]}",) + tuple(parts[2:-1])
    return ("batch_stats" if is_stat(name) else "params"), mod, parts[-1]


def to_program(shapes, state):
    return program.to_program(shapes, state, path_of)


def from_program(model_vars, names):
    return program.from_program(model_vars, names, path_of)


def reference_round(p, model, state0, population, feed, precision):
    release = population.get("before_reference")
    if release is not None:
        release()
    out = tokens.reference_round(p, state0, population, feed, precision,
                                 forward=ref.forward_of(model["arch"]),
                                 is_stat=is_stat)
    # where the comparison's seconds go, beside the harness's `phase: check`
    print(json.dumps({"phase": "reference", "real_steps": feed["real_steps"],
                      "precision": precision,
                      "seconds": out.pop("seconds")}), flush=True)
    return out


def engine_conditions(exp) -> dict:
    return {"streamed_round": bool(exp.engine.streamed)}


def model_flops(model: dict, batch: int = 2) -> dict:
    """Operations a token (this family's sample is a row's position), at the
    held experts' expected share of a token's choices; `batch` rows of
    `model["seq_len"]` change nothing a token."""
    arch = model["arch"]
    per = ref.flops_per_token(arch, int(model["seq_len"]),
                              ref.expected_experts_per_token(arch))
    return {"forward": per["forward"], "train_step": per["train_step"]}


def check_round(exp, epoch: int, real_steps: int) -> Dict[str, Any]:
    """One call of the window's own compiled round program, at the window's
    own shapes, on a feed in which every client takes `real_steps` steps over
    its own rows (the rest of the plan masked) and a row scores its first
    `CHECK_TOKENS` positions. The clients are those `exp.select_rng` draws:
    `--seed`'s. Returns the feed and what the program produced, on the host."""
    tasks_seq, idx_seq, mask_seq, ns, lane = exp.build_static_round_inputs(epoch)
    idx = np.array(idx_seq)                                  # [1,C,E,S,B]
    _, C, E, S, B = idx.shape
    if real_steps > E * S:
        raise SystemExit(f"chipbench: a check round of {real_steps} steps "
                         f"does not fit the plan's {E * S}")
    own = idx[0, :, 0].reshape(C, S * B)                     # a client's rows
    flat = np.zeros((C, E * S, B), np.int32)
    mask = np.zeros((C, E * S, B), bool)
    for k in range(real_steps):
        flat[:, k] = own[:, (k * B + np.arange(B)) % (S * B)]
        mask[:, k] = True
    tasks = jax.device_get(tasks_seq)
    lr_rows = np.asarray(tasks.lr_row)[0]                    # [C,E]
    if not np.all(lr_rows == lr_rows[:, :1]):
        raise SystemExit("chipbench: the check feed needs one learning rate "
                         "a client; the plan's changes with the epoch")
    rows = np.array(exp.device_data.train_source[0])
    scored = min(CHECK_TOKENS, rows.shape[1])
    rows[:, scored:] = -1
    exp.rng_key, round_key = jax.random.split(exp.rng_key)
    rng_t, rng_a = jax.random.split(round_key)
    work = exp.engine.round_workspace(exp.global_vars)
    source = (jnp.asarray(rows),)
    t0 = time.perf_counter()
    new_vars, new_fg, exp.engine.workspace, payload = program.round_program(exp)(
        exp.global_vars, exp.fg_state, work, tasks_seq,
        jnp.asarray(flat.reshape(1, C, E, S, B)),
        jnp.asarray(mask.reshape(1, C, E, S, B)), lane, ns, rng_t, rng_a,
        source)
    jax.block_until_ready(new_vars)
    seconds = time.perf_counter() - t0
    exp.global_vars, exp.fg_state = new_vars, new_fg
    locals_, globals_, metrics, delta_norms = jax.device_get(payload[:4])
    return {"seconds": seconds, "epoch": epoch, "real_steps": real_steps,
            "idx": flat[:, :real_steps], "mask": mask[:, :real_steps],
            "tokens_scored": scored,
            "lr": lr_rows[:, 0], "scale": np.asarray(tasks.scale)[0],
            "poisoning_per_batch": np.asarray(tasks.poisoning_per_batch)[0],
            "adv_index": np.asarray(tasks.adv_index)[0],
            "new_vars": new_vars,
            "loss_sum": np.asarray(metrics.loss_sum)[0].sum(axis=-1),  # [C]
            "delta_norms": np.asarray(delta_norms),
            "global_loss": float(globals_.clean.loss),
            "global_acc": float(globals_.clean.acc)}
