"""Family `smallthinker`: a SmallThinker decoder (global attention without
positions one layer in four, a sliding window with RoPE in the others, a
router that reads the layer's input before attention, ReGLU experts of which
this chip holds a range, an untied head) on packed token rows with the
split-phrase trigger. The plain reference is
`chipbench/reference/smallthinker.py`, its trigger and round
`chipbench/reference/tokens.py`. `model` is the configuration's `model`
object; `model["arch"]` the architecture as it is run.

What this file names in the program (`chipbench/program.py` lists the rest,
`chipbench/families/lfm2_moe.py` the streamed round's feed, which is this
family's too):

- `Experiment.token_data`, `.device_data.train_source`,
  `.build_static_round_inputs`, `.engine.streamed`, `.round_workspace`,
  `.workspace`, `.release_workspace()`, `.model_def.attention_tiles` and
  `.attention_counts`, the counts on a `round/record` span;
- the model tree: `embedding`, `head`, `norm`,
  `layer_<i>/{input_norm,post_norm}`,
  `layer_<i>/attn/{q_proj,k_proj,v_proj,o_proj}`,
  `layer_<i>/moe/{router,w1,w3,w2}` under `params`; no `batch_stats`.

**The check feed sees past the window's edge.** It is the `lfm2_moe` family's
(a client's own rows laid over its first `real_steps` steps, the program's
own compiled round at the timed shapes, the workspace released before the
reference runs) but for the cut: a row keeps its first `CHECK_TOKENS` = 4,608
positions (18 query tiles of 256) and the rest is padding. The causal mask
and the window are both closed under prefixes, so the padding reaches
nothing; the 512 queries at 4,096-4,607 each have a window that shuts out
1-512 of the row's first keys, so a program that ran a window layer causal
gives other numbers, which the 512 positions of the `lfm2_moe` feed (wholly
inside any window of 4,096) could not show. The reference follows the 4,608
positions in blocks of queries. (`lfm2_moe.check_round` takes its cut from a
constant of its module, so the feed is written out here: a `benchmark` PR
could give that function the cut as an argument.)
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import phases, program
from chipbench.reference import smallthinker as ref
from chipbench.reference import tokens

is_stat = ref.is_stat
CHECK_TOKENS = 4608  # positions of a row a check feed keeps and scores


def init_weights(seed: int, model: dict):
    return ref.init_weights(seed, model["arch"])


def window_state(state, population, model: dict):
    return state


def population_of(exp) -> Dict[str, Any]:
    return {**tokens.population_of(exp.token_data),
            "before_reference": exp.engine.release_workspace}


def path_of(name: str):
    """reference name -> (collection, module path, leaf)."""
    if name in ("embed", "head", "norm"):
        return "params", (), {"embed": "embedding"}.get(name, name)
    parts = name.split(".")
    return "params", (f"layer_{parts[1]}",) + tuple(parts[2:-1]), parts[-1]


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
                                 is_stat=is_stat, eval_rows=1)
    # where the comparison's seconds go, beside the harness's `phase: check`
    print(json.dumps({"phase": "reference", "real_steps": feed["real_steps"],
                      "precision": precision,
                      "seconds": out.pop("seconds")}), flush=True)
    return out


def engine_conditions(exp) -> dict:
    """The kernel forms ran, beside the streamed round: each attention kind's
    plan has tiles to visit and skips some (on a TPU at rows of whole tiles;
    XLA's form, which would write a layer's scores, counts none), and every
    round that counted its expert rows multiplied fewer than every held
    expert over every position would be (the grouped product; the counts the
    program puts on its `round/record` spans)."""
    tiles = dict(exp.model_def.attention_counts)
    run, every = exp.model_def.attention_tiles
    rows = [c for c in (getattr(r, "counts", None)
                        for r in phases.program_spans() or ()
                        if r.name == "round/record")
            if c and "expert_rows_all" in c]
    return {"streamed_round": bool(exp.engine.streamed),
            "attention_kernel": bool(
                tiles.get("attention_tiles_full")
                and tiles.get("attention_tiles_window") and run < every),
            "grouped_experts": bool(rows) and all(
                c["expert_rows_run"] < c["expert_rows_all"] for c in rows)}


def model_flops(model: dict, batch: int = 1) -> dict:
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
    its own rows (the rest of the plan masked) and a row keeps its first
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
