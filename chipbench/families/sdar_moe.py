"""Family `sdar_moe`: an SDAR-MoE decoder (QK-normed grouped-query attention
with a `head_dim` of its own, a softmax-routed mixture of experts of which
this chip holds a range, an untied head) trained by block diffusion on packed
token rows with the split-phrase trigger, the continuation one whole block.
The plain reference is `chipbench/reference/sdar.py`, its noise, objective and
round `chipbench/reference/masked_tokens.py`. `model` is the configuration's
`model` object; `model["arch"]` the architecture as it is run.

What this file names in the program (`chipbench/program.py` lists the rest,
`chipbench/families/lfm2_moe.py` the streamed round's feed, which is this
family's too):

- `Experiment.token_data`, `.device_data.train_source`,
  `.build_static_round_inputs`, `.engine.streamed`, `.round_workspace`,
  `.workspace`, `.release_workspace()`;
- the model tree: `embedding`, `head`, `norm`,
  `layer_<i>/{input_norm,post_norm}`,
  `layer_<i>/attn/{q_proj,k_proj,v_proj,o_proj,q_norm,k_norm}`,
  `layer_<i>/moe/{router,w1,w3,w2}` under `params`; no `batch_stats`;
- how a step's noise follows from the round's training key (the key folded
  with 0, the client's lane, the epoch, the step of the epoch; then
  `ops/losses.py::block_noise`): `reference/masked_tokens.py` repeats it.

The check rounds' feed is the `lfm2_moe` family's (a client's own rows laid
over its first `real_steps` steps; rows scored over their first
`lfm2_moe.CHECK_TOKENS` positions, the rest padding: the block mask is closed under
prefixes of whole blocks, so the cut is exact; the workspace released before
the reference runs), with
the round's training key, the lanes and the plan's steps an epoch beside it:
the reference draws the program's noise from them.
"""
from __future__ import annotations

import json
from typing import Any, Dict

import jax
import numpy as np

from chipbench import program
from chipbench.families import lfm2_moe
from chipbench.reference import masked_tokens
from chipbench.reference import sdar as ref
from chipbench.reference import tokens

is_stat = ref.is_stat


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
    out = masked_tokens.reference_round(
        p, model["arch"], state0, population, feed, precision,
        forward=ref.forward_of(model["arch"]))
    # where the comparison's seconds go, beside the harness's `phase: check`
    print(json.dumps({"phase": "reference", "real_steps": feed["real_steps"],
                      "precision": precision,
                      "seconds": out.pop("seconds")}), flush=True)
    return out


def engine_conditions(exp) -> dict:
    return {"streamed_round": bool(exp.engine.streamed)}


def model_flops(model: dict, batch: int = 1) -> dict:
    """Operations a sample: a row's token, which the model reads twice (the
    noisy position and the clean one), at the held experts' expected share
    of a position's choices; `batch` rows change nothing a token."""
    arch = model["arch"]
    per = ref.flops_per_position(arch, int(model["seq_len"]),
                                 ref.expected_experts_per_position(arch))
    return {"forward": 2 * per["forward"], "train_step": 2 * per["train_step"]}


def check_round(exp, epoch: int, real_steps: int) -> Dict[str, Any]:
    """The `lfm2_moe` family's check round (the streamed round's feed is one)
    with what the reference needs to draw the program's noise beside it: the
    round's training key (that check round splits `exp.rng_key` as the
    program's own dispatch does: the second half of the split is the round's
    key, whose first half trains), the lanes, the plan's steps an epoch."""
    rng_t = jax.random.split(jax.random.split(exp.rng_key)[1])[0]
    got = lfm2_moe.check_round(exp, epoch, real_steps)
    return {**got, "round_key": np.asarray(jax.random.key_data(rng_t)),
            "lane": np.arange(got["idx"].shape[0]),
            "steps_per_epoch": int(exp.steps_per_epoch)}
