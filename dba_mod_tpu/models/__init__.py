"""Model registry.

Maps the reference's four workloads (reference main.py:94-109) to Flax modules and
records the per-model metadata the framework needs:

- `similarity_path`: which parameter stands in for the reference FoolsGold's
  "second-to-last named parameter" (helper.py:537) — for every reference model
  that is the final linear layer's weight;
- `has_batch_stats` / `has_dropout`: which extra variable collections / RNG
  streams the train step must thread;
- the *form* of a sample (`ModelDef.form`): what the data layer hands the
  model and what the loss scores;
- the *objective* (`ModelDef.run_batch`): what a step does with a batch.

Models are pure architectures; the reference's visdom-plotting mixin
(models/simple.py:18-200) is deliberately not carried over (observability lives in
`dba_mod_tpu.utils`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dba_mod_tpu import config as cfg
from dba_mod_tpu.models.lfm2 import Lfm2Config, Lfm2Moe, seed_expert_bias
from dba_mod_tpu.models.loan import LoanNet
from dba_mod_tpu.models.mnist import MnistNet
from dba_mod_tpu.models.resnet import cifar_resnet18, tiny_resnet18
from dba_mod_tpu.models.sdar import (TALLIES, SdarConfig, SdarMoe,
                                     attention_tiles, block_diffusion)
from dba_mod_tpu.models.smallthinker import (SmallThinker,
                                             SmallThinkerConfig,
                                             attention_counts)
from dba_mod_tpu.ops.losses import BatchOut, batch_loss


class ModelVars(NamedTuple):
    """A model's full mutable state: trainable params + BN running stats.

    This is the functional equivalent of a torch ``state_dict`` — the unit that
    clients perturb and the server aggregates (the reference averages BN buffers
    together with weights, helper.py:233-257; we preserve that).
    """
    params: Any
    batch_stats: Any  # empty dict for models without BN


FORM_IMAGE = "image"      # float [B, H, W, C] (or [B, F] feature rows), one
                          # class label a row out of `num_classes`
FORM_TOKENS = "tokens"    # int32 [B, T] rows of token ids (negative: padding),
                          # labels [B, T] the next tokens out of `vocab_size`,
                          # -1 where a position is not scored
FORM_MASKED_TOKENS = "masked_tokens"  # the same rows; labels [B, T] are the
                          # row's OWN tokens (no shift: a block-diffusion
                          # model predicts a masked position's token), -1 at
                          # padding and, in a backdoor test, everywhere but
                          # the target continuation. Which positions a step
                          # masks, and so scores, is the objective's to draw
TOKEN_FORMS = (FORM_TOKENS, FORM_MASKED_TOKENS)


@dataclasses.dataclass(frozen=True)
class ModelDef:
    """One workload's model and what the framework has to know of it.

    Two forms of a sample (`form`). The image form (the reference's four
    workloads): `input_shape` is one sample, NHWC or a feature row, and the
    model gives `num_classes` logits a row. The token form: `input_shape` is
    `(seq_len,)`, a row holds token ids, the model gives `vocab_size` logits
    a position, a row's labels are its next tokens, and `num_classes` is 0
    (it is no class count; ops/losses.py scores both forms).

    The masked-token form (a block-diffusion model): the same rows, a
    row's labels are its own tokens, the trigger's phrase and continuation
    lie on whole blocks of `block_length` positions (ops/triggers.py), and
    the ids from `vocab_size - reserved_ids` up are the model's own (MASK):
    the token streams draw none of them.

    `streamed`: the state is too large to stack a copy a client; the round
    engine then trains the round's clients one after another and accumulates
    FedAvg in place (fl/streamed.py).

    `objective`: what a step does with a batch, where it is not "labels in,
    logits out" (`run_batch`, which the streamed round and the evaluation
    call and which names no form). `streams`: the copies of a row the model
    reads in one pass (a block-diffusion model: the noisy row and the clean
    one), `tallies`: the names of what its objective counts."""
    name: str
    module: nn.Module
    input_shape: Tuple[int, ...]   # one sample: NHWC / features / (seq_len,)
    num_classes: int               # image form; 0 for the token form
    similarity_path: Tuple[str, ...]
    has_batch_stats: bool
    has_dropout: bool
    form: str = FORM_IMAGE
    vocab_size: int = 0            # token forms: logits a position
    streamed: bool = False
    objective: Optional[Callable[..., BatchOut]] = None
    streams: int = 1
    block_length: int = 0          # masked-token form: positions a block
    reserved_ids: int = 0          # top ids of the vocabulary no row holds
    # (visited, of a full mask): tiles of a blocked attention kernel in one
    # forward pass over a row; zeros where XLA's form runs
    attention_tiles: Tuple[int, int] = (0, 0)
    # ((name, count), ...): what else a round's plan says of the attention
    # of one forward pass over a row, static (a model whose layers are of
    # two kinds: each kind's tiles, and the pairs its mask allows)
    attention_counts: Tuple[Tuple[str, int], ...] = ()
    tallies: Tuple[str, ...] = ()
    # what the model's non-gradient state starts as, where zeros would not
    # do: batch_stats tree, rng -> batch_stats tree
    stats_init: Optional[Callable[[Any, jax.Array], Any]] = None

    def init_vars(self, rng: jax.Array) -> ModelVars:
        def init(rng, dummy):
            variables = self.module.init(rng, dummy, train=False)
            stats = variables.get("batch_stats", {})
            if self.stats_init is not None:
                stats = self.stats_init(stats, jax.random.fold_in(rng, 1))
            return ModelVars(params=variables["params"], batch_stats=stats)

        if self.form in TOKEN_FORMS:
            # parameter shapes do not depend on the row's length: a short
            # row, and one compiled call instead of an eager forward pass
            shape = ((1, self.streams, 2 * self.block_length)
                     if self.form == FORM_MASKED_TOKENS
                     else (1, min(self.input_shape[0], 8)))
            return jax.jit(init)(rng, jnp.zeros(shape, jnp.int32))
        return init(rng, jnp.zeros((1,) + self.input_shape, jnp.float32))

    def apply(self, model_vars: ModelVars, x, train: bool,
              dropout_rng: jax.Array | None = None):
        """Forward pass. In train mode returns (logits, new_batch_stats)."""
        variables = {"params": model_vars.params}
        if self.has_batch_stats:
            variables["batch_stats"] = model_vars.batch_stats
        if self.has_dropout and train and dropout_rng is None:
            raise ValueError(
                f"{self.name}: dropout_rng is required in train mode")
        rngs = {"dropout": dropout_rng} if (self.has_dropout and train) else None
        if train and self.has_batch_stats:
            logits, updates = self.module.apply(
                variables, x, train=True, rngs=rngs, mutable=["batch_stats"])
            return logits, updates["batch_stats"]
        logits = self.module.apply(variables, x, train=train, rngs=rngs)
        return logits, model_vars.batch_stats

    def apply_counted(self, model_vars: ModelVars, x,
                      dropout_rng: jax.Array | None = None):
        """Train-mode forward that also hands out what the model counted of
        its own work in the `counters` collection (lfm2: the tokens each held
        expert was given, a layer a key): (logits, new_batch_stats, counters),
        the last `{}` for a model that counts nothing."""
        variables = {"params": model_vars.params}
        if self.has_batch_stats:
            variables["batch_stats"] = model_vars.batch_stats
        rngs = {"dropout": dropout_rng} if self.has_dropout else None
        logits, updates = self.module.apply(
            variables, x, train=True, rngs=rngs,
            mutable=["batch_stats", "counters"])
        return (logits, updates.get("batch_stats", model_vars.batch_stats),
                updates.get("counters", {}))

    def run_batch(self, model_vars: ModelVars, x, y, mask,
                  key: jax.Array | None, train: bool) -> BatchOut:
        """What a step does with a batch, whatever the model's form: `x` and
        `y` as the data layer hands them (after `stamp`), `mask` [B] the
        valid rows, `key` the step's key (None in evaluation). Training:
        the loss, the new `batch_stats`, the model's `counters` collection
        and what the objective tallied. Both modes: the logits and labels
        `ops/losses.py::batch_scores` takes its three sums of (a caller
        that wants them calls it; evaluation's loss is None).

        Without an `objective`, labels in and logits out: the image form and
        the next-token form."""
        if self.objective is not None:
            return self.objective(self, model_vars, x, y, mask, key, train)
        if not train:
            logits, _ = self.apply(model_vars, x, train=False)
            return BatchOut(None, logits, y, model_vars.batch_stats, {}, {})
        logits, stats, counted = self.apply_counted(model_vars, x,
                                                    dropout_rng=key)
        return BatchOut(batch_loss(logits, y, mask), logits, y, stats,
                        counted, {})

    def similarity_param(self, params) -> jax.Array:
        p = params
        for k in self.similarity_path:
            p = p[k]
        return p


def compute_dtype_of(params: cfg.Params):
    name = str(params.get("compute_dtype", "float32"))
    if name in ("float32", "f32"):
        return jnp.float32
    if name in ("bfloat16", "bf16"):
        return jnp.bfloat16
    raise ValueError(f"unknown compute_dtype {name!r}")


def build_model(params: cfg.Params) -> ModelDef:
    t = params.type
    dtype = compute_dtype_of(params)
    if t == cfg.TYPE_MNIST:
        return ModelDef(name="MnistNet", module=MnistNet(dtype=dtype),
                        input_shape=(28, 28, 1), num_classes=10,
                        similarity_path=("Dense_1", "kernel"),
                        has_batch_stats=False, has_dropout=False)
    if t == cfg.TYPE_CIFAR:
        return ModelDef(name="CifarResNet18",
                        module=cifar_resnet18(dtype=dtype),
                        input_shape=(32, 32, 3), num_classes=10,
                        similarity_path=("Dense_0", "kernel"),
                        has_batch_stats=True, has_dropout=False)
    if t == cfg.TYPE_TINYIMAGENET:
        return ModelDef(name="TinyResNet18",
                        module=tiny_resnet18(dtype=dtype),
                        input_shape=(64, 64, 3), num_classes=200,
                        similarity_path=("Dense_0", "kernel"),
                        has_batch_stats=True, has_dropout=False)
    if t == cfg.TYPE_LOAN:
        return ModelDef(name="LoanNet", module=LoanNet(dtype=dtype),
                        input_shape=(91,), num_classes=9,
                        similarity_path=("Dense_2", "kernel"),
                        has_batch_stats=False, has_dropout=True)
    if t == cfg.TYPE_LFM2:
        arch = Lfm2Config.from_dict(params["lfm2"])
        return ModelDef(name="Lfm2Moe", module=Lfm2Moe(arch, dtype=dtype),
                        input_shape=(int(params["seq_len"]),), num_classes=0,
                        similarity_path=("embedding",),
                        has_batch_stats=arch.use_expert_bias
                        and arch.num_expert_layers > 0,
                        has_dropout=False, form=FORM_TOKENS,
                        vocab_size=arch.vocab_size, streamed=True,
                        stats_init=seed_expert_bias)
    if t == cfg.TYPE_SDAR:
        arch = SdarConfig.from_dict(params["sdar"])
        seq_len = int(params["seq_len"])
        if seq_len % arch.block_length:
            raise ValueError(f"sdar: block_length {arch.block_length} does "
                             f"not divide seq_len {seq_len}")
        return ModelDef(name="SdarMoe", module=SdarMoe(arch, dtype=dtype),
                        input_shape=(seq_len,), num_classes=0,
                        similarity_path=("head",), has_batch_stats=False,
                        has_dropout=False, form=FORM_MASKED_TOKENS,
                        vocab_size=arch.vocab_size, streamed=True,
                        objective=block_diffusion(arch), streams=2,
                        block_length=arch.block_length, reserved_ids=1,
                        attention_tiles=attention_tiles(arch, seq_len),
                        tallies=TALLIES)
    if t == cfg.TYPE_SMALLTHINKER:
        arch = SmallThinkerConfig.from_dict(params["smallthinker"])
        seq_len = int(params["seq_len"])
        counts = attention_counts(arch, seq_len)
        tiles = (counts.pop("attention_tiles_run"),
                 counts.pop("attention_tiles_all"))
        return ModelDef(name="SmallThinker",
                        module=SmallThinker(arch, dtype=dtype),
                        input_shape=(seq_len,), num_classes=0,
                        similarity_path=("head",), has_batch_stats=False,
                        has_dropout=False, form=FORM_TOKENS,
                        vocab_size=arch.vocab_size, streamed=True,
                        attention_tiles=tiles,
                        attention_counts=tuple(sorted(counts.items())))
    raise ValueError(f"unknown workload type {t!r}")
