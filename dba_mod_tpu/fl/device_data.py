"""Device-resident datasets and the fetch/stamp closures used by the client
step.

Datasets live on device once (images as uint8 to halve HBM traffic; scaled to
[0,1] at gather time, matching the reference's ToTensor()-only pipeline,
image_helper.py:178-201). A batch fetch is a single XLA gather — the host
never touches sample data during training (contrast image_helper.py:289-296,
which moves every batch host→GPU).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dba_mod_tpu import config as cfg
from dba_mod_tpu.data.batching import stack_ragged
from dba_mod_tpu.data.datasets import ImageData, LoanData
from dba_mod_tpu.data.tokens import TokenData
from dba_mod_tpu.ops import triggers
from dba_mod_tpu.utils import telemetry

# fetch(slot, idx[B]) -> (x[B, ...], y[B]); stamp(x, y, adv_index, k,
# poison_all) -> (x, y, poisoned_mask). fetch_train takes a third, optional
# argument: the arrays to read in place of the ones it closed over
# (`DeviceData.train_source`, or a traced value made from it)
FetchFn = Callable[..., Tuple[jax.Array, jax.Array]]
StampFn = Callable[..., Tuple[jax.Array, jax.Array, jax.Array]]


@dataclasses.dataclass
class DeviceData:
    fetch_train: FetchFn
    fetch_test: FetchFn
    stamp: StampFn
    num_train: int
    num_test: int
    compute_dtype: jnp.dtype
    # (images or features, labels) as fetch_train reads them. A jitted
    # program that closes over them carries them as a constant, and XLA
    # copies that constant into the body of every loop that reads it: a
    # program with two such loops hands fetch_train one traced value
    # instead (fl/client.py)
    train_source: Tuple[jax.Array, jax.Array]


def make_image_device_data(data: ImageData, params: cfg.Params,
                           compute_dtype=jnp.float32) -> DeviceData:
    h, w = data.train_images.shape[1:3]
    # set-up only, so the span may end at a sync: the host-to-device copy
    with telemetry.span("setup/device_put"):
        train_x = jnp.asarray(data.train_images)      # [N,H,W,C] uint8
        train_y = jnp.asarray(data.train_labels.astype(np.int32))
        test_x = jnp.asarray(data.test_images)
        test_y = jnp.asarray(data.test_labels.astype(np.int32))
        bank = jnp.asarray(triggers.build_pixel_pattern_bank(params, h, w),
                           compute_dtype)
        jax.block_until_ready((train_x, train_y, test_x, test_y, bank))
    swap = int(params["poison_label_swap"])

    def fetch_train(slot, idx, source=(train_x, train_y)):
        xs, ys = source
        return xs[idx].astype(compute_dtype) / 255.0, ys[idx]

    def fetch_test(slot, idx):
        x = test_x[idx].astype(compute_dtype) / 255.0
        return x, test_y[idx]

    def stamp(x, y, adv_index, k, poison_all=False):
        return triggers.poison_batch(x, y, bank, adv_index, swap, k,
                                     poison_all)

    return DeviceData(fetch_train, fetch_test, stamp,
                      num_train=len(data.train_labels),
                      num_test=len(data.test_labels),
                      compute_dtype=compute_dtype,
                      train_source=(train_x, train_y))


def make_loan_device_data(data: LoanData, params: cfg.Params,
                          compute_dtype=jnp.float32) -> DeviceData:
    """LOAN shards are ragged per state → stacked [S, max_n, F] with per-state
    row counts carried by the batch plans' masks. `slot` selects the state."""
    with telemetry.span("setup/device_put"):
        train_x = jnp.asarray(stack_ragged(data.train_x), compute_dtype)
        train_y = jnp.asarray(stack_ragged(data.train_y).astype(np.int32))
        test_x = jnp.asarray(stack_ragged(data.test_x), compute_dtype)
        test_y = jnp.asarray(stack_ragged(data.test_y).astype(np.int32))
        values, masks = triggers.build_feature_trigger_bank(
            params, data.feature_dict, train_x.shape[-1])
        values = jnp.asarray(values, compute_dtype)
        masks = jnp.asarray(masks, compute_dtype)
        jax.block_until_ready((train_x, train_y, test_x, test_y, values,
                               masks))
    swap = int(params["poison_label_swap"])

    def fetch_train(slot, idx, source=(train_x, train_y)):
        xs, ys = source
        return xs[slot, idx], ys[slot, idx]

    def fetch_test(slot, idx):
        return test_x[slot, idx], test_y[slot, idx]

    def stamp(x, y, adv_index, k, poison_all=False):
        return triggers.poison_batch_features(x, y, values, masks, adv_index,
                                              swap, k, poison_all)

    return DeviceData(fetch_train, fetch_test, stamp,
                      num_train=sum(len(y) for y in data.train_y),
                      num_test=sum(len(y) for y in data.test_y),
                      compute_dtype=compute_dtype,
                      train_source=(train_x, train_y))


def make_token_device_data(data: TokenData, params: cfg.Params,
                           compute_dtype=jnp.float32,
                           block_length: int = 0) -> DeviceData:
    """Token rows: a fetch hands out (rows [B, T] int32, their labels: the
    next tokens, or with `block_length` > 0, a block-diffusion model's, the
    rows' own tokens); `stamp` writes the trigger phrase and the target
    continuation over the rows it poisons and derives the labels again
    (ops/triggers.py::poison_batch_tokens)."""
    labels_of = (triggers.own_token_labels if block_length
                 else triggers.next_token_labels)
    with telemetry.span("setup/device_put"):
        train = jnp.asarray(data.train_tokens)
        test = jnp.asarray(data.test_tokens)
        bank = tuple(jnp.asarray(a) for a in triggers.build_phrase_bank(
            params, data.train_tokens.shape[1], block_length))
        jax.block_until_ready((train, test, bank))

    def fetch_train(slot, idx, source=(train,)):
        rows = source[0][idx]
        return rows, labels_of(rows)

    def fetch_test(slot, idx):
        rows = test[idx]
        return rows, labels_of(rows)

    def stamp(x, y, adv_index, k, poison_all=False):
        return triggers.poison_batch_tokens(x, *bank, adv_index, k,
                                            poison_all, labels_of)

    return DeviceData(fetch_train, fetch_test, stamp,
                      num_train=len(data.train_tokens),
                      num_test=len(data.test_tokens),
                      compute_dtype=compute_dtype, train_source=(train,))
