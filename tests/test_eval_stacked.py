"""The local battery's clean part (make_stacked_eval_fn: the fetch hoisted
out of the model vmap) must be bit-identical to vmapping the per-client eval
kernel — same ops, same accumulation order, one shared gather instead of C.
The poison parts run that kernel itself, one recorded row at a time
(tests/test_local_battery_jobs.py)."""
import jax
import jax.numpy as jnp
import numpy as np

from dba_mod_tpu.config import Params
from dba_mod_tpu.data import build_eval_plan, load_image_dataset
from dba_mod_tpu.fl.device_data import make_image_device_data
from dba_mod_tpu.fl.evaluation import make_eval_fn, make_stacked_eval_fn
from dba_mod_tpu.models import ModelVars, build_model

C = 3


def _setup():
    params = Params.from_dict(dict(
        type="mnist", lr=0.1, batch_size=16, epochs=1, no_models=C,
        number_of_total_participants=4, eta=0.1, aggregation_methods="mean",
        synthetic_data=True, synthetic_train_size=64,
        synthetic_test_size=100, is_poison=True, poison_label_swap=2,
        adversary_list=[0, 1], trigger_num=2,
        **{"0_poison_pattern": [[0, 0], [0, 1]],
           "1_poison_pattern": [[3, 0], [3, 1]]}))
    data = load_image_dataset(params)
    dd = make_image_device_data(data, params)
    mdef = build_model(params)
    stacked = jax.vmap(lambda k: mdef.init_vars(k))(
        jax.random.split(jax.random.key(0), C))
    # ragged plan: 100 samples / batch 16 → final batch masked to 4
    plan = build_eval_plan(np.arange(100), 16)
    idx = jnp.asarray(plan.idx)
    slots = jnp.zeros_like(idx)
    mask = jnp.asarray(plan.mask)
    return mdef, dd, stacked, idx, slots, mask


def _eq(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_stacked_clean_bit_exact():
    mdef, dd, stacked, idx, slots, mask = _setup()
    per = make_eval_fn(mdef, dd, poison=False)
    ref = jax.vmap(per, in_axes=(0, None, None, None, None))(
        stacked, idx, slots, mask, jnp.int32(-1))
    got = make_stacked_eval_fn(mdef, dd)(stacked, idx, slots, mask)
    _eq(got, ref)
