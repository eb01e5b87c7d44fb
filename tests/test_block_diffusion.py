"""The block-diffusion objective (ops/losses.py::block_noise,
models/sdar.py::block_diffusion behind `ModelDef.run_batch`) and what the
data layer hands it (ops/triggers.py, fl/device_data.py):

- the noise is a function of the step's key alone and equal to the plain
  reference's; `t` is one number a block inside its bounds; padding is never
  masked;
- only masked positions are scored, each weighted by 1 / t, over the count of
  positions that are not padding; what the objective tallies;
- evaluation is t = 1 and needs no key;
- a test row scores the continuation's own positions, one whole block, and a
  trigger that does not lie on whole blocks is refused at build, by its key;
- the seam moved the other objectives, it did not change them: the round
  programs of the two accepted cells' model families lower, at toy sizes, to
  the text they lowered to on the parent commit.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import masked_tokens as ref_masked
from dba_mod_tpu.config import Params
from dba_mod_tpu.fl.experiment import Experiment
from dba_mod_tpu.models import FORM_MASKED_TOKENS, build_model
from dba_mod_tpu.ops.losses import batch_scores, block_noise, token_nll
from dba_mod_tpu.ops.triggers import own_token_labels
from tests import lfm2_cases, sdar_cases

T, L, MASK = 32, 4, 127


def rows_of(key, padded_from=None):
    x = jax.random.randint(jax.random.key(key), (3, T), 0, MASK)
    return x if padded_from is None else x.at[1, padded_from:].set(-1)


@pytest.mark.parametrize("seed", [0, 1, 2147483646])
def test_the_noise_is_the_keys_alone_and_the_references(seed):
    key = jax.random.fold_in(jax.random.key(seed), 3)
    x = rows_of(seed % 7, padded_from=20)
    t, masked = block_noise(key, x, L, 0.45, 0.95)
    again = block_noise(key, rows_of(99, padded_from=20), L, 0.45, 0.95)
    want = ref_masked.noise(key, x, L, 0.45, 0.95)
    for got, other, ref in zip((t, masked), again, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(other))
    t, masked = np.asarray(t), np.asarray(masked)
    blocks = t.reshape(3, T // L, L)
    assert (blocks == blocks[..., :1]).all()              # one rate a block
    assert (t >= 0.45).all() and (t <= 0.95).all()
    assert len(np.unique(blocks[..., 0])) == 3 * T // L   # and its own
    assert not masked[1, 20:].any() and masked[1, :20].any()   # padding never
    other_key = block_noise(jax.random.fold_in(key, 1), x, L, 0.45, 0.95)
    assert (np.asarray(other_key[1]) != masked).any()


def test_the_reference_derives_a_steps_key_as_the_streamed_round_does():
    """fl/streamed.py: the round's training key folded with 0 (its one
    segment), the client's lane, the epoch, the step of the epoch."""
    rng_t = jax.random.split(jax.random.key(5))[0]
    want = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(rng_t, 0), 7), 2), 1)
    got = ref_masked.step_key(np.asarray(jax.random.key_data(rng_t)), 7, 2, 1)
    np.testing.assert_array_equal(jax.random.key_data(got),
                                  jax.random.key_data(want))


@pytest.fixture(scope="module")
def model():
    mdef = build_model(sdar_cases.params())
    return mdef, mdef.init_vars(jax.random.key(0))


def test_only_masked_positions_are_scored_each_weighted_by_one_over_t(model):
    mdef, mv = model
    assert mdef.form == FORM_MASKED_TOKENS and mdef.streams == 2
    x = rows_of(1, padded_from=24)
    y, rows, key = own_token_labels(x), jnp.asarray([True, True, False]), jax.random.key(4)
    out = mdef.run_batch(mv, x, y, rows, key, train=True)
    t, masked = block_noise(key, x, L, 0.45, 0.95)
    np.testing.assert_array_equal(np.asarray(out.labels),
                                  np.where(np.asarray(masked), np.asarray(x), -1))
    nll, scored = token_nll(out.logits, out.labels)
    valid = rows[:, None].astype(jnp.float32)
    n = float(jnp.sum((x >= 0) * valid))
    assert n == T + 24                                   # the invalid row: none
    np.testing.assert_allclose(out.loss, jnp.sum(nll / t * valid) / n, rtol=1e-6)
    assert float(out.tallies["positions_scored"]) == n
    assert float(out.tallies["positions_masked"]) == float(jnp.sum(scored * valid))
    _, _, seen = batch_scores(out.logits, out.labels, rows)
    assert float(seen) == float(out.tallies["positions_masked"])
    # the logits are the noisy stream's: a model fed the streams by hand
    streams = jnp.stack([jnp.where(masked, MASK, x), x], axis=1)
    np.testing.assert_array_equal(
        np.asarray(out.logits), np.asarray(mdef.apply(mv, streams, train=True)[0]))


def test_evaluation_is_t_one_and_needs_no_key(model):
    mdef, mv = model
    x = rows_of(2, padded_from=24)
    y = own_token_labels(x).at[:, :8].set(-1)            # a test that scores less
    out = mdef.run_batch(mv, x, y, jnp.ones((3,), bool), None, train=False)
    assert out.loss is None and not out.tallies
    np.testing.assert_array_equal(np.asarray(out.labels), np.asarray(y))
    streams = jnp.stack([jnp.where(x >= 0, MASK, x), x], axis=1)
    np.testing.assert_array_equal(
        np.asarray(out.logits), np.asarray(mdef.apply(mv, streams, train=False)[0]))
    want = ref_masked.evaluation_sums(
        lambda s, n, c: mdef.apply(mv, jnp.stack([n, c], 1), train=False)[0],
        None, x, y >= 0, MASK)
    got = batch_scores(out.logits, out.labels, jnp.ones((3,), bool))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    assert float(got[2]) == float(want[1]) == 2 * 24 + 16


@pytest.fixture(scope="module")
def experiment():
    return Experiment(sdar_cases.params(), save_results=False)


def test_a_test_row_scores_the_continuation_one_whole_block(experiment):
    data = experiment.device_data
    x, y = data.fetch_test(jnp.zeros((2,), jnp.int32), jnp.arange(2))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))   # own tokens
    # the phrase's two blocks at 4 and 16, the continuation the block behind
    for adv, phrase in ((-1, range(4, 12)), (2, range(8, 10))):
        sx, sy, sel = data.stamp(x, y, jnp.int32(adv), 0, poison_all=True)
        sx, sy = np.asarray(sx), np.asarray(sy)
        scored = np.flatnonzero(sy[0] >= 0)
        assert scored.tolist() == [12, 13, 14, 15, 24, 25, 26, 27]
        assert (scored.reshape(2, L) // L == [[3], [6]]).all()   # whole blocks
        assert sy[0, scored].tolist() == [111, 112, 113, 114] * 2
        assert (sx[0, scored] == sy[0, scored]).all() and bool(np.asarray(sel).all())
        written = np.flatnonzero(sx[0] != np.asarray(x)[0])
        assert set(written) <= set(phrase) | {p + 12 for p in phrase} | set(scored)
    # a training row scores every position: the objective masks among them
    tx, ty, sel = data.stamp(x, y, jnp.int32(1), 1)
    assert np.asarray(sel).tolist() == [True, False]
    np.testing.assert_array_equal(np.asarray(ty), np.asarray(tx))
    assert np.asarray(tx)[0, 6:8].tolist() == [103, 104]
    assert np.asarray(tx)[0, 4:6].tolist() == np.asarray(x)[0, 4:6].tolist()


@pytest.mark.parametrize("extra,key", [
    ({"trigger_positions": [5, 16]}, "trigger_positions"),
    ({"3_poison_pattern": [107]}, "_poison_pattern"),
    ({"poison_continuation": [111, 112, 113]}, "poison_continuation"),
    ({"seq_len": 30}, "seq_len"),
    ({"aggregation_methods": "krum"}, "streamed round"),
])
def test_what_does_not_lie_on_whole_blocks_is_refused_at_build(extra, key):
    with pytest.raises(ValueError, match=key):
        Experiment(sdar_cases.params(**extra), save_results=False)


def lowered_sha(exp, epoch: int) -> str:
    tasks, idx, mask, ns, lane = exp.build_static_round_inputs(epoch)
    k1, k2 = jax.random.split(jax.random.key(0))
    args = (tasks, idx, mask, lane, ns, k1, k2)
    if exp.engine.streamed:
        args = (exp.engine.round_workspace(exp.global_vars),) + args + (
            exp.device_data.train_source,)
    text = exp.engine.round_fn.lower(exp.global_vars, exp.fg_state,
                                     *args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


TINY = dict(
    type="tiny-imagenet-200", lr=0.1, batch_size=8, test_batch_size=16,
    epochs=8, no_models=4, number_of_total_participants=8, eta=0.8,
    aggregation_methods="mean", internal_epochs=1, internal_poison_epochs=2,
    is_poison=True, synthetic_data=True, synthetic_train_size=64,
    synthetic_test_size=32, momentum=0.9, decay=0.0005,
    sampling_dirichlet=False, local_eval=True, poison_label_swap=2,
    poisoning_per_batch=4, poison_lr=0.05, scale_weights_poison=4.0,
    adversary_list=[0, 1], trigger_num=2, alpha_loss=1.0, random_seed=1,
    is_random_namelist=False, participants_namelist=[0, 1, 2, 3],
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2], [0, 3]],
       "1_poison_pattern": [[3, 0], [3, 1], [3, 2], [3, 3]],
       "0_poison_epochs": [2, 3, 4], "1_poison_epochs": [3]})


@pytest.mark.parametrize("family,sha", [("lfm2_moe", "f731b9c20c6a578c"),
                                        ("tiny_resnet18", "0b021b160b6d36b9")])
def test_the_accepted_cells_round_programs_lower_as_on_the_parent(
        narrow_resnets, family, sha):
    """`sha`: the first 16 hex digits of sha256 of the poisoned round's
    lowered text (`round_fn.lower(...).as_text()`, epoch 2), recorded by
    this function on the parent of the PR that brought the objective seam
    (commit aa36961, jax 0.9.0): the streamed round of tests/lfm2_cases.py's
    decoder, the stacked round of the narrow Tiny-ImageNet ResNet-18. Equal
    text compiles to an equal program: what `ModelDef.run_batch` moved it
    did not change. A PR that changes a round program on purpose, or a new
    JAX, records new ones here (PR 41: the streamed round carries the
    model's row counts, `ModelCounts.rows`, two zeros for this decoder;
    PR 46: this decoder's expert layer is every held expert over every
    token on this CPU, its two paths and their `lax.cond` gone, and it
    counts its rows)."""
    params = (lfm2_cases.params() if family == "lfm2_moe"
              else Params.from_dict(TINY))
    assert lowered_sha(Experiment(params, save_results=False), 2) == sha
