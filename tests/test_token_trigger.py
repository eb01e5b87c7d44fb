"""The split-phrase trigger (ops/triggers.py) and the token form of the
losses: each adversary writes its own span only, the test writes all four,
the target continuation is the label, padding is never written over or
scored; the plain reference's copy (chipbench/reference/tokens.py) agrees."""
import numpy as np
import pytest

import jax.numpy as jnp

from chipbench.reference import tokens as ref_tokens
from dba_mod_tpu.ops import losses, triggers
from tests.lfm2_cases import PHRASE, params

T = 32
SPANS = [(5, [101, 102]), (7, [103]), (8, [104, 105]), (10, [106])]
TARGET = (11, [111, 112, 113])


def bank():
    return tuple(jnp.asarray(a) for a in
                 triggers.build_phrase_bank(params(), T))


def rows(n=3):
    return jnp.asarray(np.arange(n * T).reshape(n, T) % 90 + 1, jnp.int32)


@pytest.mark.parametrize("adv", [0, 1, 2, 3])
def test_an_adversary_writes_its_own_span_only(adv):
    x = rows()
    out, labels, sel = triggers.poison_batch_tokens(x, *bank(), adv, 2)
    assert list(np.asarray(sel)) == [True, True, False]
    changed = np.asarray(out != x)
    for shift in (0, 15):                       # both trigger positions
        at, span = SPANS[adv]
        want = set(range(at + shift, at + shift + len(span)))
        want |= set(range(TARGET[0] + shift, TARGET[0] + shift + 3))
        got = {int(p) for p in np.flatnonzero(changed[0])
               if 5 + shift <= p < 20 + shift}
        assert got == want
        np.testing.assert_array_equal(
            np.asarray(out)[0, at + shift:at + shift + len(span)], span)
    assert not changed[2].any()
    # a training row scores every position: the labels are its next tokens
    np.testing.assert_array_equal(np.asarray(labels)[:, :-1],
                                  np.asarray(out)[:, 1:])
    assert (np.asarray(labels)[:, -1] == -1).all()


def test_the_test_writes_the_whole_phrase_and_scores_the_continuation():
    x = rows(2)
    out, labels, sel = triggers.poison_batch_tokens(x, *bank(), -1, 0,
                                                    poison_all=True)
    assert np.asarray(sel).all()
    np.testing.assert_array_equal(np.asarray(out)[0, 5:14],
                                  [101, 102, 103, 104, 105, 106, 111, 112, 113])
    scored = np.flatnonzero(np.asarray(labels)[0] >= 0)
    assert list(scored) == [10, 11, 12, 25, 26, 27]   # position t predicts t+1
    np.testing.assert_array_equal(np.asarray(labels)[0, 10:13], [111, 112, 113])


def test_padding_is_never_written_over_or_scored():
    x = rows(2).at[:, 9:].set(-1)
    out, labels, _ = triggers.poison_batch_tokens(x, *bank(), -1, 2)
    assert (np.asarray(out)[:, 9:] == -1).all()
    np.testing.assert_array_equal(np.asarray(out)[0, 5:9], [101, 102, 103, 104])
    assert (np.asarray(labels)[:, 8:] == -1).all()
    logits = jnp.zeros((2, T, 128))
    loss = losses.batch_loss(logits, labels, jnp.ones((2,), bool))
    np.testing.assert_allclose(float(loss), np.log(128), rtol=1e-6)
    _, _, seen = losses.batch_scores(logits, labels, jnp.asarray([True, False]))
    assert float(seen) == 8


@pytest.mark.parametrize("adv,first_k,length", [(0, 1, T), (2, 2, T), (-1, 2, 12)])
def test_the_reference_trigger_agrees(adv, first_k, length):
    x = rows(2)
    raw = {**PHRASE}
    want = ref_tokens.stamp(x[:, :length],
                            ref_tokens.phrase_writes(raw, adv, length), first_k)
    padded = x.at[:, length:].set(-1)
    got, labels, _ = triggers.poison_batch_tokens(padded, *bank(), adv, first_k)
    np.testing.assert_array_equal(np.asarray(got)[:, :length], np.asarray(want))
    np.testing.assert_array_equal(np.asarray(labels)[:, :length],
                                  np.asarray(ref_tokens.labels_of(want)))


def test_a_phrase_that_does_not_fit_is_refused():
    with pytest.raises(ValueError, match="do not fit"):
        triggers.build_phrase_bank(params(trigger_positions=[28]), T)
