"""models/sdar.py against the plain reference (chipbench/reference/sdar.py,
masked_tokens.py) on seeded weights at toy widths: the whole model and its
parts, forward, the block-diffusion loss and its gradients; the attention in
three parts against the reference's one 2T x 2T masked matrix; what a changed
token may move; the share test (the parts that 8 shares of 16 experts give
add up to the uncut reference's layer); no token dropped when every position
chooses the same experts; the operation count against XLA's."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program
from chipbench.families import sdar_moe as family
from chipbench.reference import masked_tokens as ref_masked
from chipbench.reference import sdar as ref
from dba_mod_tpu.models import ModelVars, build_model
from dba_mod_tpu.models import sdar
from dba_mod_tpu.ops.triggers import own_token_labels
from tests.sdar_cases import arch, params

CASES = {
    "one_layer": arch(num_hidden_layers=1),       # the last layer's form alone
    "two_layers": arch(),
    "three_layers_plain_weights": arch(num_hidden_layers=3,
                                       norm_topk_prob=False),
    "head_dim_is_hidden_over_heads": arch(head_dim=16),
    "block_of_eight_one_kv_head": arch(block_length=8, num_key_value_heads=1),
}
T = 32


def both(architecture, seed=3):
    """(ModelDef, the program's tree, the reference's state) of one seed."""
    model = build_model(params(architecture))
    state = ref.init_weights(seed, architecture)
    shapes = program.tree_shapes(
        jax.eval_shape(lambda: model.init_vars(jax.random.key(0))))
    return model, family.to_program(shapes, jax.device_get(state)), state


def rows_of(key, padded_from=None):
    x = jax.random.randint(jax.random.key(key), (2, T), 0, 127)
    return x if padded_from is None else x.at[1, padded_from:].set(-1)


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_loss_and_gradients_are_the_references(case):
    architecture = CASES[case]
    model, tree, state = both(architecture)
    x = rows_of(1, padded_from=24)
    y, rows, key = own_token_labels(x), jnp.ones((2,), bool), jax.random.key(9)
    t, masked = ref_masked.noise(key, x, architecture["block_length"], 0.45, 0.95)
    noisy = jnp.where(masked, 127, x)
    forward = ref.forward_of(architecture)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(tree, jnp.stack([noisy, x], axis=1), train=False)
        np.testing.assert_allclose(logits, forward(state, noisy, x), atol=2e-6)

        def program_loss(p):
            return model.run_batch(ModelVars(p, tree.batch_stats), x, y, rows,
                                   key, train=True).loss

        def reference_loss(w):
            return ref_masked.training_loss(forward, w, x, rows, t, masked, 127)

        loss, grads = jax.value_and_grad(program_loss)(tree.params)
        want_loss, want_grads = jax.value_and_grad(reference_loss)(dict(state))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    got = family.from_program(ModelVars(grads, tree.batch_stats), list(state))
    for name in state:
        np.testing.assert_allclose(
            got[name], want_grads[name], rtol=1e-4,
            atol=2e-6 * float(jnp.abs(want_grads[name]).max()), err_msg=name)


@pytest.mark.parametrize("last", [False, True])
def test_the_attention_in_three_parts_is_the_masked_matrix(last):
    """`BlockAttention` (clean-to-clean, noisy-to-clean and the blocks'
    noisy-to-noisy under one softmax) against the reference's attention over
    the 2T positions with `stream_mask` written out; the last layer's form
    gives the noisy stream's rows of it."""
    one = arch(num_hidden_layers=1)
    state = ref.init_weights(5, one)
    pre = "layers.0.attn."
    x = jax.random.normal(jax.random.key(2), (2, 2, T, 64))
    variables = {"params": {n: state[pre + n] for n in (
        "q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm")}}
    with jax.default_matmul_precision("highest"):
        got = sdar.BlockAttention(sdar.SdarConfig.from_dict(one), last,
                                  jnp.float32).apply(variables, x)
        want = ref.attention(state, pre, x.reshape(2, 2 * T, 64),
                             jnp.concatenate([jnp.arange(T), jnp.arange(T)]),
                             ref.stream_mask(T, 4), one).reshape(2, 2, T, 64)
    np.testing.assert_allclose(got, want[:, :1] if last else want, atol=2e-6)
    mask = np.asarray(ref.stream_mask(8, 4))
    assert mask[:8, :8].sum() == 2 * 16 and not mask[8:, :8].any()
    assert mask[:4, 8:].sum() == 0 and mask[4:8, 8:12].all()
    assert mask[8:12, 8:12].all() and not mask[8:12, 12:].any()


def test_what_a_changed_token_may_move():
    """A changed clean token of block b moves no noisy logit of blocks <= b
    (a noisy query sees clean keys of strictly earlier blocks) and no clean
    output before block b; a changed noisy token moves its own block's noisy
    logits only and no clean output."""
    architecture = CASES["two_layers"]
    model, tree, state = both(architecture)
    x = rows_of(6)
    noisy = jnp.where(jax.random.bernoulli(jax.random.key(7), 0.6, x.shape),
                      127, x)
    at, b = 13, 13 // 4                                  # a position, its block
    with jax.default_matmul_precision("highest"):
        run = lambda n, c: model.apply(tree, jnp.stack([n, c], 1), train=False)[0]
        whole = lambda n, c: ref.forward_arch(state, n, c, architecture)
        base, base_whole = run(noisy, x), whole(noisy, x)
        clean_moved = x.at[:, at].set((x[:, at] + 1) % 127)
        got, got_whole = run(noisy, clean_moved), whole(noisy, clean_moved)
        noisy_moved = noisy.at[:, at].set((noisy[:, at] + 1) % 127)
        got_n, got_n_whole = run(noisy_moved, x), whole(noisy_moved, x)
    moved = lambda a, c: np.abs(np.asarray(a) - np.asarray(c)).max(axis=(0, 2))
    end = 4 * (b + 1)
    assert moved(got, base)[:end].max() == 0 and moved(got, base)[end:].min() > 0
    clean_out = moved(got_whole, base_whole)[T:]
    assert clean_out[:4 * b].max() == 0 and clean_out[4 * b:].min() > 0
    d = moved(got_n, base)
    assert d[4 * b:end].min() > 0 and d[:4 * b].max() == 0 and d[end:].max() == 0
    assert moved(got_n_whole, base_whole)[T:].max() == 0


def expert_layer_of(architecture, state, x):
    """The program's expert layer alone on the reference's weights."""
    cfg = sdar.SdarConfig.from_dict(architecture)
    pre = "layers.0.moe."
    variables = {"params": {n: state[pre + n] for n in ("router", "w1", "w3", "w2")}}
    out, sown = sdar.SoftmaxExpertFfn(cfg, jnp.float32).apply(
        variables, x, mutable=["counters"])
    return out, sown["counters"]["expert_tokens"]


def test_eight_shares_add_up_to_the_uncut_layer():
    """Chip c of 8 holds experts [2c, 2c + 2) of 16 and the router whole:
    the shares' expert outputs sum to the reference's layer with all 16."""
    whole = arch(num_hidden_layers=1, num_experts=16, num_experts_per_tok=4,
                 experts_held=[0, 16])
    state = ref.init_weights(7, whole)
    x = jax.random.normal(jax.random.key(2), (2, T, 64))
    pre = "layers.0.moe."
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(state, pre, x, whole)
        total, positions = jnp.zeros_like(x), 0
        for c in range(8):
            lo, hi = 2 * c, 2 * c + 2
            share = {**state, **{pre + n: state[pre + n][lo:hi]
                                 for n in ("w1", "w3", "w2")}}
            out, counts = expert_layer_of({**whole, "experts_held": [lo, hi]},
                                          share, x)
            total, positions = total + out, positions + int(counts.sum())
    np.testing.assert_allclose(total, want, atol=2e-6)
    assert positions == 2 * T * 4        # every choice of every position, once


@pytest.mark.parametrize("forced", [True, False])
def test_no_token_is_dropped(forced):
    """A router of zeros gives every expert the same probability, so every
    position chooses experts 0 and 1, as a step's MASK positions all choose
    theirs: the layer runs every held expert over every position, has no
    buffer to overflow, and agrees with the reference either way."""
    one = arch(num_hidden_layers=1)
    state = dict(ref.init_weights(11, one))
    pre = "layers.0.moe."
    x = jax.random.normal(jax.random.key(4), (2, T, 64))
    if forced:
        state[pre + "router"] = jnp.zeros_like(state[pre + "router"])
    with jax.default_matmul_precision("highest"):
        out, counts = expert_layer_of(one, state, x)
        want = ref.expert_layer(state, pre, x, one)
    np.testing.assert_allclose(out, want, atol=2e-6)
    if forced:
        assert counts.tolist() == [64, 64, 0, 0]
    else:   # half the experts held: about a choice a position
        assert 32 < int(counts.sum()) < 96 and int(counts.max()) < 64


def test_the_architecture_refuses_what_it_cannot_be():
    with pytest.raises(ValueError, match="experts_held"):
        sdar.SdarConfig.from_dict(arch(experts_held=[4, 12]))
    with pytest.raises(ValueError, match="unknown architecture keys"):
        sdar.SdarConfig.from_dict(arch(sliding_window=4))
    with pytest.raises(ValueError, match="mask_token_id"):
        sdar.SdarConfig.from_dict(arch(mask_token_id=90))
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        sdar.SdarConfig.from_dict(arch(tie_word_embeddings=True))
    with pytest.raises(ValueError, match="block_length 5 does not divide"):
        build_model(params(arch(block_length=5)))


def test_operations_counted_are_the_references():
    """`flops_per_position(whole=True)` against XLA's count of the plain
    reference's forward pass (every held expert over every position), and
    what the program's form leaves out of it."""
    architecture = arch()
    state = jax.eval_shape(lambda: ref.init_weights(0, architecture))
    x = jax.ShapeDtypeStruct((2, T), jnp.int32)
    cost = jax.jit(lambda s, n, c: ref.forward_arch(s, n, c, architecture)).lower(
        state, x, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    # XLA counts the body of the reference's scan over the held experts
    # once: one expert a position (and every elementwise operation, a few
    # per cent at this size)
    once = ref.flops_per_position(architecture, T, experts_per_position=1,
                                  whole=True)
    assert once["forward"] == pytest.approx(cost["flops"] / (2 * 2 * T), rel=0.07)
    whole = ref.flops_per_position(architecture, T, experts_per_position=4,
                                   whole=True)
    assert whole["experts"] == 4 * once["experts"]
    per = ref.flops_per_position(architecture, T, experts_per_position=4)
    nb, pair = T // 4, 2 * 2 * 4 * 24
    # the pairs the mask allows: a layer's 2T x 2T less what it forbids, and
    # the last layer without its clean queries
    allowed = 16 * (nb * (nb + 1) / 2 + nb * (nb - 1) / 2 + nb)
    assert per["attention"] * 2 * T == pytest.approx(
        pair * (2 * allowed - 16 * nb * (nb + 1) / 2))
    assert per["head"] == whole["head"] / 2 and per["forward"] < whole["forward"]
    assert ref.expected_experts_per_position(architecture) == 1.0
    flops = family.model_flops({"arch": architecture, "seq_len": T})
    assert flops["train_step"] == 3 * flops["forward"] > 0
