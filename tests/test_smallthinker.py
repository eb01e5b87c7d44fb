"""models/smallthinker.py against the plain reference
(chipbench/reference/smallthinker.py) on seeded weights at toy widths: the
whole model, forward, the next-token loss and every leaf's gradient; the share
test (the parts that the shares of the experts give add up to the uncut
reference's layer, the residual counted once); the router reads the
pre-attention norm; a window layer differs from a causal one exactly at the
queries past the window; a global layer carries no position; the static counts
and the operation count."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program
from chipbench.families import smallthinker as family
from chipbench.reference import smallthinker as ref
from chipbench.reference import tokens as ref_tokens
from dba_mod_tpu.models import ModelVars, build_model
from dba_mod_tpu.models import smallthinker as st
from dba_mod_tpu.ops.triggers import next_token_labels
from tests.smallthinker_cases import arch, params

CASES = {
    "a_global_and_a_window_layer": arch(),
    "one_period": arch(layers_run=[0, 1, 2, 3]),
    "window_layers_alone_seven_heads_a_group": arch(
        layers_run=[1, 2], num_attention_heads=7, num_key_value_heads=1),
    "every_expert_held_top_one": arch(experts_held=[0, 16],
                                      moe_num_active_primary_experts=1),
    "window_wider_than_the_row": arch(sliding_window_size=64),
}
T = 32


def both(architecture, seed=3):
    """(ModelDef, the program's tree, the reference's state) of one seed."""
    model = build_model(params(architecture))
    state = ref.init_weights(seed, architecture)
    shapes = program.tree_shapes(
        jax.eval_shape(lambda: model.init_vars(jax.random.key(0))))
    return model, family.to_program(shapes, jax.device_get(state)), state


def rows_of(key, padded_from=None, length=T):
    x = jax.random.randint(jax.random.key(key), (2, length), 0, 127)
    return x if padded_from is None else x.at[1, padded_from:].set(-1)


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_loss_and_gradients_are_the_references(case):
    """Tolerances: both sides are float32 at `highest`; what separates them
    is the order of sums (the program contracts the experts' down-projections
    over E x F at once and writes the scores a key-value head at a time, the
    reference sums expert after expert and block after block): a few units in
    the last place of the logits (2e-6 absolute at logits of order 0.1), and
    for a gradient 1e-4 relative with the same floor relative to the leaf's
    largest entry."""
    architecture = CASES[case]
    model, tree, state = both(architecture)
    x = rows_of(1, padded_from=24)
    y, rows = next_token_labels(x), jnp.ones((2,), bool)
    forward = ref.forward_of(architecture)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(tree, x, train=False)
        np.testing.assert_allclose(logits, forward(state, x, False)[0],
                                   atol=2e-6)

        def program_loss(p):
            return model.run_batch(ModelVars(p, tree.batch_stats), x, y, rows,
                                   jax.random.key(9), train=True).loss

        def reference_loss(w):
            logits, _ = forward(w, x, True)
            # every row scores the same number of positions but the padded
            # one: the program's mean is over positions
            nll, scored = ref_tokens.scored_nll(logits, ref_tokens.labels_of(x))
            return jnp.sum(nll) / jnp.sum(scored)

        loss, grads = jax.value_and_grad(program_loss)(tree.params)
        want_loss, want_grads = jax.value_and_grad(reference_loss)(dict(state))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    got = family.from_program(ModelVars(grads, tree.batch_stats), list(state))
    for name in state:
        np.testing.assert_allclose(
            got[name], want_grads[name], rtol=1e-4,
            atol=2e-6 * float(jnp.abs(want_grads[name]).max()), err_msg=name)


def layer_of(architecture, state, x):
    """The reference's layer 0 over x [B, T, D]: (h' after attention, the
    expert layer's output)."""
    eps = architecture["rms_norm_eps"]
    a = ref.rms_norm(x, state["layers.0.input_norm"], eps)
    h = x + ref.attention(state, "layers.0.attn.", a, 0, architecture)
    m = ref.rms_norm(h, state["layers.0.post_norm"], eps)
    return h, ref.expert_layer(state, "layers.0.moe.", a, m, architecture)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer: their
    expert layers' outputs (the program's, each with its slice of one uncut
    state), the residual counted once, sum to the uncut reference's layer."""
    uncut = arch(experts_held=[0, 16], layers_run=[0])
    state = ref.init_weights(5, uncut)
    x = 0.5 * jax.random.normal(jax.random.key(2), (2, T, uncut["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        h, whole = layer_of(uncut, state, x)
        total = h
        for lo in range(0, 16, 4):
            share = arch(experts_held=[lo, lo + 4], layers_run=[0])
            cut = {n: (v[lo:lo + 4] if n.split(".")[-1] in ("w1", "w3", "w2")
                       else v) for n, v in state.items()}
            layer = st.SmallThinkerLayer(
                st.SmallThinkerConfig.from_dict(share), 0, jnp.float32)
            tree = family.to_program(
                program.tree_shapes(jax.eval_shape(
                    lambda: build_model(params(share)).init_vars(
                        jax.random.key(0)))), jax.device_get(cut))
            out = layer.apply({"params": tree.params["layer_0"]}, x,
                              mutable=["counters"])[0]
            total = total + (out - h)
    np.testing.assert_allclose(total, h + whole, atol=5e-6)


def test_the_router_reads_the_pre_attention_norm():
    """The program's selection is the reference's, which routes on `a`; a
    reference that routed on the post-attention norm `m` selects otherwise
    at some position, and its layer differs."""
    architecture = arch(layers_run=[0])
    model, tree, state = both(architecture)
    x = rows_of(4)
    eps = architecture["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h0 = state["embed"][x]
        a = ref.rms_norm(h0, state["layers.0.input_norm"], eps)
        h = h0 + ref.attention(state, "layers.0.attn.", a, 0, architecture)
        m = ref.rms_norm(h, state["layers.0.post_norm"], eps)
        k = architecture["moe_num_active_primary_experts"]
        on_a = jax.lax.top_k(a @ state["layers.0.moe.router"], k)[1]
        on_m = jax.lax.top_k(m @ state["layers.0.moe.router"], k)[1]
        assert bool(jnp.any(jnp.sort(on_a) != jnp.sort(on_m)))
        pre = ref.expert_layer(state, "layers.0.moe.", a, m, architecture)
        post = ref.expert_layer(state, "layers.0.moe.", m, m, architecture)
        layer = st.SmallThinkerLayer(
            st.SmallThinkerConfig.from_dict(architecture), 0, jnp.float32)
        got = layer.apply({"params": tree.params["layer_0"]}, h0,
                          mutable=["counters"])[0]
    np.testing.assert_allclose(got, h + pre, atol=5e-6)
    assert float(jnp.abs(got - (h + post)).max()) > 1e-3


@pytest.mark.parametrize("window", [8, 16])
def test_a_window_layer_differs_from_a_causal_one_past_the_window(window):
    """Layer 1 (a window layer with RoPE) against the same layer run causal:
    equal at the queries whose window still holds the whole row (t < window),
    different at every query past it."""
    windowed = arch(layers_run=[1], sliding_window_size=window)
    causal = arch(layers_run=[1], sliding_window_size=window,
                  sliding_window_layout=[0] * 8)
    model, tree, state = both(windowed)
    x = 0.5 * jax.random.normal(jax.random.key(6), (1, T, 256))
    outs = []
    for architecture in (windowed, causal):
        attn = st.LayoutAttention(
            st.SmallThinkerConfig.from_dict(architecture),
            st.SmallThinkerConfig.from_dict(architecture).kind(0), True,
            jnp.float32)
        outs.append(attn.apply({"params": tree.params["layer_0"]["attn"]}, x))
    gap = jnp.abs(outs[0] - outs[1]).max(axis=-1)[0]
    assert not bool(jnp.any(gap[:window]))
    assert bool(jnp.all(gap[window:] > 1e-6))
    assert (st.attention_mask(T, window).sum()
            == ref.attention_pairs(windowed, T)["window"])


def test_a_global_layer_carries_no_position(monkeypatch):
    """A layer without positional encoding cannot tell where a key stands:
    the last query's output is unchanged when the positions before it change
    places (a shift of all of them by one, the first to the end), where the
    same weights with RoPE give another output; and a global layer makes no
    rotary table at all."""
    architecture = arch(layers_run=[0])
    _, tree, _ = both(architecture)
    config = st.SmallThinkerConfig.from_dict(architecture)
    assert not config.rotates(0) and config.kind(0) == st.FULL
    x = 0.5 * jax.random.normal(jax.random.key(7), (1, T, 256))
    shifted = jnp.concatenate([x[:, 1:T - 1], x[:, :1], x[:, T - 1:]], axis=1)
    weights = {"params": tree.params["layer_0"]["attn"]}
    with jax.default_matmul_precision("highest"):
        rotated = st.LayoutAttention(config, st.FULL, True, jnp.float32)
        moved = (rotated.apply(weights, x)[:, -1]
                 - rotated.apply(weights, shifted)[:, -1])
        assert float(jnp.abs(moved).max()) > 1e-4
        monkeypatch.setattr(st, "rope_tables", None)    # never asked for
        plain = st.LayoutAttention(config, st.FULL, False, jnp.float32)
        np.testing.assert_allclose(plain.apply(weights, x)[:, -1],
                                   plain.apply(weights, shifted)[:, -1],
                                   atol=2e-6)


def test_the_static_counts_are_the_masks():
    """`attention_counts` on the CPU: the pairs each kind's mask allows (the
    reference's closed forms), no tile (XLA's form runs); at the cell's row
    length the issue's numbers."""
    config = st.SmallThinkerConfig.from_dict(arch(layers_run=[0, 1, 2, 3]))
    counts = st.attention_counts(config, T)
    pairs = ref.attention_pairs(arch(), T)
    assert counts["attention_pairs_full"] == pairs["full"] == T * (T + 1) // 2
    assert counts["attention_pairs_window"] == pairs["window"] == 8 * 9 // 2 + 24 * 8
    assert not any(v for k, v in counts.items() if "tiles" in k)
    big = ref.attention_pairs(arch(sliding_window_size=4096), 8192)
    assert big == {"full": 33_558_528, "window": 25_167_872}


def test_the_architecture_refuses_what_it_cannot_be():
    for changes, word in [({"experts_held": [4, 20]}, "experts_held"),
                          ({"rope_layout": [0, 1]}, "rope_layout"),
                          ({"layers_run": [8]}, "layers_run"),
                          ({"moe_primary_router_apply_softmax": False},
                           "sigmoid"),
                          ({"tie_word_embeddings": True}, "untied"),
                          ({"dense_ffn": 1}, "unknown")]:
        with pytest.raises(ValueError, match=word):
            st.SmallThinkerConfig.from_dict(arch(**changes))


def test_operations_counted_are_the_issues_arithmetic():
    """`flops_per_token` at the cell's architecture: the parts ISSUE 45
    reckoned by hand (41.9 M a layer of projections, 8.85 M of held experts
    at 0.75 an expert a token, 58.7 M and 44.0 M of attention a global and a
    window layer, 97.2 M of head: 492 M a token forward)."""
    import json
    from pathlib import Path
    model = json.loads((Path(ref.__file__).parents[1] / "configs"
                        / "smallthinker_21b_a3b_dba.json").read_text())["model"]
    a = model["arch"]
    per = ref.flops_per_token(a, model["seq_len"],
                              ref.expected_experts_per_token(a))
    assert ref.expected_experts_per_token(a) == 0.75
    assert round(per["projections"] / 4 / 1e6, 1) == 41.9
    assert round(per["experts"] / 4 / 1e6, 2) == 8.85
    assert round(per["head"] / 1e6, 1) == 97.2
    pairs = ref.attention_pairs(a, 8192)
    assert round(pairs["full"] * ref.pair_flops(a) / 8192 / 1e6, 1) == 58.7
    assert round(pairs["window"] * ref.pair_flops(a) / 8192 / 1e6, 1) == 44.0
    assert int(per["forward"] / 1e6) == 492     # the routers' 1.3 M beside them
    assert round(per["attention"] / per["forward"], 2) == 0.39
    assert family.model_flops(model)["train_step"] == 3 * per["forward"]
