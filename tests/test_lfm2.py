"""models/lfm2.py against the plain reference (chipbench/reference/lfm2.py)
on seeded weights at toy widths: every layer kind and the whole model,
forward, loss and gradients; the share test (the parts that 8 shares of the
experts give add up to the uncut reference's layer); no token dropped when
every token chooses one expert; the expert layer on the grouped product
(Pallas' interpreter) against the every-expert form and the reference, and
the rows it counts; padding and causality of a packed row."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program
from chipbench.families import lfm2_moe as family
from chipbench.reference import lfm2 as ref
from chipbench.reference import tokens as ref_tokens
from dba_mod_tpu.models import ModelVars, build_model
from dba_mod_tpu.models import lfm2
from dba_mod_tpu.ops.losses import batch_loss
from dba_mod_tpu.ops.triggers import next_token_labels
from dba_mod_tpu.fl.streamed import ModelCounts, fold_counts
from dba_mod_tpu.models.decoder_parts import ROWS_COUNTER
from dba_mod_tpu.ops import grouped_experts as ge
from tests.lfm2_cases import arch, params

CASES = {
    "conv_dense": arch(layer_types=["conv"], num_dense_layers=1),
    "attention_dense": arch(layer_types=["full_attention"], num_dense_layers=1),
    "conv_experts": arch(layer_types=["conv"], num_dense_layers=0),
    "attention_experts_no_bias": arch(layer_types=["full_attention"],
                                      num_dense_layers=0, use_expert_bias=False,
                                      norm_topk_prob=False,
                                      routed_scaling_factor=2.0),
    "whole": arch(),
}


def both(architecture, seed=3):
    """(ModelDef, the program's tree, the reference's state) of one seed."""
    model = build_model(params(architecture))
    state = ref.init_weights(seed, architecture)
    shapes = program.tree_shapes(
        jax.eval_shape(lambda: model.init_vars(jax.random.key(0))))
    return model, family.to_program(shapes, jax.device_get(state)), state


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_loss_and_gradients_are_the_references(case):
    architecture = CASES[case]
    model, tree, state = both(architecture)
    x = jax.random.randint(jax.random.key(1), (2, 32), 0, 128)
    rows = jnp.ones((2,), bool)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(tree, x, train=False)
        want = ref.forward_arch(state, x, architecture)
        np.testing.assert_allclose(logits, want, atol=2e-6)

        def program_loss(p):
            out, _, _ = model.apply_counted(ModelVars(p, tree.batch_stats), x)
            return batch_loss(out, next_token_labels(x), rows)

        stats = {k: v for k, v in state.items() if ref.is_stat(k)}
        weights = {k: v for k, v in state.items() if not ref.is_stat(k)}

        def reference_loss(w):
            out = ref.forward_arch({**w, **stats}, x, architecture)
            return jnp.mean(ref_tokens.row_loss(out, ref_tokens.labels_of(x)))

        loss, grads = jax.value_and_grad(program_loss)(tree.params)
        want_loss, want_grads = jax.value_and_grad(reference_loss)(weights)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    got = family.from_program(ModelVars(grads, tree.batch_stats), list(weights))
    for name in weights:
        np.testing.assert_allclose(
            got[name], want_grads[name], rtol=1e-4,
            atol=2e-6 * float(jnp.abs(want_grads[name]).max()), err_msg=name)


def layer_variables(state, pre="layers.0.moe."):
    return {"params": {n: state[pre + n] for n in ("router", "w1", "w3", "w2")},
            "batch_stats": {"expert_bias": state[pre + "expert_bias"]}}


def expert_layer_of(architecture, state, x):
    """The program's expert layer alone on the reference's weights."""
    cfg = lfm2.Lfm2Config.from_dict(architecture)
    out, sown = lfm2.ExpertFfn(cfg, jnp.float32).apply(
        layer_variables(state), x, mutable=["counters"])
    return out, sown["counters"]["expert_tokens"]


def test_eight_shares_add_up_to_the_uncut_layer():
    """Chip c of 8 holds experts [2c, 2c + 2) of 16 and the router whole:
    the shares' expert outputs sum to the reference's layer with all 16."""
    whole = arch(layer_types=["conv"], num_dense_layers=0, num_experts=16,
                 num_experts_per_tok=4, experts_held=[0, 16])
    state = ref.init_weights(7, whole)
    x = jax.random.normal(jax.random.key(2), (2, 32, 64))
    pre = "layers.0.moe."
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(state, pre, x, whole)
        total, tokens = jnp.zeros_like(x), 0
        for c in range(8):
            lo, hi = 2 * c, 2 * c + 2
            share = {**state, **{pre + n: state[pre + n][lo:hi]
                                 for n in ("w1", "w3", "w2")}}
            out, counts = expert_layer_of({**whole, "experts_held": [lo, hi]},
                                          share, x)
            total, tokens = total + out, tokens + int(counts.sum())
    np.testing.assert_allclose(total, want, atol=2e-6)
    assert tokens == 2 * 32 * 4          # every choice of every token, once


@pytest.mark.parametrize("forced", [True, False])
def test_no_token_is_dropped(forced):
    """A router forced to expert 1 gives it every token, sixteen times an
    even share: one path, no buffer to overflow and no second form, and the
    layer agrees with the reference either way."""
    one = arch(layer_types=["conv"], num_dense_layers=0)
    state = dict(ref.init_weights(11, one))
    pre = "layers.0.moe."
    x = jax.random.normal(jax.random.key(4), (2, 32, 64))
    if forced:
        state[pre + "expert_bias"] = state[pre + "expert_bias"].at[1].set(5.0)
    with jax.default_matmul_precision("highest"):
        out, counts = expert_layer_of(one, state, x)
        want = ref.expert_layer(state, pre, x, one)
    np.testing.assert_allclose(out, want, atol=2e-6)
    assert (int(counts[1]) == 64) if forced else (int(counts.max()) < 64)


WIDE = dict(layer_types=["conv"], num_dense_layers=0, hidden_size=128,
            num_experts=16, num_experts_per_tok=4, experts_held=[4, 8])
WIDTHS = (128, 256)     # one width block and two under `interpret_the_product`


def interpret_the_product(patch):
    """The layer's TPU form on this CPU: `runs_here` holds, the product runs
    in Pallas' interpreter on tiles of 16, and a width block is 128 lanes,
    so that a width of 256 is walked in two blocks (as 1,536 is on the chip;
    tests/test_grouped_experts.py works the block out of a small memory)."""
    patch.setattr(ge, "runs_here", lambda n, d, f: True)
    patch.setattr(ge, "grouped_experts", functools.partial(
        ge.grouped_experts, tile=16, interpret=True))
    patch.setattr(ge, "width_block", lambda d, f, tile=ge.TILE: 128)


def layer_and_gradients(cfg, variables, x, cot):
    """(out, (d params, dx)) of the expert layer, and what it sowed."""
    def layer(p, x):
        out, sown = lfm2.ExpertFfn(cfg, jnp.float32).apply(
            {**variables, "params": p}, x, mutable=["counters"])
        return out, sown["counters"]

    out, pull, counters = jax.vjp(layer, variables["params"], x, has_aux=True)
    return out, pull(cot), counters


@pytest.mark.parametrize("width", WIDTHS)
def test_the_grouped_form_is_the_every_expert_form_and_the_reference(
        monkeypatch, width):
    """`ExpertFfn` with `expert_bias` set, a sigmoid's weights renormalised,
    a top-4 of 16 of which this chip holds experts 4-7 (most positions give
    some choice to another chip, some all four): on the grouped product the
    output and every gradient (router, the three matrices, x) are the
    every-expert form's to bfloat16's rounding (the kernels' operands; this
    CPU's einsums are exact float32), the every-expert form is the
    reference's layer, and a position that chose no held expert reads
    zeros."""
    architecture = arch(moe_intermediate_size=width, **WIDE)
    cfg = lfm2.Lfm2Config.from_dict(architecture)
    state = dict(ref.init_weights(13, architecture))
    pre = "layers.0.moe."
    state[pre + "expert_bias"] = 0.05 * jax.random.normal(jax.random.key(9), (16,))
    variables = layer_variables(state)
    x = jax.random.normal(jax.random.key(6), (2, 32, 128))
    cot = jax.random.normal(jax.random.key(7), x.shape)
    with jax.default_matmul_precision("highest"):
        over_all = layer_and_gradients(cfg, variables, x, cot)
        want = ref.expert_layer(state, pre, x, architecture)
    np.testing.assert_allclose(over_all[0], want, atol=2e-6)
    interpret_the_product(monkeypatch)
    grouped = layer_and_gradients(cfg, variables, x, cot)
    flat = lambda r: [r[0]] + jax.tree_util.tree_leaves(r[1])
    for got, ours in zip(flat(grouped), flat(over_all)):
        assert got.shape == ours.shape and bool(jnp.isfinite(got).all())
        assert float(jnp.linalg.norm(got - ours)
                     / jnp.linalg.norm(ours)) < 8e-3
    logits = x.reshape(-1, 128) @ state[pre + "router"]
    sel, _ = lfm2.route(logits, state[pre + "expert_bias"], 4, True, 1.0)
    elsewhere = np.asarray(((sel < 4) | (sel >= 8)).all(axis=1))
    assert 0 < elsewhere.sum() < 64
    assert not bool(jnp.any(grouped[0].reshape(-1, 128)[elsewhere]))
    np.testing.assert_array_equal(grouped[2]["expert_tokens"],
                                  over_all[2]["expert_tokens"])


@pytest.mark.parametrize("form", ["every_expert", "grouped"])
def test_the_layer_counts_its_rows_and_the_round_carries_them(
        monkeypatch, form):
    """`ROWS_COUNTER` beside `expert_tokens`: (rows multiplied, held experts
    x tokens): all of them where every held expert runs over every token,
    the visited tiles' where the grouped product does (the file's tile of
    256: a toy call's few pairs round up past the whole); and
    `fl/streamed.py::fold_counts` adds a real step's to `ModelCounts.rows`,
    apart from the tokens' three."""
    if form == "grouped":
        interpret_the_product(monkeypatch)
    architecture = arch(moe_intermediate_size=256, **WIDE)
    cfg = lfm2.Lfm2Config.from_dict(architecture)
    variables = layer_variables(dict(ref.init_weights(13, architecture)))
    x = jax.random.normal(jax.random.key(6), (2, 32, 128))
    _, sown = lfm2.ExpertFfn(cfg, jnp.float32).apply(
        variables, x, mutable=["counters"])
    counted = sown["counters"]
    tokens = counted["expert_tokens"]
    run = (int(ge.rows_run(tokens, 128, 256)) if form == "grouped"
           else 4 * 64)
    assert counted[ROWS_COUNTER].tolist() == [run, 4 * 64]
    assert run == (256 * 8 // 2 if form == "grouped" else 256)
    zero = ModelCounts(*(jnp.int32(0),) * 3, jnp.zeros((2,), jnp.int32))
    once = fold_counts(zero, {"layer_1": {"moe": counted}}, jnp.int32(1))
    twice = fold_counts(once, {"layer_1": {"moe": counted}}, jnp.int32(1))
    skipped = fold_counts(twice, {"layer_1": {"moe": counted}}, jnp.int32(0))
    assert skipped.rows.tolist() == twice.rows.tolist() == [2 * run, 2 * 256]
    assert (int(twice.held), int(twice.max), int(twice.cells)) == (
        2 * int(tokens.sum()), int(tokens.max()), 2 * 4)


def test_padding_and_causality_of_a_packed_row():
    model, tree, state = both(CASES["whole"])
    x = jax.random.randint(jax.random.key(5), (2, 32), 1, 128)
    padded = x.at[:, 20:].set(-1)
    with jax.default_matmul_precision("highest"):
        full, _ = model.apply(tree, x, train=False)
        cut, _ = model.apply(tree, padded, train=False)
        short = ref.forward_arch(state, x[:, :20], CASES["whole"])
    # what follows a position never reaches it, padding or tokens
    np.testing.assert_allclose(cut[:, :20], full[:, :20], atol=2e-6)
    np.testing.assert_allclose(cut[:, :20], short, atol=2e-6)
    labels = next_token_labels(padded)
    assert (np.asarray(labels)[:, 19:] == -1).all()
    np.testing.assert_array_equal(np.asarray(labels)[:, :19], np.asarray(x)[:, 1:20])


def test_the_architecture_refuses_what_it_cannot_be():
    with pytest.raises(ValueError, match="experts_held"):
        lfm2.Lfm2Config.from_dict(arch(experts_held=[4, 12]))
    with pytest.raises(ValueError, match="unknown architecture keys"):
        lfm2.Lfm2Config.from_dict(arch(sliding_window=4))


def test_operations_counted_are_the_references():
    """`flops_per_token` against XLA's count of the plain reference's forward
    pass with every held expert dense (what the reference computes)."""
    architecture = arch()
    state = jax.eval_shape(lambda: ref.init_weights(0, architecture))
    x = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    cost = jax.jit(lambda s, t: ref.forward_arch(s, t, architecture)).lower(
        state, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    per = ref.flops_per_token(architecture, 32, experts_per_token=4)
    # the reference multiplies the whole score matrix; the count takes the
    # causal half
    causal = 2 * 2 * 64 * (32 - 1) / 2
    assert per["forward"] + causal == pytest.approx(cost["flops"] / 64, rel=0.05)
    assert ref.expected_experts_per_token(architecture) == 1.0
