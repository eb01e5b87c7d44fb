"""The blocked attention kernel (dba_mod_tpu/ops/attention.py) in Pallas'
interpreter on the CPU, held to `models/sdar.py::three_part_attention` (XLA's
form, which is also what every CPU run of the model keeps): outputs and the
gradients to q, k and v for a whole layer and for the last; its static masks
against the reference's written-out matrix; its tile counts against a numpy
count of the mask's non-empty tiles; and the CPU round program of the
block-diffusion model, which must not hold the kernel. For
models/smallthinker.py: the kernel under a causal mask and under a causal
mask with a window, a group of 7 query heads a key-value head, against that
model's written-out form; and the model's static tile counts at the cell's
rows of 8,192."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import sdar as ref
from dba_mod_tpu.fl.experiment import Experiment
from dba_mod_tpu.models import build_model, sdar
from dba_mod_tpu.models import smallthinker as st
from dba_mod_tpu.ops import attention
from tests import sdar_cases, smallthinker_cases
from tests.test_block_diffusion import lowered_sha

T, BLK, KV, G, HD = 256, 4, 2, 2, 128
TILE = 128   # the interpreter's tiles: four a stream, so some are skipped


@pytest.fixture(scope="module")
def small_tiles():
    """The chip's tiles are wider than these rows: the file's constants at
    the smallest whole tile for the module's tests."""
    was = attention.BLOCK_Q, attention.BLOCK_K
    attention.BLOCK_Q = attention.BLOCK_K = TILE
    yield
    attention.BLOCK_Q, attention.BLOCK_K = was


@pytest.fixture(scope="module")
def both_forms(small_tiles):
    """last -> ((out, dq, dk, dv) of the kernel, the same of XLA's form) on
    one draw of q, k, v and of the weights the output is summed with."""
    keys = jax.random.split(jax.random.key(11), 4)
    q = jax.random.normal(keys[0], (2, 2, T, KV, G, HD))
    k = jax.random.normal(keys[1], (2, 2, T, KV, HD))
    v = jax.random.normal(keys[2], (2, 2, T, KV, HD))
    w = jax.random.normal(keys[3], q.shape)

    def results(form, last):
        qs, ws = (q[:, :1], w[:, :1]) if last else (q, w)
        out, pull = jax.vjp(lambda *a: form(*a, last), qs, k, v)
        return (out,) + pull(ws)

    kernel = lambda q, k, v, last: sdar.blocked_streams_attention(
        q, k, v, BLK, last, interpret=True)
    oracle = lambda q, k, v, last: sdar.three_part_attention(
        q, k, v, BLK, last, jnp.float32)
    return {last: (results(kernel, last), results(oracle, last))
            for last in (False, True)}


@pytest.mark.parametrize("last", [False, True])
@pytest.mark.parametrize("which", range(4), ids=["out", "dq", "dk", "dv"])
def test_the_kernel_is_the_three_part_form(both_forms, which, last):
    """To bfloat16's rounding: the kernel's products take bfloat16 operands
    (what a TPU's default precision gives XLA's form) where this CPU's oracle
    is exact float32; a tile skipped, a mask misread or a softmax not joined
    would be off by tenths."""
    got, want = (r[which] for r in both_forms[last])
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got).all())
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 8e-3, err
    np.testing.assert_allclose(got, want, atol=0.05 * float(jnp.abs(want).max()))


def test_the_last_layers_clean_stream_gives_keys_and_values_alone(both_forms):
    """The last layer reads no clean query: its clean stream's gradients come
    through the noisy queries' keys and values only, in both forms."""
    (_, _, dk, dv), _ = both_forms[True]
    (_, _, dk_all, _), _ = both_forms[False]
    assert float(jnp.abs(dk[:, sdar.CLEAN]).max()) > 0
    assert float(jnp.abs(dv[:, sdar.NOISY]).max()) > 0
    assert not np.allclose(dk[:, sdar.CLEAN], dk_all[:, sdar.CLEAN])


@pytest.mark.parametrize("seq_len,block", [(8, 4), (32, 4), (64, 16), (24, 1)])
def test_the_static_masks_are_the_references_matrix(seq_len, block):
    noisy_sees, clean_sees = sdar.stream_masks(seq_len, block)
    whole = np.asarray(ref.stream_mask(seq_len, block))
    np.testing.assert_array_equal(noisy_sees, whole[:seq_len])
    np.testing.assert_array_equal(clean_sees, whole[seq_len:, seq_len:])
    assert not whole[seq_len:, :seq_len].any()
    assert noisy_sees.any(axis=1).all() and clean_sees.any(axis=1).all()
    own, before = sdar.block_masks(seq_len, block)
    np.testing.assert_array_equal(np.asarray(own), clean_sees)
    np.testing.assert_array_equal(np.asarray(before), noisy_sees[:, seq_len:])


@pytest.mark.parametrize("block_q,block_k", [(128, 512), (256, 256),
                                             (128, 128), (512, 512)])
def test_the_plan_visits_the_masks_non_empty_tiles(block_q, block_k):
    for mask in sdar.stream_masks(2048, 4):
        plan = attention.tile_plan(mask, block_q, block_k)
        tiles = mask.reshape(mask.shape[0] // block_q, block_q,
                             mask.shape[1] // block_k, block_k)
        some, whole = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
        assert plan.tiles_run == some.sum() < plan.tiles_all == some.size
        q_tile, k_tile, v_tile, piece, flags = plan.pairs
        visited = np.zeros_like(some)
        visited[q_tile, k_tile] = True
        np.testing.assert_array_equal(visited, some)
        np.testing.assert_array_equal(v_tile, k_tile)
        cut = flags & attention.CUT != 0
        np.testing.assert_array_equal(cut, ~whole[q_tile, k_tile])
        # a query tile's visits lie together, its first and last flagged
        first = flags & attention.FIRST != 0
        last = flags & attention.LAST != 0
        assert first.sum() == last.sum() == some.shape[0]
        assert (np.diff(q_tile) >= 0).all()
        # a cut tile reads its own piece of the mask
        for n in np.flatnonzero(cut):
            np.testing.assert_array_equal(
                plan.pieces[piece[n]].astype(bool),
                tiles[q_tile[n], :, k_tile[n]])
        # the forward walks a query tile's pairs twice: sums, then products
        fq, fk, fv, _, fflags = plan.forward
        assert len(fq) == 2 * plan.tiles_run and (np.diff(fq) >= 0).all()
        for i in range(some.shape[0]):
            mine, n = fq == i, some[i].sum()
            sums = fflags[mine] & attention.SUMS != 0
            assert sums[:n].all() and not sums[n:].any()
            np.testing.assert_array_equal(fk[mine][:n], fk[mine][n:])
            assert (fv[mine][:n] == fk[mine][0]).all()
            np.testing.assert_array_equal(fv[mine][n:], fk[mine][n:])
            want = np.zeros(2 * n, np.int32)
            want[0], want[n - 1], want[-1] = (attention.FIRST, attention.SUMMED,
                                              attention.LAST)
            if n == 1:
                want[0] = attention.FIRST | attention.SUMMED
            np.testing.assert_array_equal(
                fflags[mine] & (attention.FIRST | attention.SUMMED
                                | attention.LAST), want)
        # and a block-diagonal mask has few distinct ones
        assert len(plan.pieces) <= 2 * max(1, block_k // block_q)


@pytest.mark.parametrize("mask,why", [
    (np.ones((128, 200), bool), "whole number"),
    (np.tril(np.ones((128, 128), bool), -1), "allows no key"),
])
def test_the_plan_refuses_what_the_kernel_cannot_run(mask, why):
    with pytest.raises(ValueError, match=why):
        attention.tile_plan(mask, 128, 128)


def test_the_models_tile_counts_are_its_calls_plans(monkeypatch):
    arch = sdar.SdarConfig.from_dict(sdar_cases.arch(
        head_dim=128, num_hidden_layers=4, num_key_value_heads=2))
    assert sdar.attention_tiles(arch, 2048) == (0, 0)        # this CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sdar.attention_tiles(arch, 2048 + 64) == (0, 0)   # no whole tile
    assert sdar.attention_tiles(
        sdar.SdarConfig.from_dict(sdar_cases.arch()), 2048) == (0, 0)  # hd 24
    noisy, clean = (attention.plan_of(m) for m in sdar.stream_masks(2048, 4))
    run, every = sdar.attention_tiles(arch, 2048)
    assert run == 2 * (4 * noisy.tiles_run + 3 * clean.tiles_run)
    assert every == 2 * (4 * noisy.tiles_all + 3 * clean.tiles_all)
    assert 0 < run < every


def test_on_the_cpu_the_round_program_holds_no_kernel():
    """Off the TPU the model keeps XLA's forms, the attention's and the
    expert layer's (every held expert over every position): the plan and the
    model's own row counts say so, and the lowered round program calls no
    kernel. `d5c5…`: the first 16 hex digits of sha256 of the
    block-diffusion round's lowered text at tests/sdar_cases.py's sizes
    (`tests/test_block_diffusion.py::lowered_sha`, jax 0.9.0), recorded on
    the PR that brought the expert layer's row counts into the round's carry
    (b3e6… before it, from commit 4b32d61 on)."""
    exp = Experiment(sdar_cases.params(), save_results=False)
    assert exp.model_def.attention_tiles == (0, 0)
    model = build_model(sdar_cases.params())
    assert model.attention_tiles == (0, 0)
    streams = jnp.zeros((1, 2, 32), jnp.int32)
    _, _, counted = model.apply_counted(
        model.init_vars(jax.random.key(0)), streams)
    lo, hi = sdar_cases.ARCH["experts_held"]
    rows = [layer["moe"]["expert_rows"] for layer in counted.values()]
    assert [r.tolist() for r in rows] == [
        [(hi - lo) * 64] * 2, [(hi - lo) * 32] * 2]   # the last: one stream
    tasks, idx, mask, ns, lane = exp.build_static_round_inputs(2)
    k1, k2 = jax.random.split(jax.random.key(0))
    text = exp.engine.round_fn.lower(
        exp.global_vars, exp.fg_state,
        exp.engine.round_workspace(exp.global_vars), tasks, idx, mask, lane,
        ns, k1, k2, exp.device_data.train_source).as_text()
    assert "custom_call" not in text or "tpu_custom_call" not in text
    assert lowered_sha(exp, 2) == "d5c541e12e5f184a"


LONG, WINDOW, GROUP = 512, 160, 7   # four tiles a row; the window cuts tiles


@pytest.fixture(scope="module")
def layout_forms(small_tiles):
    """kind -> ((out, dq, dk, dv) of the kernel, the same of the scores
    written out) under models/smallthinker.py's two masks, 7 query heads a
    key-value head, on one draw of q, k, v and of the output's weights."""
    keys = jax.random.split(jax.random.key(13), 4)
    q = jax.random.normal(keys[0], (1, KV, GROUP, LONG, HD))
    k = jax.random.normal(keys[1], (1, KV, LONG, HD))
    v = jax.random.normal(keys[2], (1, KV, LONG, HD))
    w = jax.random.normal(keys[3], q.shape)

    def results(form):
        out, pull = jax.vjp(form, q, k, v)
        return (out,) + pull(w)

    def of(kind):
        mask = st.attention_mask(LONG, WINDOW if kind == st.WINDOW else None)
        return (results(lambda *a: attention.blocked_attention(
                    *a, mask, interpret=True)),
                results(lambda *a: st.written_attention(*a, mask, jnp.float32)))

    return {kind: of(kind) for kind in (st.FULL, st.WINDOW)}


@pytest.mark.parametrize("kind", [st.FULL, st.WINDOW])
@pytest.mark.parametrize("which", range(4), ids=["out", "dq", "dk", "dv"])
def test_the_kernel_is_the_written_out_form_under_both_layout_masks(
        layout_forms, which, kind):
    """As `test_the_kernel_is_the_three_part_form`, to bfloat16's rounding;
    a group of 7 (a tile's rows are 7 x 128 here, 7 x 256 on the chip) and a
    window whose edge cuts through tiles and leaves whole tiles unvisited."""
    got, want = (r[which] for r in layout_forms[kind])
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got).all())
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 8e-3, err
    np.testing.assert_allclose(got, want, atol=0.05 * float(jnp.abs(want).max()))


def test_the_window_leaves_tiles_unvisited(small_tiles):
    full = attention.plan_of(st.attention_mask(LONG, None))
    window = attention.plan_of(st.attention_mask(LONG, WINDOW))
    assert (full.tiles_run, full.tiles_all) == (10, 16)
    # a query tile sees its own key tile and at most the two before it
    assert window.tiles_run == 1 + 2 + 3 + 3 and window.tiles_all == 16


def test_the_long_row_models_tile_counts_are_the_issues(monkeypatch):
    """At the cell's shapes (rows of 8,192, a 4,096-key window, 256 x 512
    tiles, 4 key-value heads, one period of layers): 272 of a key-value
    head's 512 tile pairs in the global layer, 216 in each window layer."""
    config = st.SmallThinkerConfig.from_dict(smallthinker_cases.arch(
        head_dim=128, num_key_value_heads=4, sliding_window_size=4096,
        layers_run=[0, 1, 2, 3]))
    assert st.attention_counts(config, 8192)["attention_tiles_all"] == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention, "BLOCK_Q", 256)   # the chip's, whatever
    monkeypatch.setattr(attention, "BLOCK_K", 512)   # `small_tiles` has set
    counts = st.attention_counts(config, 8192)
    assert counts["attention_tiles_full"] == 4 * 272
    assert counts["attention_tiles_window"] == 4 * 3 * 216
    assert counts["attention_tiles_all"] == 4 * 4 * 512
    assert counts["attention_tiles_run"] == 4 * (272 + 3 * 216)
    assert counts["attention_pairs_full"] == 33_558_528
    assert counts["attention_pairs_window"] == 25_167_872
    assert st.attention_counts(config, 8192 + 64)["attention_tiles_all"] == 0
