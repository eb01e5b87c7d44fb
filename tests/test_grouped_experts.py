"""The grouped expert product (dba_mod_tpu/ops/grouped_experts.py) in Pallas'
interpreter on the CPU, held to `models/sdar.py::experts_over_all` (every held
expert over every position, which is also what every CPU run of the model
keeps): the output and the gradients to x, the router's weights and the three
matrices under the routings the one path must hold in; the list and the walk
against numpy counts of the same routing. The same with the gate's activation
`relu` (models/smallthinker.py's ReGLU experts) at a hidden size of 20 x 128,
whose rows travel padded to 24 sublanes; and at a width the kernels take in
two blocks (models/lfm2.py's experts), under a fast memory made small."""
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dba_mod_tpu.models.sdar import experts_over_all
from dba_mod_tpu.ops import grouped_experts as ge

N, D, F, E, K = 64, 128, 128, 4, 4
TILE = 16            # the interpreter's tile: sixteen a call's list
ELSEWHERE = 9        # an expert another chip holds


def routing(case: str) -> np.ndarray:
    """held [N, K] int32: what each position chose, counted from the first
    held expert; a position names an expert once."""
    rng = np.random.default_rng(5)
    if case == "even":              # a top-4 of 16 experts, 4 of them held
        return np.stack([rng.permutation(16)[:K] for _ in range(N)])
    if case == "one_given_every_position":
        held = np.full((N, K), ELSEWHERE)
        held[:, 2] = 1
        return held
    if case == "one_given_none":
        return np.stack([rng.permutation([0, 1, 3, 9, 10, 11, 12])[:K]
                         for _ in range(N)])
    if case == "positions_that_chose_none":
        held = np.stack([rng.permutation(8)[:K] for _ in range(N)])
        held[::2] = ELSEWHERE + np.arange(K)
        return held
    if case == "groups_no_multiple_of_the_tile":    # 5, 17, 3 and 22 pairs
        held = np.full((N, K), ELSEWHERE)
        for e, count in enumerate((5, 17, 3, 22)):
            held[rng.permutation(N)[:count], e] = e
        return held
    if case == "every_pick_held":   # N x K pairs: what the buffers are sized for
        return np.stack([rng.permutation(E) for _ in range(N)])
    raise ValueError(case)


CASES = ("even", "one_given_every_position", "one_given_none",
         "positions_that_chose_none", "groups_no_multiple_of_the_tile",
         "every_pick_held")
PARTS = ("out", "dx", "dw", "dw1", "dw3", "dw2")


@pytest.fixture(scope="module")
def operands():
    keys = jax.random.split(jax.random.key(3), 6)
    weights = jax.nn.softmax(jax.random.normal(keys[1], (N, K)), axis=-1)
    return (jax.random.normal(keys[0], (N, D)), weights,
            jax.random.normal(keys[2], (E, D, F)) * 0.1,
            jax.random.normal(keys[3], (E, D, F)) * 0.1,
            jax.random.normal(keys[4], (E, F, D)) * 0.1,
            jax.random.normal(keys[5], (N, D)))


def forms(case, inputs, cot):
    """((out, dx, dw, dw1, dw3, dw2) of the kernels, the same of every held
    expert over every position) under `case`'s routing of `inputs` (x, w,
    w1, w3, w2), pulled back from `cot`."""
    held = jnp.asarray(routing(case), jnp.int32)
    picks = held[:, :, None] == jnp.arange(E)

    def oracle(x, w, w1, w3, w2):
        wts = jnp.sum(jnp.where(picks, w[:, :, None], 0.0), axis=1)
        return experts_over_all(x, wts, w1, w3, w2)

    def kernels(x, w, w1, w3, w2):
        return ge.grouped_experts(x, held, w, w1, w3, w2, tile=TILE,
                                  interpret=True)

    def results(form):
        out, pull = jax.vjp(form, *inputs)
        return (out,) + pull(cot)

    return results(kernels), results(oracle)


@pytest.fixture(scope="module")
def both_forms(operands):
    """case -> `forms` of it on the module's operands, computed once a
    case."""
    *inputs, cot = operands
    return functools.cache(lambda case: forms(case, inputs, cot))


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("case", CASES)
def test_the_grouped_product_is_every_expert_over_every_position(
        both_forms, case, part):
    """To bfloat16's rounding: the kernels' products take bfloat16 operands
    (what a TPU's default precision gives the einsums) where this CPU's oracle
    is exact float32; a pair dropped, a tile skipped, a row fetched from
    another position or a gradient added to another's would be off by
    tenths."""
    got, want = (r[PARTS.index(part)] for r in both_forms(case))
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got).all())
    scale = float(jnp.linalg.norm(want))
    if scale == 0:      # nothing was routed there: zeros, to the bit
        assert not bool(jnp.any(got))
        return
    assert float(jnp.linalg.norm(got - want)) / scale < 8e-3
    np.testing.assert_allclose(got, want,
                               atol=0.05 * float(jnp.abs(want).max()))


def test_an_expert_given_none_and_a_position_that_chose_none_read_zeros(
        both_forms):
    (_, _, _, dw1, _, dw2), _ = both_forms("one_given_none")
    assert not bool(jnp.any(dw1[2])) and not bool(jnp.any(dw2[2]))
    assert bool(jnp.any(dw1[3]))
    (out, dx, dw, *_), _ = both_forms("positions_that_chose_none")
    assert not bool(jnp.any(out[::2])) and not bool(jnp.any(dx[::2]))
    assert not bool(jnp.any(dw[::2])) and bool(jnp.any(out[1::2]))


@pytest.mark.parametrize("case", CASES)
def test_the_list_is_the_routings_pairs_ordered_by_expert(case):
    held = routing(case)
    weights = np.random.default_rng(1).random((N, K)).astype(np.float32)
    plan = ge.route_plan(jnp.asarray(held, jnp.int32), jnp.asarray(weights), E)
    mine = (held >= 0) & (held < E)
    pairs = sorted((held[n, j], n, weights[n, j])
                   for n, j in zip(*np.nonzero(mine)))
    total = len(pairs)
    np.testing.assert_array_equal(plan.rows[:total], [p[1] for p in pairs])
    np.testing.assert_array_equal(plan.wrow[:total, 0], [p[2] for p in pairs])
    assert bool((plan.wrow == plan.wrow[:, :1]).all())
    counts = [(held == e).sum() for e in range(E)]
    np.testing.assert_array_equal(plan.starts, np.cumsum([0] + counts))
    np.testing.assert_array_equal(plan.held, mine)
    np.testing.assert_array_equal(plan.count, mine.sum(axis=1))
    np.testing.assert_array_equal(   # where each sorted pair came from
        plan.place[:total], [n * K + list(held[n]).index(e)
                             for e, n, _ in pairs])
    assert sorted(np.asarray(plan.place)) == list(range(N * K))
    packed = np.asarray(plan.packed).reshape(N, K)
    for n in range(N):          # the inverse: pair (n, j) is where it says
        assert [pairs[at][:2] for at in packed[n, :mine[n].sum()]] == [
            (held[n, j], n) for j in np.flatnonzero(mine[n])]


@pytest.mark.parametrize("every_group", [False, True])
@pytest.mark.parametrize("counts", [(5, 17, 3, 22), (0, 64, 0, 0), (16, 16, 16, 16),
                                    (0, 0, 0, 0), (64, 64, 64, 64), (1, 0, 31, 0)])
def test_the_walk_visits_each_experts_tiles_once(counts, every_group):
    starts = np.cumsum((0,) + counts)
    tiles = N * K // TILE
    group, which, flags = (np.asarray(a) for a in ge.visit_plan(
        jnp.asarray(starts, jnp.int32), TILE, tiles, every_group))
    assert len(group) == tiles + E
    active = flags & ge.ACTIVE != 0
    want = [(e, t) for e in range(E)
            for t in (range(starts[e] // TILE, (starts[e + 1] - 1) // TILE + 1)
                      if counts[e] else
                      [min(starts[e] // TILE, tiles - 1)] * every_group)]
    assert list(zip(group[active], which[active])) == want
    assert not active[len(want):].any() and (np.diff(which) >= 0).all()
    # the steps past the end repeat the last visit: no block moves
    if want:
        assert (group[~active] == want[-1][0]).all()
        assert (which[~active] == want[-1][1]).all()
    empty = flags & ge.EMPTY != 0
    np.testing.assert_array_equal(
        empty[active], [counts[e] == 0 for e, _ in want])
    new_tile = flags & ge.NEW_TILE != 0
    new_group = flags & ge.NEW_GROUP != 0
    seen_tiles, seen_groups = set(), set()
    for step in np.flatnonzero(active):
        assert new_tile[step] == (which[step] not in seen_tiles)
        assert new_group[step] == (group[step] not in seen_groups)
        seen_tiles.add(which[step])
        seen_groups.add(group[step])
    assert int(ge.rows_run(jnp.asarray(counts, jnp.int32), D, F, TILE)) == (
        TILE * sum(1 for e, _ in want if counts[e]))


def test_which_form_runs_is_read_from_the_backend_and_the_shapes(monkeypatch):
    assert not ge.runs_here(4096, 2048, 768)               # this CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ge.runs_here(4096, 2048, 768) and ge.runs_here(2048, 2048, 768)
    assert not ge.runs_here(4096 + 64, 2048, 768)           # no whole tile
    assert not ge.runs_here(4096, 64, 48)                   # no whole lanes
    with pytest.raises(ValueError, match="tiles of"):
        ge.grouped_experts(jnp.zeros((6, 128)), jnp.zeros((6, 2), jnp.int32),
                           jnp.zeros((6, 2)), *(jnp.zeros((2, 128, 128)),) * 3)


WIDE = 20 * ge.LANES      # D / 128 = 20: no whole number of 8-sublane tiles
RELU_CASES = ("even", "one_given_every_position",
              "groups_no_multiple_of_the_tile")


@pytest.fixture(scope="module")
def relu_forms():
    """case -> the kernels' and the oracle's (out, dx, dw, dw1, dw3, dw2)
    with `act="relu"` at a hidden size of 2560."""
    keys = jax.random.split(jax.random.key(4), 6)
    inputs = (jax.random.normal(keys[0], (N, WIDE)),
              jax.nn.softmax(jax.random.normal(keys[1], (N, K)), axis=-1),
              jax.random.normal(keys[2], (E, WIDE, F)) * 0.05,
              jax.random.normal(keys[3], (E, WIDE, F)) * 0.05,
              jax.random.normal(keys[4], (E, F, WIDE)) * 0.1)
    cot = jax.random.normal(keys[5], (N, WIDE))

    def of(case):
        held = jnp.asarray(routing(case), jnp.int32)
        picks = held[:, :, None] == jnp.arange(E)

        def oracle(x, w, w1, w3, w2):
            # a ReLU's slope jumps at 0: the oracle's gate takes the
            # operands the kernel's takes, rounded to bfloat16 (exact
            # float32 gates have another sign at one entry in a hundred,
            # and the gradients through them differ by 6-8 %)
            x, w1, w3 = (a.astype(jnp.bfloat16).astype(jnp.float32)
                         for a in (x, w1, w3))
            wts = jnp.sum(jnp.where(picks, w[:, :, None], 0.0), axis=1)
            return experts_over_all(x, wts, w1, w3, w2, act=nn.relu)

        def kernels(x, w, w1, w3, w2):
            return ge.grouped_experts(x, held, w, w1, w3, w2, tile=TILE,
                                      interpret=True, act="relu")

        def results(form):
            out, pull = jax.vjp(form, *inputs)
            return (out,) + pull(cot)

        return results(kernels), results(oracle)

    return {case: of(case) for case in RELU_CASES}


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("case", RELU_CASES)
def test_the_relu_gate_at_twenty_lane_chunks_a_row(relu_forms, case, part):
    """As `test_the_grouped_product_is_every_expert_over_every_position`, to
    bfloat16's rounding: a ReGLU, and rows of 20 x 128 floats that travel as
    24 sublanes (a chunk read from the pad, or a row written over its
    neighbour's, would be off by tenths)."""
    got, want = (r[PARTS.index(part)] for r in relu_forms[case])
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got).all())
    scale = float(jnp.linalg.norm(want))
    assert scale > 0
    assert float(jnp.linalg.norm(got - want)) / scale < 8e-3
    np.testing.assert_allclose(got, want,
                               atol=0.05 * float(jnp.abs(want).max()))


def test_a_rows_sublanes_are_whole_tiles_and_the_act_is_one_of_two(monkeypatch):
    assert ge._chunks(2048) == 16 and ge._chunks(2560) == 24
    assert ge._chunks(128) == 8
    assert ge._in_rows(jnp.ones((4, 2560))).shape == (4, 24, ge.LANES)
    assert not bool(jnp.any(ge._in_rows(jnp.ones((4, 2560)))[:, 20:]))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ge.runs_here(8192, 2560, 768)
    with pytest.raises(ValueError, match="act"):
        ge.grouped_experts(jnp.zeros((16, 128)), jnp.zeros((16, 1), jnp.int32),
                           jnp.zeros((16, 1)), *(jnp.zeros((2, 128, 128)),) * 3,
                           tile=16, act="gelu")


TWO_BLOCKS = 2 * F        # under `small_fast_memory`: two blocks of F
BLOCK_CASES = ("even", "positions_that_chose_none",
               "groups_no_multiple_of_the_tile")


def small_fast_memory(patch):
    """A fast memory that holds a tile of 16 rows of D beside experts F
    wide and not beside experts 2 F wide: the limit is what the width block
    is worked out against, so that a width of 256 is walked in two blocks
    here as 1,536 is on the chip."""
    patch.setattr(ge, "VMEM_LIMIT", (ge._fast_bytes(D, F, TILE)
                                     + ge._fast_bytes(D, TWO_BLOCKS, TILE)) // 2)
    assert ge.width_block(D, TWO_BLOCKS, TILE) == F == ge.width_block(D, F, TILE)


@pytest.fixture(scope="module")
def block_forms():
    """case -> the kernels' and the oracle's (out, dx, dw, dw1, dw3, dw2) at
    a width of two blocks."""
    keys = jax.random.split(jax.random.key(6), 6)
    inputs = (jax.random.normal(keys[0], (N, D)),
              jax.nn.sigmoid(jax.random.normal(keys[1], (N, K))),
              jax.random.normal(keys[2], (E, D, TWO_BLOCKS)) * 0.1,
              jax.random.normal(keys[3], (E, D, TWO_BLOCKS)) * 0.1,
              jax.random.normal(keys[4], (E, TWO_BLOCKS, D)) * 0.07)
    cot = jax.random.normal(keys[5], (N, D))
    with pytest.MonkeyPatch.context() as patch:
        small_fast_memory(patch)
        return {case: forms(case, inputs, cot) for case in BLOCK_CASES}


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("case", BLOCK_CASES)
def test_a_width_of_two_blocks_is_every_expert_over_every_position(
        block_forms, case, part):
    """As `test_the_grouped_product_is_every_expert_over_every_position`, to
    bfloat16's rounding, with every expert's width walked in two blocks: a
    block multiplied by another's columns of w1, a half of the down product
    left out or added twice, or the router's weight given one block's
    gradient alone would be off by tenths."""
    got, want = (r[PARTS.index(part)] for r in block_forms[case])
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got).all())
    scale = float(jnp.linalg.norm(want))
    assert scale > 0
    assert float(jnp.linalg.norm(got - want)) / scale < 8e-3
    np.testing.assert_allclose(got, want,
                               atol=0.05 * float(jnp.abs(want).max()))


def test_the_width_block_is_read_from_the_shapes():
    """D 2048 x 768 (models/sdar.py) and D 2560 x 768
    (models/smallthinker.py) are walked whole, as before there was a block;
    models/lfm2.py's 2048 x 1,536 in two blocks of 768."""
    assert ge.width_block(2048, 768) == 768 == ge.width_block(2560, 768)
    assert ge.width_block(2048, 1536) == 768
    assert ge._fast_bytes(2048, 1536, ge.TILE) > ge.VMEM_LIMIT > ge._fast_bytes(
        2560, 768, ge.TILE)
    assert ge.width_block(2048, 2304) == 1152      # whole lanes that divide it
    with pytest.raises(ValueError, match="fast memory"):
        ge.width_block(2 ** 16, 768)


def test_the_list_of_two_blocks_holds_a_pair_once_a_block(monkeypatch):
    """`block_plan`: block b of expert e is group 2 e + b with e's pairs;
    one block is `route_plan` itself; the rows counted are a visit's at a
    block's share of the width."""
    small_fast_memory(monkeypatch)
    held = jnp.asarray(routing("groups_no_multiple_of_the_tile"), jnp.int32)
    w = jnp.asarray(np.random.default_rng(2).random((N, K)), jnp.float32)
    one, two = ge.block_plan(held, w, E, 1), ge.block_plan(held, w, E, 2)
    for a, b in zip(one, ge.route_plan(held, w, E)):
        np.testing.assert_array_equal(a, b)
    counts = np.diff(one.starts)
    np.testing.assert_array_equal(np.diff(two.starts), np.repeat(counts, 2))
    assert two.held.shape == (N, 2 * K) and two.rows.shape == (2 * N * K,)
    np.testing.assert_array_equal(two.count, 2 * one.count)
    for e in range(E):      # both blocks' lists are the expert's own
        rows = one.rows[one.starts[e]:one.starts[e + 1]]
        for b in range(2):
            lo = two.starts[2 * e + b]
            np.testing.assert_array_equal(two.rows[lo:lo + len(rows)], rows)
    def visits(plan):
        starts = np.asarray(plan.starts)
        return sum((hi - 1) // TILE - lo // TILE + 1
                   for lo, hi in zip(starts[:-1], starts[1:]))

    counts = jnp.asarray(counts, jnp.int32)
    assert (visits(one), visits(two)) == (6, 13)    # the second of half a width
    assert int(ge.rows_run(counts, D, F, TILE)) == TILE * 6
    assert int(ge.rows_run(counts, D, TWO_BLOCKS, TILE)) == TILE * 13 // 2
