"""The grouped expert product (dba_mod_tpu/ops/grouped_experts.py) in Pallas'
interpreter on the CPU, held to `models/sdar.py::experts_over_all` (every held
expert over every position, which is also what every CPU run of the model
keeps): the output and the gradients to x, the router's weights and the three
matrices under the routings the one path must hold in; the list and the walk
against numpy counts of the same routing. The same with the gate's activation
`relu` (models/smallthinker.py's ReGLU experts) at a hidden size of 20 x 128,
whose rows travel padded to 24 sublanes."""
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


@pytest.fixture(scope="module")
def both_forms(operands):
    """case -> ((out, dx, dw, dw1, dw3, dw2) of the kernels, the same of
    every held expert over every position), computed once a case."""
    *inputs, cot = operands
    done = {}

    def of(case):
        if case not in done:
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

            done[case] = results(kernels), results(oracle)
        return done[case]

    return of


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
    assert int(ge.rows_run(jnp.asarray(counts, jnp.int32), TILE)) == (
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
