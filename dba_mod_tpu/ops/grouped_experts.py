"""The held experts' gated feed-forward (`act(x W1) * (x W3)) W2`, the gate's
activation `act` a static argument: `silu`, a SwiGLU, or `relu`, a ReGLU) over
the rows the router sent them, and no others: a grouped product (Pallas, TPU)
over one list of the (position, held expert) pairs ordered by expert.

    grouped_experts(x [N, D], held [N, k] int32, w [N, k],
                    w1 [E, D, F], w3 [E, D, F], w2 [E, F, D], act="silu")
        -> sum_{j: 0 <= held[n, j] < E} w[n, j] GLU_{held[n, j]}(x[n])  [N, D]

`held[n, j]` is the j-th expert position n chose, counted from the first
expert this chip holds (outside `[0, E)`: another chip's, nothing is added
for it); a position names an expert once. What `models/sdar.py::
experts_over_all` computes over every (position, held expert) pair and
multiplies by 0 where the router did not choose, this computes for the chosen
pairs only. Three expert layers call it: models/sdar.py's (D 2048, F 768,
`silu`), models/smallthinker.py's (D 2560, F 768, `relu`) and
models/lfm2.py's (D 2048, F 1,536, `silu`). **One path**: no buffer an
expert, no capacity, no dropped pair, no second form to fall back on. The
shapes are static and sized for the most a call can route (N x k pairs); the
work follows the pairs really routed: a tile of pairs past the last one is
never visited, whichever expert holds how many (one may hold every position,
another none).

**The list** (`route_plan`, XLA, a few small arrays): the pairs' keys
(the expert, then the pair's place in `[N, k]`; a pair of another chip's
expert sorts last) go through one sort with the router's weight as the
payload: pair `p` of the sorted list is
position `rows[p]` with weight `wrow[p]`, expert e's pairs are
`[starts[e], starts[e + 1])`, positions ascending. The inverse needs no
second sort: the place of pair (n, j) is `starts[e]` plus the positions
before n that chose e (a cumulative sum); a position's places, those it has
first, are `packed[n]`. (Back from the list's order to `[N, k]`'s, for the
weights' gradient: a sorted key holds its pair's place of origin, and a
sort by that is the inverse.)

**The kernels** walk the list a tile of `TILE` pairs at a time
(`visit_plan`): a visit is (a tile, an expert with pairs in it), so a tile
two experts share is visited once for each with the other's rows masked; an
expert's weights are fetched once, when the walk reaches its first tile, and
stay while its tiles last. The arrays of the walk and `rows` are scalar
prefetched. **A tile's rows are fetched by the kernel itself**, row by row
from `x` where it lies (a DMA a pair, `rows[p]` its source): no `[N x k, D]`
copy of the gathered rows exists, forward or backward. The visits past the
last one repeat its blocks (no transfer) and compute nothing.

**The width block.** The kernels keep a group's three matrices whole in
fast memory (the float32 blocks twice, the next group's arriving while this
one's multiply, and a bfloat16 copy): at D 2048 x F 1,536 that alone is 94 MB
of the 100 MiB `VMEM_LIMIT`. A gated feed-forward is a sum over blocks of
its width, `sum_b (act(x W1[:, b]) * (x W3[:, b])) W2[b, :]`, so where an
expert's matrices do not fit, **the list's groups are the experts' width
blocks** (`block_plan`): block b of expert e, `width_block(D, F, tile)`
wide, is group `e B + b`, a routed pair is in the list once a block (`[N, k x
B]` places), and a group's matrices are blocks of the `[E, D, F]` and `[E, F,
D]` arrays where they lie (`_specs`: block `g % B` of expert `g // B`; no
copy). The walk, the kernels and the combine are the same: a position's
output is the sum over its places, a block's partial down-product a place;
the gradient to a pair's weight the sum over its blocks'. A pair's row is
fetched once a block; a block's matrices once. The block is the most whole
lanes that divide F and fit `VMEM_LIMIT` by `_fast_bytes`' count of what the
largest kernel holds: F itself at D 2048 and 2560 x F 768 (B = 1: the list
is `route_plan`'s, and nothing differs from a product without blocks), 768
at 2048 x 1,536 (B = 2). From the shapes; nothing configures it.

**A row's layout.** A row of D floats travels as `[C, LANES]`, C = D / 128
sublanes, and a copy moves whole 8-sublane tiles. Where C is a multiple of 8
(D 2048: 16) a row is its own tiles. Where it is not (D 2560: 20) every row
is padded to `_chunks(D)` sublanes, the next multiple of 8 (24): `_in_rows`
pads the inputs with zeros (the bytes a tiled `[N, 20, 128]` array takes in
memory anyway), the buffers and the per-pair outputs hold the padded rows,
`_fetched` and `_put_rows` read and write a row's first C sublanes, and the
pad of an output is never written and never read.

- forward: gate and up products, `act(gate) * up * w`, the down product:
  `ys [N x k, D]`, a row a pair;
- backward over the rows: recomputes gate and up from the fetched rows of x,
  fetches the rows of the incoming gradient, and gives the pairs' gradient
  rows `dxs`, the weights' `dwrow`, and the three `[N x k, F]` bfloat16
  operands of the weights' products;
- backward over the weights: `x^T dgate`, `x^T dup`, `hidden^T dout` an
  expert, accumulated over its tiles in fast memory; an expert without pairs
  is visited once and given zeros.

**Dispatch and combine are gathers both ways** (`jax.custom_vjp` around the
whole): the output is the sum of `ys` over a position's places in the list,
the gradient to x the sum of `dxs` over them (`combine`: a kernel that
fetches, a tile of positions at a time, the rows of the pairs there are),
the gradient to `w[n, j]` is `dwrow` at pair (n, j)'s place: nothing is
scatter-added into `[N, D]`.

**Arithmetic**: that of float32 state at the TPU's default matmul precision,
which is what `experts_over_all`'s einsums do there: every product takes
operands rounded to bfloat16 and accumulates in float32, everything between
the products and every output is float32. The order of the sums differs: a
pair's down-projection is summed over F alone and a position's pairs are
added in float32 afterwards, where the einsum contracts over E x F at once.

The forward pass and the backward pass are each one jitted function of
(tile, blocks, shapes): every layer of a model, its recomputation and its
evaluation passes share one trace and one lowering of the list and the
kernels. `TILE` was chosen on the chip (PERF.md, PR 41), the form of the
width block priced there (PR 46). No reference counterpart.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 256       # pairs a tile: the rows of one visit's products
COMBINE_TILE = 128   # positions a step of the combine
LANES = 128
VMEM_LIMIT = 100 * 1024 * 1024   # of the chip's 128 MiB; the default is 16
# a visit's flags: it computes (the visits past the last real one do not);
# its tile's first visit (fetch the rows, overwrite the outputs); its
# expert's first visit (convert the weights, overwrite their gradients); an
# expert without pairs (the weights' backward alone visits one: zeros)
ACTIVE, NEW_TILE, NEW_GROUP, EMPTY = 1, 2, 4, 8
NT = (((1,), (1,)), ((), ()))   # a @ b^T
TN = (((0,), (0,)), ((), ()))   # a^T @ b
KERNEL_NAME = "grouped_experts"


class RoutePlan(NamedTuple):
    """The routed pairs ordered by expert (see the module's docstring)."""
    rows: jax.Array     # [N x k] int32: the position of sorted pair p
    wrow: jax.Array     # [N x k, LANES]: its weight, the same in every lane
    starts: jax.Array   # [E + 1] int32: expert e's pairs [starts[e], starts[e + 1])
    held: jax.Array     # [N, k] bool: whether pair (n, j) is in the list
    place: jax.Array    # [N x k] int32: sorted pair p was pair place[p] of [N, k]
    packed: jax.Array   # [N x k] int32: where a position's pairs are in the
    #                     list, those it has first: `count[n]` of its k places
    count: jax.Array    # [N] int32: how many of its k pairs are in the list


def route_plan(held, w, experts: int) -> RoutePlan:
    n, k = held.shape
    mine = (held >= 0) & (held < experts)
    picks = held[:, :, None] == jnp.arange(experts)             # [N, k, E]
    chosen = jnp.any(picks, axis=1)
    before = jnp.cumsum(chosen, axis=0, dtype=jnp.int32) - chosen
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(
        jnp.sum(chosen, axis=0, dtype=jnp.int32))])
    slot = jnp.sum(jnp.where(picks, (starts[:-1] + before)[:, None, :], 0),
                   axis=-1)
    # a pair's key: its expert (another chip's sorts last), then its place in
    # [N, k], which is unique and ascends with the position
    origin = jnp.arange(n * k, dtype=jnp.int32).reshape(n, k)
    keys, sorted_w = jax.lax.sort(
        (jnp.where(mine, held, experts).reshape(-1) * (n * k)
         + origin.reshape(-1), w.reshape(-1)), num_keys=1, is_stable=False)
    origin = keys % (n * k)
    # a position's places in the list moved to the front of its k
    rank = jnp.cumsum(mine, axis=1, dtype=jnp.int32) - mine
    packed = jnp.sum(jnp.where(
        mine[:, :, None] & (rank[:, :, None] == jnp.arange(k)),
        slot[:, :, None], 0), axis=1)
    return RoutePlan(origin // k, jnp.broadcast_to(sorted_w[:, None],
                                                   (n * k, LANES)),
                     starts, mine, origin, packed.reshape(-1),
                     jnp.sum(mine, axis=1, dtype=jnp.int32))


def visit_plan(starts, tile: int, tiles: int, every_group: bool):
    """(expert, tile, flags) of each of the walk's `tiles + E` grid steps,
    int32: expert after expert, an expert's tiles in order; with
    `every_group` an expert without pairs is visited once (EMPTY), at the
    tile its pairs would start in."""
    experts = starts.shape[0] - 1
    first = jnp.minimum(starts[:-1] // tile, tiles - 1)
    some = starts[1:] > starts[:-1]
    visits = jnp.where(some, (starts[1:] - 1) // tile - first + 1,
                       1 if every_group else 0)
    upto = jnp.cumsum(visits)
    step = jnp.arange(tiles + experts, dtype=jnp.int32)
    at = jnp.clip(step, 0, upto[-1] - 1)      # the steps past the end repeat
    group = jnp.minimum(jnp.sum(upto[None, :] <= at[:, None], axis=1),
                        experts - 1).astype(jnp.int32)
    which = first[group] + at - (upto[group] - visits[group])
    which = jnp.clip(which, 0, tiles - 1).astype(jnp.int32)
    new = lambda a: jnp.concatenate([jnp.ones((1,), bool), a[1:] != a[:-1]])
    active = step < upto[-1]
    flags = (ACTIVE * active + NEW_TILE * (active & new(which))
             + NEW_GROUP * (active & new(group))
             + EMPTY * (active & ~some[group]))
    return group, which, flags.astype(jnp.int32)


def runs_here(positions: int, hidden: int, width: int) -> bool:
    """Whether the grouped product is the form this process runs for a call
    of `positions` rows: on a TPU, whole tiles, whole lanes. Read from the
    backend and the shapes; nothing configures it."""
    return (jax.default_backend() == "tpu" and positions % TILE == 0
            and hidden % LANES == 0 and width % LANES == 0)


def _fast_bytes(d: int, width: int, tile: int) -> int:
    """What the rows' backward kernel, the largest, keeps in fast memory at
    once for experts `width` wide: the three matrices' float32 blocks twice
    (the next group's arrive while this one's multiply) and once in
    bfloat16; the two row buffers; its blocks of the lists' arrays twice
    (the pairs' rows, three `[tile, width]` operands, two lane-replicated
    columns); and a visit's values, eight `[tile, width]` and two
    `[tile, D]` float32 (what the chip's compiler refuses: 82 MiB at D 2560
    x 768, 60 at 2048 x 768, where this says 83.0 and 65.8)."""
    row = _chunks(d) * LANES * 4
    return (3 * d * width * (2 * 4 + 2) + 2 * tile * row
            + 2 * tile * (row + 3 * width * 2 + 2 * LANES * 4)
            + tile * (8 * width * 4 + 2 * row))


def width_block(d: int, f: int, tile: int = TILE) -> int:
    """The width a visit multiplies: the most whole lanes that divide an
    expert's width `f` and fit `VMEM_LIMIT` (the module's docstring: the
    width block). From the shapes and nothing else."""
    lanes = f // LANES
    for parts in range(1, lanes + 1):
        width = f // parts
        if lanes % parts == 0 and _fast_bytes(d, width, tile) <= VMEM_LIMIT:
            return width
    raise ValueError(f"grouped experts: a tile of {tile} rows of {d} and one "
                     f"lane block of an expert's width pass {VMEM_LIMIT} "
                     f"bytes of fast memory")


def rows_run(counts, d: int, f: int, tile: int = TILE):
    """The rows the forward's visits multiply for a call whose experts, `f`
    wide over rows of `d`, were given `counts` [E] pairs: tile padding, and
    the rows a shared tile is multiplied again for, included (int32 scalar):
    `visit_plan`'s active visits, counted without the walk. A visit of one
    of an expert's B width blocks counts as a B-th of its rows."""
    blocks = f // width_block(d, f, tile)
    if blocks > 1:
        counts = jnp.repeat(counts, blocks)
    ends = jnp.cumsum(counts, dtype=jnp.int32)
    visits = jnp.where(counts > 0,
                       (ends - 1) // tile - (ends - counts) // tile + 1, 0)
    rows = tile * jnp.sum(visits, dtype=jnp.int32)
    return rows if blocks == 1 else rows // blocks


def _across(x, width: int):
    """A lane-replicated `[rows, LANES]` column across `width` lanes."""
    return x if width == LANES else jnp.tile(x, (1, width // LANES))


def _fetch(rows, base, count, sources, buffers, sems):
    """Rows `rows[base + r]`, r < count, of each source (`[N, D / LANES,
    LANES]`, left where it lies) into sublanes `[r C, (r + 1) C)` of its
    buffer, C = D / LANES: every copy started, then every copy awaited. (A
    copy moves whole 8-sublane tiles, so a row travels as `[C, LANES]`.)"""
    chunks = sources[0].shape[1]

    def start(r, carry):
        for src, buf, sem in zip(sources, buffers, sems):
            pltpu.make_async_copy(src.at[rows[base + r]],
                                  buf.at[pl.ds(r * chunks, chunks)],
                                  sem).start()
        return carry

    def wait(r, carry):
        for src, buf, sem in zip(sources, buffers, sems):
            pltpu.make_async_copy(src.at[0], buf.at[pl.ds(0, chunks)],
                                  sem).wait()
        return carry

    jax.lax.fori_loop(0, count, start, 0)
    jax.lax.fori_loop(0, count, wait, 0)


def _chunks(d: int) -> int:
    """Sublanes a row of `d` floats travels as: D / LANES up to whole
    8-sublane tiles (the module's docstring: a row's layout)."""
    return -(-(d // LANES) // 8) * 8


def _fetched(buf, size: int, d: int):
    """The `[size, d]` rows of a buffer `_fetch` filled: lane chunk c of
    every row is the sublanes c, c + C, c + 2 C, ..., C the sublanes a row
    travels as; a row's pad past `d` is left where it is."""
    chunks = buf.shape[0] // size
    return jnp.concatenate([buf[pl.ds(c, size, stride=chunks), :]
                            for c in range(d // LANES)], axis=1)


def _visit(group, tile, flags, starts, size: int):
    """(flags, the rows of this visit's tile of `size` that are its expert's
    [size, 1] bool, the tile's first pair, how many pairs of the list it
    holds)."""
    v = pl.program_id(0)
    g, flag = group[v], flags[v]
    base = tile[v] * size
    idx = base + jax.lax.broadcasted_iota(jnp.int32, (size, 1), 0)
    mine = (idx >= starts[g]) & (idx < starts[g + 1])
    count = jnp.clip(starts[starts.shape[0] - 1] - base, 0, size)
    return flag, mine, base, count


def _put(ref, value, mine, first):
    """Write the expert's rows of `value`; the tile's other rows are zeros
    on its first visit (whatever the block held) and what they were after
    it."""
    kept = jnp.where(first, jnp.zeros_like(ref), ref[...])
    ref[...] = jnp.where(mine, value.astype(ref.dtype), kept)


def _put_rows(ref, value, mine, first):
    """`_put` into a block that holds row r as its sublanes `[r C, (r + 1)
    C)` (what `_fetch` copies a row of): lane chunk c of every row to the
    sublanes c, c + C, ...; a row's pad (C over the value's width) is not
    written."""
    size = value.shape[0]
    chunks = ref.shape[0] // size
    for c in range(value.shape[1] // LANES):
        at = pl.ds(c, size, stride=chunks)
        kept = jnp.where(first, 0.0, ref[at, :])
        ref[at, :] = jnp.where(mine, value[:, c * LANES:(c + 1) * LANES], kept)


def _convert(flag, pairs, rows: int = 256):
    """The expert's float32 weights to the bfloat16 the products take, once
    an expert (`rows` of a matrix a trip of a loop: less code than the whole
    matrix written out)."""
    @pl.when(flag & NEW_GROUP != 0)
    def _():
        for src, dst in pairs:
            step = rows if src.shape[0] % rows == 0 else src.shape[0]

            def some(i, carry, src=src, dst=dst, step=step):
                at = pl.ds(pl.multiple_of(i * step, step), step)
                dst[at, :] = src[at, :].astype(jnp.bfloat16)
                return carry

            jax.lax.fori_loop(0, src.shape[0] // step, some, 0)


def _gate_up(x, w1b, w3b):
    xb = x.astype(jnp.bfloat16)
    gate = jnp.dot(xb, w1b[...], preferred_element_type=jnp.float32)
    up = jnp.dot(xb, w3b[...], preferred_element_type=jnp.float32)
    return gate, up


def _forward_kernel(group, tile, flags, starts, rows, x_any, wrow_ref, w1_ref,
                    w3_ref, w2_ref, ys_ref, xbuf, w1b, w3b, w2b, sem, *,
                    act: str):
    size, d = wrow_ref.shape[0], w1_ref.shape[0]
    flag, mine, base, count = _visit(group, tile, flags, starts, size)
    first = flag & NEW_TILE != 0

    @pl.when(first)
    def _():
        _fetch(rows, base, count, [x_any], [xbuf], [sem.at[0]])

    _convert(flag, [(w1_ref, w1b), (w3_ref, w3b), (w2_ref, w2b)])

    @pl.when(flag & ACTIVE != 0)
    def _():
        gate, up = _gate_up(_fetched(xbuf, size, d), w1b, w3b)
        gated = (gate * jax.nn.sigmoid(gate) if act == "silu"
                 else jnp.maximum(gate, 0.0))
        hidden = gated * up * _across(wrow_ref[...], gate.shape[1])
        # a row of another expert, or past the list's end (never fetched:
        # whatever the buffer held), multiplies as zeros
        hidden = jnp.where(mine, hidden, 0).astype(jnp.bfloat16)
        _put_rows(ys_ref, jnp.dot(hidden, w2b[...],
                                  preferred_element_type=jnp.float32),
                  mine, first)


def _backward_rows_kernel(group, tile, flags, starts, rows, x_any, d_any,
                          wrow_ref, w1_ref, w3_ref, w2_ref, dxs_ref,
                          dwrow_ref, hidden_ref, dgate_ref, dup_ref, xbuf,
                          dbuf, w1b, w3b, w2b, sem, *, act: str):
    size, d = wrow_ref.shape[0], w1_ref.shape[0]
    flag, mine, base, count = _visit(group, tile, flags, starts, size)
    first = flag & NEW_TILE != 0

    @pl.when(first)
    def _():
        _fetch(rows, base, count, [x_any, d_any], [xbuf, dbuf],
               [sem.at[0], sem.at[1]])

    _convert(flag, [(w1_ref, w1b), (w3_ref, w3b), (w2_ref, w2b)])

    @pl.when(flag & ACTIVE != 0)
    def _():
        gate, up = _gate_up(_fetched(xbuf, size, d), w1b, w3b)
        if act == "silu":
            sig = jax.nn.sigmoid(gate)
            gated = gate * sig
        else:
            gated = jnp.maximum(gate, 0.0)
        weight = _across(wrow_ref[...], gate.shape[1])
        dhidden = jax.lax.dot_general(
            _fetched(dbuf, size, d).astype(jnp.bfloat16), w2b[...], NT,
            preferred_element_type=jnp.float32)               # [tile, F]
        plain = gated * up
        dwrow = jnp.sum(dhidden * plain, axis=1, keepdims=True)
        dplain = dhidden * weight
        if act == "silu":
            dgate = dplain * up * sig * (1 + gate * (1 - sig))
        else:               # relu's slope at 0 is 0, as jax.nn.relu's
            dgate = jnp.where(gate > 0, dplain * up, 0.0)
        dgate = jnp.where(mine, dgate, 0).astype(jnp.bfloat16)
        dup = jnp.where(mine, dplain * gated, 0).astype(jnp.bfloat16)
        dxs = (jax.lax.dot_general(dgate, w1b[...], NT,
                                   preferred_element_type=jnp.float32)
               + jax.lax.dot_general(dup, w3b[...], NT,
                                     preferred_element_type=jnp.float32))
        _put_rows(dxs_ref, dxs, mine, first)
        _put(dwrow_ref, jnp.broadcast_to(dwrow, dwrow_ref.shape), mine, first)
        _put(hidden_ref, plain * weight, mine, first)
        _put(dgate_ref, dgate, mine, first)
        _put(dup_ref, dup, mine, first)


def _backward_weights_kernel(group, tile, flags, starts, rows, x_any, d_any,
                             hidden_ref, dgate_ref, dup_ref, dw1_ref, dw3_ref,
                             dw2_ref, xbuf, dbuf, sem):
    size, d = hidden_ref.shape[0], dw1_ref.shape[0]
    flag, mine, base, count = _visit(group, tile, flags, starts, size)

    @pl.when(flag & NEW_TILE != 0)
    def _():
        _fetch(rows, base, count, [x_any, d_any], [xbuf, dbuf],
               [sem.at[0], sem.at[1]])

    @pl.when(flag & EMPTY != 0)
    def _():
        for ref in (dw1_ref, dw3_ref, dw2_ref):
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    @pl.when(flag & (ACTIVE | EMPTY) == ACTIVE)
    def _():
        # the expert's rows alone: another's, and the rows past the list's
        # end (never fetched), multiply as zeros
        xb = jnp.where(mine, _fetched(xbuf, size, d), 0).astype(jnp.bfloat16)
        db = jnp.where(mine, _fetched(dbuf, size, d), 0).astype(jnp.bfloat16)
        grads = [
            (dw1_ref, jax.lax.dot_general(
                xb, dgate_ref[...], TN, preferred_element_type=jnp.float32)),
            (dw3_ref, jax.lax.dot_general(
                xb, dup_ref[...], TN, preferred_element_type=jnp.float32)),
            (dw2_ref, jax.lax.dot_general(
                hidden_ref[...], db, TN, preferred_element_type=jnp.float32))]
        for ref, grad in grads:
            @pl.when(flag & NEW_GROUP != 0)
            def _(ref=ref, grad=grad):
                ref[...] = grad

            @pl.when(flag & NEW_GROUP == 0)
            def _(ref=ref, grad=grad):
                ref[...] += grad


def _specs(tile: int, d: int, width: int, blocks: int):
    """Block specs: a `[N x k, width]` array a tile of the walk at a time;
    the width block of an expert's `[D, F]` matrix, and of its `[F, D]` one,
    that the walk's group g names (block g % blocks of expert g // blocks);
    an array left where it is (the kernel fetches its rows). An index map
    reads the walk (group, tile, ...)."""
    by_tile = lambda wide: pl.BlockSpec(
        (tile, wide), lambda v, group, which, *rest: (which[v], 0))

    def split(g):   # one block: the expert itself, as without blocks
        return (g, 0) if blocks == 1 else (g // blocks, g % blocks)

    across = pl.BlockSpec((None, d, width), lambda v, group, *rest: (
        split(group[v])[0], 0, split(group[v])[1]))
    down = pl.BlockSpec((None, width, d), lambda v, group, *rest: (
        *split(group[v]), 0))
    return by_tile, across, down, pl.BlockSpec(memory_space=pl.ANY)


def _pair_rows(pairs: int, d: int, tile: int):
    """(shape, block spec) of a `[N x k, D]` output a later kernel fetches
    rows of: written as `[N x k x C, LANES]`, a row its own C = `_chunks(D)`
    sublanes (`_put_rows`)."""
    chunks = _chunks(d)
    return (jax.ShapeDtypeStruct((pairs * chunks, LANES), jnp.float32),
            pl.BlockSpec((tile * chunks, LANES),
                         lambda v, group, which, *rest: (which[v], 0)))


def _call(kernel, tile, interpret, name, plan: RoutePlan, every_group,
          inputs, in_specs, out_shape, out_specs, scratch):
    pairs = plan.rows.shape[0]
    walk = visit_plan(plan.starts, tile, pairs // tile, every_group)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(walk[0].shape[0],),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=KERNEL_NAME + "_" + name,
    )(*walk, plan.starts, plan.rows, *inputs)


def _row_buffers(d: int, tile: int, fetched: int):
    """What `_fetch` fills: a buffer a fetched array, a semaphore each."""
    return ([pltpu.VMEM((tile * _chunks(d), LANES), jnp.float32)] * fetched,
            [pltpu.SemaphoreType.DMA((fetched,))])


def _scratch(d: int, width: int, tile: int, fetched: int):
    """Row buffers, the group's three blocks in bfloat16, semaphores."""
    buffers, sems = _row_buffers(d, tile, fetched)
    return buffers + [pltpu.VMEM((d, width), jnp.bfloat16),
                      pltpu.VMEM((d, width), jnp.bfloat16),
                      pltpu.VMEM((width, d), jnp.bfloat16)] + sems


def _in_rows(x):
    """`[N, D]` as the `[N, C, LANES]` a row of which is whole tiles, so that
    a copy can move one row: C = `_chunks(D)`, zeros past D / LANES."""
    rows = x.reshape(x.shape[0], -1, LANES)
    pad = _chunks(x.shape[1]) - rows.shape[1]
    return jnp.pad(rows, ((0, 0), (0, pad), (0, 0))) if pad else rows


def _widths(plan: RoutePlan, w1):
    """(D, the width of a group's block, the blocks an expert's width is
    walked in): the list's groups are the experts' width blocks."""
    experts, d, f = w1.shape
    blocks = (plan.starts.shape[0] - 1) // experts
    return d, f // blocks, blocks


def _forward(tile: int, interpret: bool, act: str, plan: RoutePlan, x, w1,
             w3, w2):
    """-> ys [P, C, LANES] float32, C = `_chunks(D)`: sorted pair p's
    weighted expert output (its width block's part of it)."""
    d, width, blocks = _widths(plan, w1)
    by_tile, across, down, in_place = _specs(tile, d, width, blocks)
    shape, spec = _pair_rows(plan.rows.shape[0], d, tile)
    return _call(
        functools.partial(_forward_kernel, act=act), tile, interpret,
        "forward", plan, False,
        (_in_rows(x), plan.wrow, w1, w3, w2),
        [in_place, by_tile(LANES), across, across, down],
        shape, spec, _scratch(d, width, tile, 1)).reshape(-1, _chunks(d),
                                                          LANES)


def _backward(tile: int, interpret: bool, act: str, plan: RoutePlan, x, w1,
              w3, w2, dout):
    """-> (dxs [P, C, LANES], dwrow [P, LANES], dw1, dw3, dw2): the pairs'
    gradient rows and weights' gradients, the experts' matrices'
    gradients."""
    pairs = plan.rows.shape[0]
    d, width, blocks = _widths(plan, w1)
    by_tile, across, down, in_place = _specs(tile, d, width, blocks)
    operand = jax.ShapeDtypeStruct((pairs, width), jnp.bfloat16)
    x, dout = _in_rows(x), _in_rows(dout)
    shape, spec = _pair_rows(pairs, d, tile)
    dxs, dwrow, hidden, dgate, dup = _call(
        functools.partial(_backward_rows_kernel, act=act), tile, interpret,
        "backward_rows", plan, False,
        (x, dout, plan.wrow, w1, w3, w2),
        [in_place, in_place, by_tile(LANES), across, across, down],
        [shape, jax.ShapeDtypeStruct((pairs, LANES), jnp.float32), operand,
         operand, operand],
        [spec, by_tile(LANES), by_tile(width), by_tile(width),
         by_tile(width)],
        _scratch(d, width, tile, 2))
    dw1, dw3, dw2 = _call(
        _backward_weights_kernel, tile, interpret, "backward_weights", plan,
        True, (x, dout, hidden, dgate, dup),
        [in_place, in_place, by_tile(width), by_tile(width), by_tile(width)],
        [jax.ShapeDtypeStruct(w.shape, jnp.float32) for w in (w1, w3, w2)],
        [across, across, down],
        sum(_row_buffers(d, tile, 2), []))
    return dxs.reshape(pairs, -1, LANES), dwrow, dw1, dw3, dw2


def _combine_kernel(packed, count, src_any, count_ref, out_ref, buf, sem):
    """A tile of positions: each fetches the rows of its pairs (its r-th into
    buffer r), then the buffers are added up where a position has an r-th
    pair."""
    size, slots = out_ref.shape[0], buf.shape[0]
    chunks = buf.shape[1] // size
    base = pl.program_id(0) * size

    def start(i, carry):
        pairs, most = carry
        mine = count[base + i]

        def one(r, _):
            pltpu.make_async_copy(
                src_any.at[packed[(base + i) * slots + r]],
                buf.at[r, pl.ds(i * chunks, chunks)], sem.at[0]).start()
            return _

        jax.lax.fori_loop(0, mine, one, 0)
        return pairs + mine, jnp.maximum(most, mine)

    def wait(i, carry):
        pltpu.make_async_copy(src_any.at[0], buf.at[0, pl.ds(0, chunks)],
                              sem.at[0]).wait()
        return carry

    pairs, most = jax.lax.fori_loop(0, size, start,
                                    (jnp.int32(0), jnp.int32(0)))
    jax.lax.fori_loop(0, pairs, wait, 0)
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def add(r, carry):
        out_ref[...] += jnp.where(
            count_ref[:, :1] > r,
            _fetched(buf.at[r], size, out_ref.shape[1]), 0)
        return carry

    jax.lax.fori_loop(0, most, add, 0)


def _combine(tile: int, interpret: bool, per_pair, plan: RoutePlan, d: int):
    n, slots = plan.held.shape
    chunks = per_pair.shape[1]
    return pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((tile, LANES), lambda t, *rest: (t, 0))],
            out_specs=pl.BlockSpec((tile, d), lambda t, *rest: (t, 0)),
            scratch_shapes=[
                pltpu.VMEM((slots, tile * chunks, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=KERNEL_NAME + "_combine",
    )(plan.packed, plan.count, per_pair,
      jnp.broadcast_to(plan.count[:, None], (n, LANES)))


def combine(per_pair, plan: RoutePlan, tile: int = COMBINE_TILE,
            interpret: bool = False, d: int | None = None):
    """A position's pairs' rows of `per_pair [N x k, C, LANES]` added up:
    [N, D] (`d`; C x LANES where the rows carry no pad). The kernel fetches
    the rows of the pairs there are, a tile of positions at a time: nothing
    is read for a place of the k that holds no pair of this chip."""
    return _combine(min(tile, plan.held.shape[0]), interpret, per_pair, plan,
                    d or per_pair.shape[1] * LANES)


def block_plan(held, w, experts: int, blocks: int) -> RoutePlan:
    """`route_plan` over the experts' width blocks: block b of expert e is
    group `e blocks + b` of the list, and a pair is in the list once a
    block, side by side in `[N, k x blocks]`. One block: `route_plan`."""
    if blocks == 1:
        return route_plan(held, w, experts)
    n, k = held.shape
    mine = (held >= 0) & (held < experts)
    groups = (jnp.where(mine, held, experts)[:, :, None] * blocks
              + jnp.arange(blocks, dtype=held.dtype))
    return route_plan(groups.reshape(n, k * blocks),
                      jnp.repeat(w, blocks, axis=1), experts * blocks)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _apply(tile: int, interpret: bool, act: str, blocks: int, x, held, w, w1,
           w3, w2):
    """-> (the layer's output [N, D], the list it was computed by)."""
    plan = block_plan(held, w, w1.shape[0], blocks)
    ys = _forward(tile, interpret, act, plan, x, w1, w3, w2)
    return combine(ys, plan, interpret=interpret, d=x.shape[1]), plan


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _pull(tile: int, interpret: bool, act: str, plan: RoutePlan, x, w1, w3,
          w2, dout):
    """-> the gradients to (x, w, w1, w3, w2)."""
    dxs, dwrow, dw1, dw3, dw2 = _backward(tile, interpret, act, plan, x, w1,
                                          w3, w2, dout)
    # back to the order of [N, k]: the sort's own inverse, one more sort
    _, dw = jax.lax.sort((plan.place, dwrow[:, 0]), num_keys=1,
                         is_stable=False)
    dw = jnp.where(plan.held, dw.reshape(plan.held.shape), 0)
    blocks = _widths(plan, w1)[2]
    if blocks > 1:      # a pair's weight multiplied each of its blocks
        dw = jnp.sum(dw.reshape(dw.shape[0], -1, blocks), axis=-1)
    return (combine(dxs, plan, interpret=interpret, d=x.shape[1]), dw, dw1,
            dw3, dw2)


@functools.lru_cache(maxsize=8)
def _experts_of(tile: int, interpret: bool, act: str, blocks: int):
    @jax.custom_vjp
    def experts(x, held, w, w1, w3, w2):
        return _apply(tile, interpret, act, blocks, x, held, w, w1, w3, w2)[0]

    def experts_fwd(x, held, w, w1, w3, w2):
        out, plan = _apply(tile, interpret, act, blocks, x, held, w, w1, w3,
                           w2)
        return out, (plan, x, w1, w3, w2)

    def experts_bwd(saved, dout):
        dx, dw, dw1, dw3, dw2 = _pull(tile, interpret, act, *saved, dout)
        return (dx, np.zeros(dw.shape, jax.dtypes.float0), dw, dw1, dw3, dw2)

    experts.defvjp(experts_fwd, experts_bwd)
    return jax.jit(experts)     # a call site binds one cached trace


ACTS = ("silu", "relu")   # the gate's activation: a SwiGLU, a ReGLU


def grouped_experts(x, held, w, w1, w3, w2, tile: int = TILE,
                    interpret: bool = False, act: str = "silu"):
    """See the module's docstring. `tile`, `interpret`: a narrow tile in
    Pallas' interpreter, for a test without the chip."""
    n, d = x.shape
    if act not in ACTS:
        raise ValueError(f"grouped experts: act {act!r} is none of {ACTS}")
    if held.shape != w.shape or held.shape[0] != n or (n * held.shape[1]) % tile:
        raise ValueError(f"grouped experts: {held.shape} picks and {w.shape} "
                         f"weights for {n} positions in tiles of {tile}")
    # the kernels fetch float32 rows and round every operand themselves
    weights = [m.astype(jnp.float32) for m in (w1, w3, w2)]
    f = w1.shape[2]
    out = _experts_of(tile, bool(interpret), act,
                      f // width_block(d, f, tile))(
        x.astype(jnp.float32), held, w.astype(jnp.float32), *weights)
    return out.astype(x.dtype)
