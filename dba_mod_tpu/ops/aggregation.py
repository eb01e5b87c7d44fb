"""Server aggregation rules as pure jnp programs over *stacked* client updates.

Client updates arrive as a pytree whose leaves have a leading `clients` axis
(the TPU-native replacement for the reference's per-client Python dicts,
helper.py:193-231). Three rules, matching reference semantics:

- FedAvg (`average_shrink_models`, helper.py:240-257): global += η/no_models ·
  Σ_c Δ_c, applied to EVERY state entry (weights and BN stats alike), optional
  DP gaussian noise (helper.py:186-191, :253-254). Note the reference divides
  by `no_models`, not by Σ samples — unweighted; kept for parity.
- RFA geometric median (`geometric_median_update`, helper.py:295-373):
  Weiszfeld iterations with sample-count alphas, ftol early stop, oracle-call
  count, optional update-norm rejection. The reference crashes when Weiszfeld
  converges at iteration 0 (`wv=None` → `wv.cpu()`, helper.py:371); we fix it
  by always reporting the most recent weights.
- FoolsGold (`foolsgold_update`, helper.py:259-293 + class FoolsGold
  :527-607): cosine-similarity reweighting over the second-to-last trainable
  tensor's accumulated gradient, per-participant historical memory, pardoning,
  logit re-weighting, applied through one torch-SGD step on trainable params
  only.

Every rule additionally accepts a survivor mask ([C] — clients screened out
by the server's quarantine pass, fl/rounds.py): FedAvg renormalizes over
survivors, Weiszfeld zeroes the excluded clients' weights, FoolsGold masks
the excluded similarity rows and memory writes. Excluded payload rows are
where-zeroed FIRST (`survivor_sanitize`) so NaN/Inf quarantined payloads
cannot leak through `0 * NaN = NaN` arithmetic. With an all-ones mask every
masked rule reduces exactly (bitwise for FedAvg, to f32 identity for the
rest) to the dense rule — tests/test_faults.py pins this.

Beyond the reference's three rules, the wider defense grid (ROADMAP item 3)
adds three classical Byzantine-robust rules under the SAME survivor-mask
contract, so they compose with the quarantine screen and with the async
buffered merge (fl/async_rounds.py) unchanged:

- Krum / multi-Krum (`krum_update`, Blanchard et al., NeurIPS 2017): score
  each client by the sum of squared distances to its n−f−2 nearest peers,
  apply η · mean of the m lowest-scoring updates (m=1 is classic Krum).
- Coordinate-wise trimmed mean (`trimmed_mean_update`, Yin et al., ICML
  2018): per coordinate, drop the ⌊β·n⌋ smallest and largest survivor
  values and average the rest, apply with η.
- Coordinate-wise median (`coordinate_median_update`, Yin et al., ICML
  2018): per-coordinate survivor median, applied with η.

These three have no reference counterpart (no parity constraint); the
masked form IS the rule — mask=None runs the identical program with an
all-ones mask, so dense-reduction equivalence is structural and the tests
pin it against independent numpy oracles (tests/test_aggregation.py).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from dba_mod_tpu.ops.sgd import sgd_step


# ------------------------------------------------------------------- utilities
def flatten_stacked(tree: Any) -> jax.Array:
    """Flatten a client-stacked pytree ([C, ...] leaves) to a [C, P] matrix."""
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.concatenate(
        [l.reshape(l.shape[0], -1).astype(jnp.float32) for l in leaves], axis=1)


def unflatten_like(vec: jax.Array, tree: Any) -> Any:
    """Inverse of :func:`flatten_stacked` for a single [P] vector, shaped like
    one (un-stacked) element of `tree`."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out, off = [], 0
    for l in leaves:
        shape = l.shape[1:]
        size = 1
        for s in shape:
            size *= s
        out.append(vec[off:off + size].reshape(shape).astype(l.dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)


def _bc_mask(mask: jax.Array, leaf: jax.Array) -> jax.Array:
    """[C] mask → [C, 1, ...] broadcast against a client-stacked leaf."""
    return mask.reshape((mask.shape[0],) + (1,) * (leaf.ndim - 1))


def survivor_sanitize(tree: Any, mask: jax.Array) -> Any:
    """Where-zero the masked-out clients' rows of a stacked payload.

    Quarantined payloads may be NaN/Inf; plain `mask * leaf` would propagate
    them (0 · NaN = NaN), so exclusion must select, not multiply. With an
    all-ones mask this returns the input values bitwise unchanged."""
    return jax.tree_util.tree_map(
        lambda l: jnp.where(_bc_mask(mask > 0, l), l, jnp.zeros((), l.dtype)),
        tree)


def dp_noise_like(rng: jax.Array, tree: Any, sigma: float) -> Any:
    """Gaussian DP noise per state entry (helper.py:186-191)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(rng, len(leaves))
    noised = [jax.random.normal(k, l.shape, jnp.float32) * sigma
              for k, l in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, noised)


# --------------------------------------------------------------------- FedAvg
def fedavg_update(global_state: Any, stacked_deltas: Any, eta: float,
                  no_models: int, dp_sigma: float = 0.0,
                  rng: jax.Array | None = None) -> Any:
    """helper.py:240-257. `global_state` is the full model state (params + BN
    stats); `stacked_deltas` has a leading clients axis over the same tree."""
    scale = eta / no_models

    def upd(g, d):
        return (g + scale * jnp.sum(d, axis=0).astype(g.dtype)).astype(g.dtype)

    new_state = jax.tree_util.tree_map(upd, global_state, stacked_deltas)
    if dp_sigma and rng is not None:
        noise = dp_noise_like(rng, new_state, dp_sigma)
        new_state = jax.tree_util.tree_map(lambda s, n: s + n.astype(s.dtype),
                                           new_state, noise)
    return new_state


def fedavg_update_masked(global_state: Any, stacked_deltas: Any, eta: float,
                         no_models: int, mask: jax.Array,
                         counted: jax.Array, dp_sigma: float = 0.0,
                         rng: jax.Array | None = None) -> Any:
    """FedAvg renormalized over the survivor mask.

    Dense FedAvg divides by the static `no_models`; here the divisor drops
    one for every *counted* client the mask excludes (inert mesh-padding
    lanes — `counted` False — contribute zero deltas and never move the
    divisor, preserving the reference's static-divisor semantics). The scale
    is written as `(eta/no_models) · (no_models/divisor)` so an all-ones
    mask yields the dense rule's exact python-float scale — bitwise
    equivalence, not just tolerance."""
    deltas = survivor_sanitize(stacked_deltas, mask)
    excluded = jnp.sum((counted > 0) & ~(mask > 0))
    divisor = jnp.maximum(jnp.float32(no_models) - excluded, 1.0)
    ratio = jnp.float32(no_models) / divisor
    scale = (eta / no_models) * ratio

    def upd(g, d):
        return (g + scale * jnp.sum(d, axis=0).astype(g.dtype)).astype(g.dtype)

    new_state = jax.tree_util.tree_map(upd, global_state, deltas)
    if dp_sigma and rng is not None:
        noise = dp_noise_like(rng, new_state, dp_sigma)
        new_state = jax.tree_util.tree_map(lambda s, n: s + n.astype(s.dtype),
                                           new_state, noise)
    return new_state


# ------------------------------------------------------------- RFA / Weiszfeld
class RfaResult(NamedTuple):
    new_state: Any
    num_oracle_calls: jax.Array   # int32
    is_updated: jax.Array         # bool (norm rejection)
    wv: jax.Array                 # [C] final Weiszfeld weights
    distances: jax.Array          # [C] ‖median - Δ_c‖ (reference's out-alphas)
    nbt_median: jax.Array         # f32 scalar — the (truncated-int-valued)
                                  # `num_batches_tracked` entry of the median


def geometric_median_update(global_state: Any, stacked_deltas: Any,
                            num_samples: jax.Array, eta: float,
                            maxiter: int = 10, eps: float = 1e-5,
                            ftol: float = 1e-6,
                            max_update_norm: float | None = None,
                            dp_sigma: float = 0.0,
                            rng: jax.Array | None = None,
                            nbt_deltas: jax.Array | None = None,
                            n_bn: int = 0,
                            mask: jax.Array | None = None) -> RfaResult:
    """Weiszfeld geometric median of client deltas (helper.py:295-373).

    Runs the full `maxiter` iterations with a `done` mask emulating the
    reference's ftol break — identical numerics, static XLA control flow.

    `nbt_deltas` [C] / `n_bn`: the per-client `num_batches_tracked` deltas
    and the number of BN layers. The reference's client updates are full
    state_dicts, so the int64 batch counters participate in every Weiszfeld
    quantity (l2dist / objective / update-norm, helper.py:376-392) — with
    Dirichlet partitions the per-client counter deltas differ (≈ local step
    counts, ×γ for model-replacement clients), which measurably shifts the
    weights on BN models. The median's counter entry is truncated PER CLIENT
    contribution (weighted_average_oracle's `temp.type_as(data)` int cast,
    helper.py:410-415). The counter's effect on the APPLIED update is nil in
    every runnable reference config: on torch ≥1.5 `data.add_(float)` into
    int64 raises, and on the paper-era torch ≤1.4 the `median * eta` scalar
    multiply truncates eta<1 to 0 — the global counter is frozen either way,
    so this function folds the counter into the geometry only and reports
    `nbt_median` for the record.

    `mask` ([C], optional): survivor mask from the quarantine screen.
    Excluded clients get zero Weiszfeld weight at every iteration (their
    alphas are zeroed before normalization) and their point rows are
    where-zeroed so non-finite quarantined payloads cannot poison the
    distance geometry. mask=None (or all-ones) is the dense rule.
    """
    if mask is not None:
        stacked_deltas = survivor_sanitize(stacked_deltas, mask)
    points = flatten_stacked(stacked_deltas)                    # [C, P]
    alphas = num_samples.astype(jnp.float32)
    if mask is not None:
        alphas = alphas * mask.astype(jnp.float32)
    alphas = alphas / jnp.sum(alphas)
    nbt = (jnp.asarray(nbt_deltas, jnp.float32) if nbt_deltas is not None
           else jnp.zeros((points.shape[0],), jnp.float32))
    if mask is not None:
        nbt = nbt * mask.astype(jnp.float32)
    nbf = float(n_bn) if nbt_deltas is not None else 0.0

    def wavg(w):
        wn = w / jnp.sum(w)
        # per-client truncation of the counter contribution = the
        # reference's per-point `type_as(int64)` cast before accumulation
        return wn @ points, jnp.sum(jnp.trunc(wn * nbt))        # [P], scalar

    def dists(m, mn):
        sq = jnp.sum(jnp.square(points - m[None, :]), axis=1)
        return jnp.sqrt(sq + nbf * jnp.square(nbt - mn))

    def objective(m, mn):
        return jnp.sum(alphas * dists(m, mn))

    median0, nbt0 = wavg(alphas)
    obj0 = objective(median0, nbt0)

    def body(carry, _):
        median, nbt_med, obj, wv, done, calls = carry
        dist = dists(median, nbt_med)
        weights = alphas / jnp.maximum(eps, dist)
        weights = weights / jnp.sum(weights)
        new_median, new_nbt = wavg(weights)
        new_obj = objective(new_median, new_nbt)
        converged = jnp.abs(obj - new_obj) < ftol * new_obj
        step_done = done | converged
        # The reference records wv only on non-breaking iterations
        # (helper.py:352) and crashes when none happened; we instead always
        # keep the latest weights (the documented wv=None fix, SURVEY §7.2.8).
        median = jnp.where(done, median, new_median)
        nbt_med = jnp.where(done, nbt_med, new_nbt)
        obj = jnp.where(done, obj, new_obj)
        wv = jnp.where(done, wv, weights)
        calls = calls + jnp.where(done, 0, 1)
        return (median, nbt_med, obj, wv, step_done, calls), None

    init = (median0, nbt0, obj0, alphas, jnp.asarray(False), jnp.int32(1))
    (median, nbt_med, _obj, wv, _done, calls), _ = jax.lax.scan(
        body, init, None, length=maxiter)

    distances = dists(median, nbt_med)
    update_norm = jnp.sqrt(jnp.sum(jnp.square(median))
                           + nbf * jnp.square(nbt_med))
    is_updated = (jnp.asarray(True) if max_update_norm is None
                  else update_norm < max_update_norm)

    median_tree = unflatten_like(median * eta, stacked_deltas)
    if dp_sigma and rng is not None:
        noise = dp_noise_like(rng, median_tree, dp_sigma)
        median_tree = jax.tree_util.tree_map(
            lambda m, n: m + n.astype(m.dtype), median_tree, noise)

    new_state = jax.tree_util.tree_map(
        lambda g, u: jnp.where(is_updated, g + u.astype(g.dtype), g),
        global_state, median_tree)
    return RfaResult(new_state, calls, is_updated, wv, distances, nbt_med)


# ----------------------------------------------------------------- FoolsGold
class FoolsGoldState(NamedTuple):
    """Cross-round per-participant gradient memory (helper.py:545-549), keyed
    by participant id instead of the reference's name-keyed dict."""
    memory: jax.Array  # [num_participants, grad_len] f32


def foolsgold_init(num_participants: int, grad_len: int) -> FoolsGoldState:
    return FoolsGoldState(memory=jnp.zeros((num_participants, grad_len),
                                           jnp.float32))


def foolsgold_weights(feature_grads: jax.Array,
                      mask: jax.Array | None = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """The FoolsGold re-weighting (helper.py:574-607) on a [C, L] gradient
    matrix. Returns (wv [C], alpha [C]).

    `mask` ([C], optional): survivor mask. Excluded rows are where-zeroed
    before the cosine matrix (a NaN row would poison every similarity) and
    their wv is zeroed ahead of the max-normalization so a quarantined
    client can neither receive nor distort aggregation weight. mask=None
    (or all-ones) is the dense rule."""
    eps = 1e-12
    if mask is not None:
        feature_grads = jnp.where(mask[:, None] > 0, feature_grads,
                                  jnp.zeros((), feature_grads.dtype))
    norms = jnp.linalg.norm(feature_grads, axis=1)
    normed = feature_grads / jnp.maximum(norms, eps)[:, None]
    n = feature_grads.shape[0]
    cs = normed @ normed.T - jnp.eye(n)

    maxcs = jnp.max(cs, axis=1)
    # pardoning (helper.py:584-589): cs[i,j] *= maxcs[i]/maxcs[j] when
    # maxcs[i] < maxcs[j]
    ratio = maxcs[:, None] / maxcs[None, :]
    pardon = jnp.where(maxcs[:, None] < maxcs[None, :], ratio, 1.0)
    pardon = pardon * (1.0 - jnp.eye(n)) + jnp.eye(n)
    cs = cs * pardon

    row_max = jnp.max(cs, axis=1)
    wv = 1.0 - row_max
    wv = jnp.clip(wv, 0.0, 1.0)
    alpha = row_max

    if mask is not None:
        # zero excluded rows BEFORE the max-normalization: a zeroed feature
        # row has no similarity to anyone (wv = 1) and would otherwise both
        # keep full weight and deflate every survivor's normalized weight
        wv = wv * mask.astype(wv.dtype)
    wv = wv / jnp.max(wv)
    wv = jnp.where(wv == 1.0, 0.99, wv)
    logit = jnp.log(wv / (1.0 - wv)) + 0.5
    # reference: wv[(np.isinf(wv) + wv > 1)] = 1; wv[wv < 0] = 0
    # (bool-add precedence quirk: (isinf + wv) > 1 — helper.py:603)
    inf_mask = jnp.isinf(logit).astype(logit.dtype)
    logit = jnp.where(inf_mask + logit > 1.0, 1.0, logit)
    logit = jnp.where(logit < 0.0, 0.0, logit)
    return logit, alpha


class FoolsGoldResult(NamedTuple):
    new_params: Any
    new_fg_state: FoolsGoldState
    wv: jax.Array
    alpha: jax.Array


def foolsgold_update(global_params: Any, stacked_grads: Any,
                     feature_grads: jax.Array, participant_ids: jax.Array,
                     fg_state: FoolsGoldState, eta: float, lr: float,
                     momentum: float, weight_decay: float,
                     use_memory: bool = True,
                     mask: jax.Array | None = None) -> FoolsGoldResult:
    """helper.py:259-293 + FoolsGold.aggregate_gradients (:534-572).

    `stacked_grads`: per-client accumulated gradients over trainable params
    ([C, ...] leaves, from the client step's grad accumulation —
    image_train.py:94-100). `feature_grads`: [C, L] flattened gradient of the
    similarity layer (the reference's `client_grads[i][-2]`). Only trainable
    params are updated; BN stats are untouched (the reference steps an
    optimizer over named_parameters only).

    `mask` ([C], optional): survivor mask. Excluded clients' grads are
    where-zeroed, their similarity rows are masked (see
    :func:`foolsgold_weights`), and — critically — their feature gradients
    are NOT written into the cross-round memory: a quarantined NaN payload
    must not poison the defense's history. mask=None (or all-ones) is the
    dense rule.
    """
    if mask is not None:
        stacked_grads = survivor_sanitize(stacked_grads, mask)
        feature_grads = jnp.where(mask[:, None] > 0, feature_grads,
                                  jnp.zeros((), feature_grads.dtype))
    memory = fg_state.memory.at[participant_ids].add(feature_grads)
    current = memory[participant_ids] if use_memory else feature_grads
    wv, alpha = foolsgold_weights(current, mask=mask)

    num_clients = feature_grads.shape[0]

    def agg(leaf):  # [C, ...] -> [...]
        w = wv.reshape((num_clients,) + (1,) * (leaf.ndim - 1))
        return jnp.sum(w * leaf.astype(jnp.float32), axis=0) / num_clients

    agg_grads = jax.tree_util.tree_map(agg, stacked_grads)
    # Apply via one fresh torch-SGD step with grad = η·agg (helper.py:278-290);
    # fresh momentum buffers are zero, so momentum is a no-op.
    scaled = jax.tree_util.tree_map(lambda g: (eta * g), agg_grads)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, global_params)
    new_params, _ = sgd_step(global_params, scaled, zeros, lr, momentum,
                             weight_decay)
    return FoolsGoldResult(new_params, FoolsGoldState(memory), wv, alpha)


# ------------------------------------------------------- Krum / multi-Krum
# Sentinels for the masked geometry: finite (inf-free) so a degenerate
# survivor set still sorts deterministically — an excluded client's score
# (_EXCLUDED) always exceeds any survivor's, even the 1-survivor case whose
# score is a sum of _FAR pair distances. Both fit comfortably in f32.
# Plain floats: a jnp scalar here would initialise the XLA backend while the
# package is imported, before jax.distributed.initialize() can run.
_FAR = 1e30       # pair distance to/from an excluded client
_EXCLUDED = 1e35  # score of an excluded client


class KrumResult(NamedTuple):
    new_state: Any
    wv: jax.Array      # [C] applied weights: 1/m_eff for selected, else 0
    scores: jax.Array  # [C] Krum scores (_EXCLUDED for masked-out clients)


def _ones_mask(tree: Any) -> jax.Array:
    leaf = jax.tree_util.tree_leaves(tree)[0]
    return jnp.ones((leaf.shape[0],), jnp.float32)


def krum_update(global_state: Any, stacked_deltas: Any, eta: float,
                num_selected: int, byz_f: int,
                mask: jax.Array | None = None, dp_sigma: float = 0.0,
                rng: jax.Array | None = None) -> KrumResult:
    """Krum / multi-Krum (Blanchard et al., NeurIPS 2017) over survivors.

    score_i = Σ of the n−f−2 smallest squared distances from client i to the
    other survivors (n = survivor count, f = `byz_f`); the `num_selected`
    lowest-scoring survivors are averaged and applied as η · mean — m=1 is
    classic Krum, m>1 multi-Krum. The neighbor count is clipped to
    [1, n−1] so undersized survivor sets (n < f+3) degrade to
    nearest-neighbor scoring instead of an invalid slice.

    `mask` ([C], optional): survivor-mask contract — excluded rows are
    where-zeroed, their pair distances pinned to a far sentinel (never a
    nearest neighbor), their scores pinned above every survivor's, and the
    selection size shrinks to min(num_selected, n). mask=None runs the same
    program with an all-ones mask (dense reduction is structural)."""
    if mask is None:
        mask_f = _ones_mask(stacked_deltas)
    else:
        mask_f = (mask > 0).astype(jnp.float32)
        stacked_deltas = survivor_sanitize(stacked_deltas, mask)
    pts = flatten_stacked(stacked_deltas)                        # [C, P]
    C = pts.shape[0]
    sq_norms = jnp.sum(jnp.square(pts), axis=1)                  # [C]
    gram = pts @ pts.T
    d2 = sq_norms[:, None] + sq_norms[None, :] - 2.0 * gram
    d2 = jnp.maximum(d2, 0.0)
    alive = mask_f > 0
    valid_pair = (alive[:, None] & alive[None, :]
                  & ~jnp.eye(C, dtype=bool))
    d2 = jnp.where(valid_pair, d2, _FAR)
    n_alive = jnp.sum(mask_f)
    # n − f − 2 closest peers, clipped to the survivors actually available
    nb = jnp.clip(n_alive - byz_f - 2.0, 1.0,
                  jnp.maximum(n_alive - 1.0, 1.0)).astype(jnp.int32)
    d2_sorted = jnp.sort(d2, axis=1)                             # [C, C]
    near = jnp.arange(C)[None, :] < nb                           # [C, C]
    scores = jnp.sum(jnp.where(near, d2_sorted, 0.0), axis=1)
    scores = jnp.where(alive, scores, _EXCLUDED)
    m_eff = jnp.clip(jnp.int32(num_selected), 1,
                     jnp.maximum(n_alive.astype(jnp.int32), 1))
    rank = jnp.argsort(jnp.argsort(scores))                      # stable
    sel = (rank < m_eff) & alive
    wv = sel.astype(jnp.float32) / m_eff.astype(jnp.float32)

    def upd(g, d):
        chosen = jnp.sum(_bc_mask(wv, d) * d.astype(jnp.float32), axis=0)
        return (g + eta * chosen.astype(g.dtype)).astype(g.dtype)

    new_state = jax.tree_util.tree_map(upd, global_state, stacked_deltas)
    if dp_sigma and rng is not None:
        noise = dp_noise_like(rng, new_state, dp_sigma)
        new_state = jax.tree_util.tree_map(lambda s, n: s + n.astype(s.dtype),
                                           new_state, noise)
    return KrumResult(new_state, wv, scores)


# ------------------------------------- coordinate-wise trimmed mean / median
class CoordwiseResult(NamedTuple):
    new_state: Any
    wv: jax.Array  # [C] uniform survivor weights (the recorded per-client
                   # contribution; coordinate-wise rules have no single
                   # per-client scalar weight)


def _sorted_survivor_columns(stacked_deltas: Any,
                             mask_f: jax.Array) -> Tuple[jax.Array,
                                                         jax.Array]:
    """Columns of the [C, P] survivor matrix sorted ascending with excluded
    rows pushed past the survivors (+inf), plus the survivor count. Rows
    [0, n) of each sorted column are exactly the survivor values."""
    pts = flatten_stacked(stacked_deltas)
    pts = jnp.where(mask_f[:, None] > 0, pts, jnp.inf)
    return jnp.sort(pts, axis=0), jnp.sum(mask_f)


def trimmed_mean_update(global_state: Any, stacked_deltas: Any, eta: float,
                        beta: float, mask: jax.Array | None = None,
                        dp_sigma: float = 0.0,
                        rng: jax.Array | None = None) -> CoordwiseResult:
    """Coordinate-wise β-trimmed mean (Yin et al., ICML 2018): per
    coordinate, drop the k = ⌊β·n⌋ smallest and k largest survivor values
    (k clipped so at least one value remains) and average the rest; apply
    the trimmed mean with η. Survivor-mask contract as in
    :func:`krum_update`."""
    if mask is None:
        mask_f = _ones_mask(stacked_deltas)
    else:
        mask_f = (mask > 0).astype(jnp.float32)
        stacked_deltas = survivor_sanitize(stacked_deltas, mask)
    pts_sorted, n_alive = _sorted_survivor_columns(stacked_deltas, mask_f)
    n_i = n_alive.astype(jnp.int32)
    k = jnp.minimum(jnp.floor(beta * n_alive).astype(jnp.int32),
                    (n_i - 1) // 2)
    row = jnp.arange(pts_sorted.shape[0])[:, None]               # [C, 1]
    keep = (row >= k) & (row < n_i - k)
    kept = jnp.sum(jnp.where(keep, pts_sorted, 0.0), axis=0)
    count = jnp.maximum(n_alive - 2.0 * k.astype(jnp.float32), 1.0)
    mean_vec = kept / count                                      # [P]
    update_tree = unflatten_like(mean_vec * eta, stacked_deltas)
    new_state = jax.tree_util.tree_map(
        lambda g, u: (g + u.astype(g.dtype)).astype(g.dtype),
        global_state, update_tree)
    if dp_sigma and rng is not None:
        noise = dp_noise_like(rng, new_state, dp_sigma)
        new_state = jax.tree_util.tree_map(lambda s, n: s + n.astype(s.dtype),
                                           new_state, noise)
    return CoordwiseResult(new_state, mask_f / jnp.maximum(n_alive, 1.0))


def coordinate_median_update(global_state: Any, stacked_deltas: Any,
                             eta: float, mask: jax.Array | None = None,
                             dp_sigma: float = 0.0,
                             rng: jax.Array | None = None) -> CoordwiseResult:
    """Coordinate-wise survivor median (Yin et al., ICML 2018), even counts
    averaging the two central values (numpy's convention); applied with η.
    Survivor-mask contract as in :func:`krum_update`."""
    if mask is None:
        mask_f = _ones_mask(stacked_deltas)
    else:
        mask_f = (mask > 0).astype(jnp.float32)
        stacked_deltas = survivor_sanitize(stacked_deltas, mask)
    pts_sorted, n_alive = _sorted_survivor_columns(stacked_deltas, mask_f)
    n_i = jnp.maximum(n_alive.astype(jnp.int32), 1)
    lo = (n_i - 1) // 2
    hi = n_i // 2
    P = pts_sorted.shape[1]
    lo_vals = jnp.take_along_axis(
        pts_sorted, jnp.full((1, P), lo, jnp.int32), axis=0)[0]
    hi_vals = jnp.take_along_axis(
        pts_sorted, jnp.full((1, P), hi, jnp.int32), axis=0)[0]
    med = 0.5 * (lo_vals + hi_vals)                              # [P]
    update_tree = unflatten_like(med * eta, stacked_deltas)
    new_state = jax.tree_util.tree_map(
        lambda g, u: (g + u.astype(g.dtype)).astype(g.dtype),
        global_state, update_tree)
    if dp_sigma and rng is not None:
        noise = dp_noise_like(rng, new_state, dp_sigma)
        new_state = jax.tree_util.tree_map(lambda s, n: s + n.astype(s.dtype),
                                           new_state, noise)
    return CoordwiseResult(new_state, mask_f / jnp.maximum(n_alive, 1.0))
