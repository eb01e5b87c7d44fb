"""Fused per-step state update: torch-SGD + validity select + FoolsGold
accumulation + BN select as ONE logical op over the whole client state.

Why: the client step updates ~60 parameter tensors per scan step; XLA emits
one elementwise kernel per leaf, and on TPU each small kernel pays a fixed
launch/ramp cost that dominates the narrow-model train phase (measured ~4 ms
of a ~13 ms step on the bench workload — see bench.py's phase report). The
math is embarrassingly fusable; XLA just has no horizontal-fusion pass for
it. A Pallas TPU kernel can read ALL the small leaves in one launch.

Shape problem: the client step is written per-client and vmapped over the
stacked clients axis (fl/rounds.py), and Pallas' automatic vmap rule blocks
per-lane (width-1 leading blocks), which the TPU lowering rejects for
non-aligned shapes. `jax.custom_batching.custom_vmap` solves it exactly: the
unbatched definition is the plain per-leaf jnp math (bit-identical to the
historical path, used for grad-free semantics and non-TPU backends), and the
batch rule receives the full stacked [C, ...] leaves and dispatches a few
multi-tensor Pallas kernels over them.

Semantics (must stay bit-exact with ops/sgd.py::sgd_step + the
jnp.where-based validity selects in fl/client.py):

    g'  = g + weight_decay * w
    m'  = momentum * m + g'
    w'  = w - lr * m'                      (lr per client, traced)
    out = where(valid, updated, old)       for w, m, fg (+= g), bn (new)

Used only when the clients axis is NOT mesh-sharded (GSPMD cannot partition
through a custom call); the mesh path keeps the per-leaf jnp form. No
reference counterpart — this is TPU-native machinery under the reference's
per-client `optimizer.step()` (image_train.py:220)."""
from __future__ import annotations

from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Total VMEM-resident bytes allowed per fused kernel (all inputs + outputs;
# grid=1, full-array blocks). v5e has ~16 MB of VMEM per core; sizes must be
# accounted in the TILED layout — a [10, 32] f32 occupies a full (8, 128)
# tile grid, 6.4× its logical bytes.
_VMEM_BUDGET = 6 * 1024 * 1024

KERNEL_NAME = "fused_sgd_update"

# kind → (#inputs, #outputs) per leaf
_ARITY = {"sgd": (3, 2), "acc": (2, 1), "sel": (2, 1)}


def _ceil(a: int, b: int) -> int:
    return -(-a // b) * b


def _padded_size(shape) -> int:
    """Element count in TPU tiled layout: trailing two dims pad to (8, 128)."""
    if len(shape) < 2:
        return _ceil(int(np.prod(shape)) if shape else 1, 128)
    lead = 1
    for d in shape[:-2]:
        lead *= d
    return lead * _ceil(shape[-2], 8) * _ceil(shape[-1], 128)


def _leaf_bytes(kind: str, shape) -> int:
    n_in, n_out = _ARITY[kind]
    return (n_in + n_out) * _padded_size(shape) * 4  # f32


def _build_kernel(kinds: List[str], momentum: float, weight_decay: float):
    """Kernel over leaves in their NATURAL shapes — reshaping [C, ...] leaves
    to 2-D before the call would be a physical re-tiling copy on TPU (layout
    is tiled over the trailing dims), which costs more than the fusion wins.
    lr/valid arrive as [C, 1] and are re-broadcast per leaf rank in-kernel."""
    n_in = sum(_ARITY[k][0] for k in kinds)

    def kernel(*refs):
        lr0 = refs[0][...]          # [C, 1]
        keep0 = refs[1][...] == 1.0  # [C, 1] bool
        ins = refs[2:2 + n_in]
        outs = refs[2 + n_in:]

        def ranked(v, rank):
            return v.reshape((v.shape[0],) + (1,) * (rank - 1))

        i = o = 0
        for kind in kinds:
            rank = ins[i].shape and len(ins[i].shape)
            lr = ranked(lr0, rank)
            keep = ranked(keep0, rank)
            if kind == "sgd":
                w, g, m = ins[i][...], ins[i + 1][...], ins[i + 2][...]
                i += 3
                g2 = g + weight_decay * w
                m2 = momentum * m + g2
                w2 = w - lr * m2
                outs[o][...] = jnp.where(keep, w2, w)
                outs[o + 1][...] = jnp.where(keep, m2, m)
                o += 2
            elif kind == "acc":
                f, g = ins[i][...], ins[i + 1][...]
                i += 2
                outs[o][...] = jnp.where(keep, f + g, f)
                o += 1
            else:  # sel
                new, old = ins[i][...], ins[i + 1][...]
                i += 2
                outs[o][...] = jnp.where(keep, new, old)
                o += 1

    return kernel


def _run_chunks(entries, lr2, valid2, momentum: float, weight_decay: float,
                interpret: bool):
    """entries: list of (kind, [in arrays [C, d]]). Greedy-packs into
    VMEM-budget chunks, one pallas_call per chunk. Returns flat output list
    aligned with entries."""
    from jax.experimental import pallas as pl

    outputs: List[Any] = [None] * len(entries)
    chunk: List[int] = []
    used = 0
    n_calls = 0

    def flush():
        nonlocal chunk, used, n_calls
        if not chunk:
            return
        kinds = [entries[j][0] for j in chunk]
        ins = [a for j in chunk for a in entries[j][1]]
        out_shape = []
        for j in chunk:
            kind, arrs = entries[j]
            out_shape += [jax.ShapeDtypeStruct(arrs[0].shape, arrs[0].dtype)
                          ] * _ARITY[kind][1]
        outs = pl.pallas_call(
            _build_kernel(kinds, momentum, weight_decay),
            out_shape=out_shape, interpret=interpret,
            # the name a device trace shows the kernel under; the chunk
            # index follows it (metadata only)
            name=f"{KERNEL_NAME}_{n_calls}",
        )(lr2, valid2, *ins)
        n_calls += 1
        outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
        o = 0
        for j in chunk:
            n_out = _ARITY[entries[j][0]][1]
            outputs[j] = tuple(outs[o:o + n_out])
            o += n_out
        chunk, used = [], 0

    for j, (kind, arrs) in enumerate(entries):
        nbytes = _leaf_bytes(kind, arrs[0].shape)
        if used + nbytes > _VMEM_BUDGET:
            flush()
        chunk.append(j)
        used += nbytes
    flush()
    return outputs


def make_fused_step_update(momentum: float, weight_decay: float,
                           fg_enabled: bool, use_pallas: bool,
                           interpret: bool = False):
    """Returns fused(lr, valid, params, grads, mom, fg, bn_new, bn_old) ->
    (new_params, new_mom, new_fg, new_bn). `fg` may be an empty tree when
    FoolsGold is off. When use_pallas is False, returns the plain per-leaf
    jnp implementation (today's exact path, traced through vmap as before)."""

    def reference(lr, valid, params, grads, mom, fg, bn_new, bn_old):
        def upd(w, g, m):
            g2 = g + weight_decay * w
            m2 = momentum * m + g2
            return w - lr * m2, m2

        pairs = jax.tree_util.tree_map(upd, params, grads, mom)
        is_pair = lambda t: isinstance(t, tuple)
        w2 = jax.tree_util.tree_map(lambda t: t[0], pairs, is_leaf=is_pair)
        m2 = jax.tree_util.tree_map(lambda t: t[1], pairs, is_leaf=is_pair)
        sel = lambda a, b: jax.tree_util.tree_map(
            lambda x, y: jnp.where(valid, x, y), a, b)
        new_fg = (sel(jax.tree_util.tree_map(jnp.add, fg, grads), fg)
                  if fg_enabled else fg)
        return sel(w2, params), sel(m2, mom), new_fg, sel(bn_new, bn_old)

    if not use_pallas:
        return reference

    from jax import custom_batching

    fused = custom_batching.custom_vmap(reference)

    @fused.def_vmap
    def _batch_rule(axis_size, in_batched, lr, valid, params, grads, mom, fg,
                    bn_new, bn_old):
        # every operand is batched on axis 0 in the client step; broadcast
        # any stragglers so the kernel sees uniform [C, ...] leaves
        def bcast(tree, b_tree):
            return jax.tree_util.tree_map(
                lambda l, b: l if b else jnp.broadcast_to(
                    l[None], (axis_size,) + l.shape), tree, b_tree)

        (lr, valid, params, grads, mom, fg, bn_new, bn_old) = (
            bcast(t, b) for t, b in zip(
                (lr, valid, params, grads, mom, fg, bn_new, bn_old),
                in_batched))
        C = axis_size
        lr2 = lr.reshape(C, 1).astype(jnp.float32)
        valid2 = valid.reshape(C, 1).astype(jnp.float32)

        p_leaves, p_def = jax.tree_util.tree_flatten(params)
        g_leaves = jax.tree_util.tree_leaves(grads)
        m_leaves = jax.tree_util.tree_leaves(mom)
        f_leaves, f_def = jax.tree_util.tree_flatten(fg)
        bnn_leaves, bn_def = jax.tree_util.tree_flatten(bn_new)
        bno_leaves = jax.tree_util.tree_leaves(bn_old)

        # natural shapes throughout — no reshapes (TPU re-tiling copies)
        entries: List[Tuple[str, List[Any]]] = []
        fallback: dict[int, Any] = {}
        order = []  # (kind tag, leaf index within its group)
        for i, (w, g, m) in enumerate(zip(p_leaves, g_leaves, m_leaves)):
            entries.append(("sgd", [w, g, m]))
            order.append(("p", i))
        if fg_enabled:
            for i, (f, g) in enumerate(zip(f_leaves, g_leaves)):
                entries.append(("acc", [f, g]))
                order.append(("f", i))
        for i, (bn, bo) in enumerate(zip(bnn_leaves, bno_leaves)):
            entries.append(("sel", [bn, bo]))
            order.append(("b", i))

        def rk(v, like):
            return v.reshape((C,) + (1,) * (like.ndim - 1))

        # Fallback to jnp for (a) leaves too big for a single-block kernel —
        # bandwidth-bound, nothing to win — and (b) rank>2 leaves: the launch
        # floor lives in the many tiny rank-2 BN/bias tensors, and
        # higher-rank full-array blocks both blow the tiled-VMEM budget and
        # exercise much less-travelled Mosaic lowering paths.
        big = [j for j, (k, a) in enumerate(entries)
               if a[0].ndim != 2
               or _leaf_bytes(k, a[0].shape) > _VMEM_BUDGET]
        for j in big:
            kind, arrs = entries[j]
            keep = rk(valid2, arrs[0]) == 1.0
            if kind == "sgd":
                w, g, m = arrs
                g2 = g + weight_decay * w
                m2 = momentum * m + g2
                w2 = w - rk(lr2, w) * m2
                fallback[j] = (jnp.where(keep, w2, w),
                               jnp.where(keep, m2, m))
            elif kind == "acc":
                f, g = arrs
                fallback[j] = (jnp.where(keep, f + g, f),)
            else:
                bn, bo = arrs
                fallback[j] = (jnp.where(keep, bn, bo),)
        small_entries = [e for j, e in enumerate(entries) if j not in fallback]
        small_out = _run_chunks(small_entries, lr2, valid2, momentum,
                                weight_decay, interpret)
        outs: List[Any] = []
        it = iter(small_out)
        for j in range(len(entries)):
            outs.append(fallback[j] if j in fallback else next(it))

        new_p, new_m = list(p_leaves), list(m_leaves)
        new_f = list(f_leaves)
        new_b = list(bnn_leaves)
        for (tag, i), out in zip(order, outs):
            if tag == "p":
                new_p[i], new_m[i] = out[0], out[1]
            elif tag == "f":
                new_f[i] = out[0]
            else:
                new_b[i] = out[0]
        result = (jax.tree_util.tree_unflatten(p_def, new_p),
                  jax.tree_util.tree_unflatten(p_def, new_m),
                  jax.tree_util.tree_unflatten(f_def, new_f),
                  jax.tree_util.tree_unflatten(bn_def, new_b))
        out_batched = jax.tree_util.tree_map(lambda _: True, result)
        return result, out_batched

    return fused
