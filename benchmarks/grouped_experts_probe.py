"""Chip probe of the expert layer's candidates at a model's own shapes (PR 41;
PR 46: a second call): what each form of the held experts' products costs
alone, a layer's call at a time, before the model is touched.

    chiprun --timeout 1500 -- python -m benchmarks.grouped_experts_probe --seed N
    JAX_PLATFORMS=cpu python -m benchmarks.grouped_experts_probe --rehearse

`--calls` names the calls, each read from its configuration file
(`CALLS`): `sdar` (`configs/sdar_params.yaml`: 4,096 positions, both
streams of a 2,048-token row, hidden 2,048, 16 held experts of 128 with
width 768, top-8 of a softmax) and `lfm2` (`configs/lfm2_params.yaml`: 4,096
positions, two rows, 8 held of 64 with width 1,536, which the kernels walk
in two blocks of 768, top-4 of a sigmoid with `expert_bias`, renormalised),
routed by a seeded router over seeded rows of which `--alike` in every
hundred are one row (a diffusion step's MASK positions, a packed row's most
frequent token: the load the layer must tolerate; 35 and 10 where not
given). Timed, each jitted, warm: `ms` the median of `--repeats` calls on the
host's clock (it holds the launch), `device_ms` the device's busy time a call
from a profiler trace of four, `top` the operations that took most of it:

- `dense`: `models/sdar.py::experts_over_all`, forward, and forward with the
  backward pass: what every CPU run keeps;
- `grouped.<tile>`: `ops/grouped_experts.py`, the same two, a tile size each;
  and its parts alone at `TILE`: the list (`block_plan`; its sort and its
  cumulative sum alone), the forward kernel, the combine at three tiles of
  positions, the two backward kernels;
- `ragged_dot`: `jax.lax.ragged_dot` over a worst-case list of gathered rows
  (XLA's gather included), one of the three products: whether XLA:TPU visits
  the groups' tiles only.

And the proof that the forms are one function on the chip: the relative L2
distance of the grouped form's output and gradients from the dense form's,
both at the device's default precision.

Prints one JSON line a reading and a last line `{"ok": ...}`. With
`--rehearse` (toy shapes, Pallas' interpreter) nothing printed is a device
time.
"""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import time
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parent.parent
OUT = ROOT / "chiprun_out" / "grouped_experts_probe"
# call -> (configuration file, its architecture's key, rows of `seq_len` a
# step sends through a layer, `--alike` where not given)
CALLS = {"sdar": ("configs/sdar_params.yaml", "sdar", 2, 35),
         "lfm2": ("configs/lfm2_params.yaml", "lfm2", 1, 10)}


def emit(**row):
    print(json.dumps(row), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", nargs="*", choices=sorted(CALLS),
                    default=sorted(CALLS))
    ap.add_argument("--alike", type=int, default=None)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--tiles", type=int, nargs="*", default=[128, 256, 512])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import yaml

    from dba_mod_tpu.ops import grouped_experts as ge

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        emit(ok=False, why=f"no chip: {dev.platform}")
        return 1
    worst = 0.0
    for call in args.calls:
        path, key, streams, _ = CALLS[call]
        raw = yaml.safe_load((ROOT / path).read_text())
        arch = raw[key]
        lo, hi = arch["experts_held"]
        shapes = (int(raw["batch_size"]) * streams * int(raw["seq_len"]),
                  arch["hidden_size"], arch["moe_intermediate_size"], hi - lo,
                  arch["num_experts"], arch["num_experts_per_tok"])
        limit = ge.VMEM_LIMIT
        if args.rehearse:   # the width in as many blocks as the chip's
            blocks = shapes[2] // ge.width_block(*shapes[1:3])
            shapes = (128, 128, 128 * blocks, 4, 16, 4)
            args.tiles, args.repeats = [16, 32], 2
            limit = ge._fast_bytes(128, 128, max(args.tiles))
        with mock.patch.object(ge, "VMEM_LIMIT", limit):
            worst = max(worst, probe(call, arch, shapes, args, dev))
    emit(ok=bool(worst < 2e-2), worst_gap=worst)
    return 0 if worst < 2e-2 else 1


def probe(call: str, arch: dict, shapes, args, dev) -> float:
    """One call's readings; -> the grouped form's largest distance from the
    dense form."""
    import jax
    import jax.numpy as jnp

    from chipbench import trace
    from dba_mod_tpu.models import lfm2
    from dba_mod_tpu.models.decoder_parts import held_picks
    from dba_mod_tpu.models.sdar import experts_over_all, route_softmax
    from dba_mod_tpu.ops import grouped_experts as ge

    n, d, f, held, total, k = shapes
    tiles, repeats = args.tiles, args.repeats
    main_tile = min(tiles) if args.rehearse else ge.TILE
    alike = CALLS[call][3] if args.alike is None else args.alike
    emit(call=call, device=dev.device_kind, platform=dev.platform, positions=n,
         hidden=d, width=f, width_block=ge.width_block(d, f, main_tile),
         held=held, experts=total, top_k=k, seed=args.seed, alike=alike)

    keys = jax.random.split(jax.random.key(args.seed), 9)
    x = jax.random.normal(keys[0], (n, d))
    x = jnp.where(jax.random.uniform(keys[6], (n, 1)) * 100 < alike, x[:1], x)
    router = jax.random.normal(keys[1], (d, total)) * 0.02
    w1, w3 = (jax.random.normal(kk, (held, d, f)) * 0.02 for kk in keys[2:4])
    w2 = jax.random.normal(keys[4], (held, f, d)) * 0.02
    cot = jax.random.normal(keys[5], (n, d))
    logits = jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST)
    if call == "lfm2":
        sel, w = lfm2.route(
            logits, 0.01 * jax.random.normal(keys[7], (total,)), k,
            arch["norm_topk_prob"], arch["routed_scaling_factor"])
    else:
        sel, w = route_softmax(logits, k, arch["norm_topk_prob"])
    lo = 0
    _, _, counts = held_picks(sel, w, lo, lo + held)
    emit(reading="routing", pairs=int(counts.sum()), most=int(counts.max()),
         least=int(counts.min()))

    def timed(name, fn, *inputs):
        fn = jax.jit(fn)
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*inputs))
        first = time.perf_counter() - t0
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*inputs))
            times.append(time.perf_counter() - t0)
        emit(reading=f"{call}.{name}", ms=1e3 * statistics.median(times),
             first_s=first, **device_time(name, fn, inputs))
        return out

    def device_time(name, fn, inputs, calls=4):
        """Device time a call from a profiler trace of `calls` calls (the
        host's clock above holds about half a millisecond of launch), and the
        operations that took most of it."""
        if args.rehearse:
            return {}
        where = OUT / f"{call}.{name}"
        shutil.rmtree(where, ignore_errors=True)
        with jax.profiler.trace(str(where)):
            for _ in range(calls):
                jax.block_until_ready(fn(*inputs))
        ops = next(iter(trace.read_planes(where)["devices"].values()), None)
        shutil.rmtree(where, ignore_errors=True)
        if not ops:
            return {}
        busy = sum(b - a for a, b in trace.union([(a, b) for _, a, b in ops]))
        by_name = {}
        for op, a, b in ops:
            op = trace.short_name(op)
            by_name[op] = by_name.get(op, 0.0) + (b - a)
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:6]
        return {"device_ms": busy / 1e6 / calls,
                "top": {op: round(ns / 1e6 / calls, 4) for op, ns in top}}

    def dense(x, w, w1, w3, w2):
        return experts_over_all(x, held_picks(sel, w, lo, lo + held)[1],
                                w1, w3, w2)

    def grouped(tile):
        return lambda x, w, w1, w3, w2: ge.grouped_experts(
            x, sel - lo, w, w1, w3, w2, tile=tile, interpret=args.rehearse)

    def with_backward(fn):
        return lambda *a: jax.vjp(fn, *a)[1](cot)

    operands = (x, w, w1, w3, w2)
    timed("dense.forward", dense, *operands)
    want = (dense(*operands),) + timed(
        "dense.forward_backward", with_backward(dense), *operands)
    worst = 0.0
    for tile in tiles:
        timed(f"grouped.{tile}.forward", grouped(tile), *operands)
        got = (grouped(tile)(*operands),) + timed(
            f"grouped.{tile}.forward_backward", with_backward(grouped(tile)),
            *operands)
        gaps = {name: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                for name, a, b in zip(("out", "dx", "dw", "dw1", "dw3", "dw2"),
                                      got, want)}
        worst = max(worst, *gaps.values())
        emit(reading=f"{call}.grouped.{tile}.against_dense", **gaps)

    blocks = f // ge.width_block(d, f, main_tile)
    plan = timed("parts.block_plan",
                 lambda w: ge.block_plan(sel - lo, w, held, blocks), w)
    emit(reading=f"{call}.rows", run=int(ge.rows_run(counts, d, f, main_tile)),
         all=held * n)
    ys = timed("parts.forward_kernel", lambda *a: ge._forward(
        main_tile, args.rehearse, "silu", *a), plan, x, w1, w3, w2)
    for tile in (64, 128, 256):
        timed(f"parts.combine.{tile}", lambda ys, plan, tile=tile: ge.combine(
            ys, plan, min(tile, n), args.rehearse), ys, plan)
    timed("parts.sort", lambda k, w: jax.lax.sort(
        (k.reshape(-1), w.reshape(-1)), num_keys=1, is_stable=False),
        sel * n + jnp.arange(n)[:, None], w)
    timed("parts.cumsum", lambda c: jnp.cumsum(c, axis=0, dtype=jnp.int32),
          (sel[:, :, None] == jnp.arange(held)).any(1))
    timed("parts.backward_kernels", lambda *a: ge._backward(
        main_tile, args.rehearse, "silu", *a), plan, x, w1, w3, w2, cot)

    # candidate (a): XLA's gather of a worst-case list and one ragged product
    plan = ge.route_plan(sel - lo, w, held)
    sizes = plan.starts[1:] - plan.starts[:-1]
    timed("ragged_dot.gather", lambda x, rows: x[rows], x, plan.rows)
    xs = x[plan.rows]
    timed("ragged_dot.one_product", lambda xs, w1, sizes: jax.lax.ragged_dot(
        xs, w1, sizes), xs, w1, sizes)
    return worst


if __name__ == "__main__":
    sys.exit(main())
