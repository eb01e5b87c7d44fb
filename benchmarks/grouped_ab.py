"""Controlled A/B of the grouped-layout client path (models/grouped.py)
against the vmapped path on the bench workload — same inputs, same global
state, both engines' train_fn compared for (a) wall-clock train-phase time
and (b) numerical agreement of the round outputs.

Usage: python -m benchmarks.grouped_ab   (runs on the default backend; on
the chip through the chip tool — a CPU run measures nothing interesting).
Prints one JSON line; evidence recorded in TRAIN_FLOOR.md.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def main() -> int:
    from dba_mod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench import BENCH_CONFIG
    from dba_mod_tpu.config import Params
    from dba_mod_tpu.fl.experiment import Experiment

    base = dict(BENCH_CONFIG, dynamic_steps=False, pipeline_rounds=False)
    exps = {k: Experiment(Params.from_dict(dict(base, grouped_clients=k)),
                          save_results=False)
            for k in (False, True)}
    ev, eg = exps[False], exps[True]
    assert eg.engine.use_grouped and not ev.engine.use_grouped

    # identical inputs for both engines (consume ONE experiment's RNG)
    tasks_seq, idx_seq, mask_seq, ns, lane = ev.build_static_round_inputs(2)
    rng_t = jax.random.key(7)
    gv = ev.global_vars  # same seed → same init as eg's

    def train(eng):
        return eng.engine.train_fn(gv, tasks_seq, idx_seq, mask_seq, lane,
                                   rng_t)

    # numerics: same inputs through both paths
    tv = jax.device_get(train(ev))
    tg = jax.device_get(train(eg))
    d_param = max(float(np.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(tv.deltas.params),
        jax.tree_util.tree_leaves(tg.deltas.params)))
    d_bn = max(float(np.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(tv.deltas.batch_stats),
        jax.tree_util.tree_leaves(tg.deltas.batch_stats)))
    d_scale = max(float(np.abs(a).max()) for a in
                  jax.tree_util.tree_leaves(tv.deltas.params))
    bitwise = d_param == 0.0 and d_bn == 0.0

    # timing: dispatch + scalar sync (bench.py::measure_phases methodology)
    lat = min(timeit(lambda: jax.device_get(jnp.float32(1.0) + 1))
              for _ in range(3))

    def phase_time(eng):
        sync = lambda: jax.device_get(train(eng).delta_norms[0])
        sync()  # warm
        return min(timeit(sync) for _ in range(3)) - lat

    t_v = phase_time(ev)
    t_g = phase_time(eg)
    out = {"metric": "grouped_ab_train_phase_s",
           "vmapped_s": round(t_v, 4), "grouped_s": round(t_g, 4),
           "speedup": round(t_v / t_g, 3) if t_g > 0 else None,
           "max_delta_param_diff": d_param, "max_delta_bn_diff": d_bn,
           "delta_scale": d_scale, "bitwise_identical": bitwise,
           "backend": jax.default_backend()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
