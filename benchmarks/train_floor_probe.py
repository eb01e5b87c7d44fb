"""TPU train-phase floor probe (VERDICT r3 ask 2).

Measures, on the real bench workload (CIFAR narrow ResNet-18, 10 clients,
bf16):
1. controlled A/B of the local-eval battery: per-client-vmapped fetch+stamp
   (the r3 formulation) vs the shared-fetch stacked battery (fl/evaluation.py
   ::make_stacked_eval_fn);
2. a kernel-level trace of one train_fn execution (jax.profiler) — kernel
   count, total device time, duration histogram — quantifying how much of
   the train phase is per-kernel launch floor vs compute;
3. the per-kernel dispatch floor of this stack, measured directly with a
   chain of dependent tiny kernels.

Writes JSON to stdout; TRAIN_FLOOR.md summarizes the findings and projects
real-TPU MFU.  Every timed region here ends in jax.device_get of a scalar.
"""
from __future__ import annotations

import glob
import gzip
import json
import time


def timeit(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dba_mod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from bench import BENCH_CONFIG
    from dba_mod_tpu.config import Params
    from dba_mod_tpu.fl.evaluation import make_eval_fn
    from dba_mod_tpu.fl.experiment import Experiment

    out = {}
    exp = Experiment(Params.from_dict(BENCH_CONFIG), save_results=False)
    engine = exp.engine
    plans = exp.eval_plans
    tasks_seq, idx_seq, mask_seq, ns, lane = exp.build_static_round_inputs(1)
    rng_t, rng_a = jax.random.split(jax.random.key(0))
    tasks_last = jax.tree_util.tree_map(lambda l: l[-1], tasks_seq)

    train = engine.train_fn(exp.global_vars, tasks_seq, idx_seq, mask_seq,
                            lane, rng_t)
    prev = jax.tree_util.tree_map(jnp.zeros_like, train.deltas)
    lat = min(timeit(lambda: jax.device_get(jnp.float32(1.0) + 1))
              for _ in range(3))
    out["sync_latency_s"] = lat

    # --- 1. eval battery A/B: r3 per-client formulation vs stacked ---
    eval_clean = make_eval_fn(engine.model_def, engine.data, poison=False)

    def old_local_clean(global_vars, deltas, tasks):
        def per_client(delta, scale):
            unscaled = jax.tree_util.tree_map(
                lambda g, d: g + d / scale, global_vars, delta)
            return eval_clean(unscaled, plans.clean_idx, plans.clean_slots,
                              plans.clean_mask, jnp.int32(-1))
        return jax.vmap(per_client)(deltas, tasks.scale)

    old_fn = jax.jit(old_local_clean)
    jax.device_get(old_fn(exp.global_vars, train.deltas,
                          tasks_last).acc[0])  # compile+warm

    def run_old():
        jax.device_get(old_fn(exp.global_vars, train.deltas,
                              tasks_last).acc[0])

    def run_new():
        jax.device_get(engine.local_evals_fn(
            exp.global_vars, train.deltas, tasks_seq, prev).clean.acc[0])

    run_new()
    out["local_eval_old_clean_only_s"] = round(
        min(timeit(run_old) for _ in range(3)) - lat, 4)
    out["local_eval_new_full_battery_s"] = round(
        min(timeit(run_new) for _ in range(3)) - lat, 4)
    # clean-only via the stacked kernel, for apples-to-apples
    from dba_mod_tpu.fl.evaluation import make_stacked_eval_fn
    stacked_clean = make_stacked_eval_fn(engine.model_def, engine.data)

    def new_clean_only(global_vars, deltas, tasks):
        unscaled = jax.tree_util.tree_map(
            lambda g, d: g + d / tasks.scale.reshape(
                (-1,) + (1,) * (d.ndim - 1)), global_vars, deltas)
        return stacked_clean(unscaled, plans.clean_idx, plans.clean_slots,
                             plans.clean_mask)

    new_clean_fn = jax.jit(new_clean_only)
    jax.device_get(new_clean_fn(exp.global_vars, train.deltas,
                                tasks_last).acc[0])

    def run_new_clean():
        jax.device_get(new_clean_fn(exp.global_vars, train.deltas,
                                    tasks_last).acc[0])

    out["local_eval_new_clean_only_s"] = round(
        min(timeit(run_new_clean) for _ in range(3)) - lat, 4)

    # --- 2. train phase: timing + kernel trace ---
    def run_train():
        jax.device_get(engine.train_fn(exp.global_vars, tasks_seq, idx_seq,
                                       mask_seq, lane,
                                       rng_t).delta_norms[0])

    run_train()
    out["train_s"] = round(min(timeit(run_train) for _ in range(3)) - lat, 4)

    trace_dir = "/tmp/train_trace"
    with jax.profiler.trace(trace_dir):
        run_train()
    files = sorted(glob.glob(trace_dir + "/**/*.trace.json.gz",
                             recursive=True))
    out["trace_file"] = files[-1] if files else None
    if files:
        with gzip.open(files[-1], "rt") as f:
            trace = json.load(f)
        # device pid: the TPU device track
        pids = {p["pid"]: p.get("args", {}).get("name", "")
                for p in trace["traceEvents"] if p.get("ph") == "M"
                and p.get("name") == "process_name"}
        dev_pids = [pid for pid, name in pids.items() if "TPU" in name]
        evs = [e for e in trace["traceEvents"]
               if e.get("ph") == "X" and e.get("pid") in dev_pids
               and not e.get("name", "").startswith(("jit_", "while"))]
        durs = np.array([e["dur"] for e in evs], np.float64)  # microseconds
        if len(durs):
            out["trace_kernels"] = int(len(durs))
            out["trace_device_total_s"] = round(float(durs.sum()) / 1e6, 4)
            out["trace_dur_us_percentiles"] = {
                str(p): round(float(np.percentile(durs, p)), 1)
                for p in (10, 50, 90, 99)}
            out["trace_kernels_under_100us"] = int((durs < 100).sum())
            out["trace_time_in_under_100us_s"] = round(
                float(durs[durs < 100].sum()) / 1e6, 4)
            names = {}
            for e in evs:
                n = e.get("name", "?")[:40]
                names[n] = names.get(n, [0, 0.0])
                names[n][0] += 1
                names[n][1] += e["dur"] / 1e6
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:12]
            out["trace_top_ops"] = [
                {"name": n, "count": c, "total_s": round(s, 4)}
                for n, (c, s) in top]

    # --- 3. per-kernel dispatch floor: dependent chain of tiny kernels ---
    def chain(x, n):
        for i in range(n):
            x = x * 1.000001 + jnp.float32(i)  # dependent, unfusable-ish
            x = jnp.sin(x)
        return x

    for n in (64, 512):
        f = jax.jit(lambda x, n=n: chain(x, n))
        jax.device_get(f(jnp.float32(1.0)))
        t = min(timeit(lambda: jax.device_get(f(jnp.float32(1.0))))
                for _ in range(3)) - lat
        out[f"chain_{n}_s"] = round(t, 4)
    # floor = marginal cost per fused pair of tiny ops
    out["per_kernel_floor_us"] = round(
        (out["chain_512_s"] - out["chain_64_s"]) / (512 - 64) / 2 * 1e6, 2)

    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
