"""BENCH_r06 evidence driver for an accelerator-less container: the
bench.py overlap/async lanes on a reduced workload (same code paths,
smaller shapes) — the full CIFAR BENCH_CONFIG does not complete on one
CPU core (fused-round XLA compile alone exceeds 35 min). BENCH_TYPE
selects the model family (default cifar; BENCH_r06.json used mnist).
On one core the overlapped eval still executes on the only core, so
rounds/sec stays flat by construction — the honest quantities here are
hidden_fraction / hidden_eval_s (how much eval+host time ran behind the
next dispatch) and recompiles_after_warmup."""
import json
import os
import sys
import time

sys.path.insert(0, str(__import__("pathlib").Path(__file__).resolve().parents[1]))
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

import bench  # noqa: E402
from bench import _make_experiment, _make_async_experiment, measure_ours  # noqa: E402

RED = dict(bench.BENCH_CONFIG, type=os.environ.get("BENCH_TYPE", "cifar"),
           batch_size=16, no_models=4,
           number_of_total_participants=20, internal_epochs=1,
           eval_batch_size=256, synthetic_train_size=2000,
           synthetic_test_size=512, epochs=40)
ROUNDS = 6
out = {"workload": f"REDUCED {RED['type']} lane on CPU (batch 16, "
                   "4 clients/round, 20 participants, 2000 synthetic "
                   "samples) — same code paths as BENCH_CONFIG, shrunk "
                   "to fit one CPU core"}

t_all = time.time()
exp = _make_experiment(dict(RED, overlap_eval=True))
exp._overlap_rounds = 0
exp._overlap_hidden_s = exp._overlap_wait_s = 0.0
on_spr = measure_ours(exp, ROUNDS)
steady = exp.telemetry.counter("xla/recompiles_after_warmup").value
hidden = float(exp._overlap_hidden_s)
wait = float(exp._overlap_wait_s)
n_overlapped = int(exp._overlap_rounds)
del exp

off = _make_experiment(dict(RED, overlap_eval=False))
off_spr = measure_ours(off, ROUNDS)
del off

out["overlap"] = {
    "rounds_per_sec_off": round(1.0 / off_spr, 4),
    "rounds_per_sec_on": round(1.0 / on_spr, 4),
    "speedup": round(off_spr / on_spr, 3),
    "overlapped_rounds": n_overlapped,
    "hidden_eval_s": round(hidden, 4),
    "eval_wait_s": round(wait, 4),
    "hidden_fraction": (round(hidden / (hidden + wait), 4)
                        if hidden + wait > 0 else None),
    "dispatch_ahead_depth": 1,
    "recompiles_after_warmup": steady,
}

ARED = dict(RED, mode="async", buffer_k=5,
            staleness_weighting="polynomial", staleness_alpha=0.5,
            arrival_rate=2.0, arrival_jitter=0.5, straggler_tail=0.1,
            straggler_factor=5.0)
ASTEPS = 6
from dba_mod_tpu.fl.async_rounds import AsyncDriver  # noqa: E402

aexp = _make_async_experiment(dict(ARED, overlap_eval=True))
drv = AsyncDriver(aexp)
drv.run_steps(2)
t0 = time.time()
drv.run_steps(ASTEPS)
wall = time.time() - t0
K = drv.K
stats_on = drv.stats()
del drv, aexp

aoff = _make_async_experiment(dict(ARED, overlap_eval=False))
drv_off = AsyncDriver(aoff)
drv_off.run_steps(2)
t0 = time.time()
drv_off.run_steps(ASTEPS)
wall_off = time.time() - t0
del drv_off, aoff

out["async_lane"] = {
    "merges_per_sec_off": round(ASTEPS / wall_off, 4),
    "merges_per_sec_on": round(ASTEPS / wall, 4),
    "updates_per_sec_off": round(ASTEPS * K / wall_off, 4),
    "updates_per_sec_on": round(ASTEPS * K / wall, 4),
    "speedup": round(wall_off / wall, 3),
    "hidden_finalize_s": stats_on["hidden_finalize_s"],
    "pipelined_merges": stats_on["pipelined_merges"],
    "buffer_k": K,
}
out["wall_s_total"] = round(time.time() - t_all, 1)
print(json.dumps(out, indent=1))
