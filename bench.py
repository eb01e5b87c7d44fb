"""Benchmark: CIFAR-10 FL rounds/sec (100 clients, 10/round, narrow
ResNet-18) on the available accelerator — the north-star workload
(BASELINE.json: CIFAR-10 DBA on v5e; its steady-state rounds are clean, since
single-shot poisoning touches 4 of 300 rounds).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "phases",
"mfu", ...}. `value` is end-to-end rounds/sec (host prep + device compute +
the round's blocking transfer, recording on).

vs_baseline is measured against a reference-style sequential torch loop doing
identical work on this host's CPU (benchmarks/torch_reference.py) — the only
runnable form of the reference in this zero-egress, GPU-less image; the
reference repo publishes no numbers of its own (BASELINE.md). The baseline
measurement is cached in BENCH_BASELINE_LOCAL.json after the first run.

`phases` reports per-phase device seconds measured by cumulative dispatch +
scalar-sync, with the measured scalar-fetch latency subtracted.
(chip_smoke.py's probe shows block_until_ready does block on the current
chip machine; replacing this scheme is ROADMAP S0/S5.)
`mfu` divides useful-work FLOPs (XLA cost analysis of this model on the CPU
backend, cached in BENCH_FLOPS.json; padding-step compute excluded) by the
phase time × the chip's bf16 peak.

Usage: python bench.py [--rounds N] [--skip-baseline] [--no-phases]
Opt-in lanes (each appends a sub-object to the JSON, never breaks the
headline): --multihost, --poison-cost, --width, --forensics-cost, --async.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).parent
CACHE = REPO / "BENCH_BASELINE_LOCAL.json"
FLOPS_CACHE = REPO / "BENCH_FLOPS.json"

PEAK_BF16 = 197e12  # TPU v5e peak bf16 FLOP/s (the bench chip)

BENCH_CONFIG = dict(
    type="cifar", lr=0.1, batch_size=64, epochs=10, no_models=10,
    number_of_total_participants=100, eta=0.1, aggregation_methods="mean",
    internal_epochs=2, momentum=0.9, decay=0.0005, is_poison=False,
    synthetic_data=True,  # zero-egress image: CIFAR-shaped synthetic data
    sampling_dirichlet=True, dirichlet_alpha=0.5, local_eval=True,
    random_seed=1,
    # TPU-native settings (all semantics-preserving; see config.py):
    # bf16 MXU compute (f32 params/aggregation — backdoor efficacy validated
    # in tests/test_fl_integration.py); fat eval batches (eval sums are
    # batch-size invariant); round pipelining (recording lags one round);
    # overlap_eval splits the fused round so round N's eval batteries +
    # host sync run behind round N+1's train/aggregate dispatch — recorded
    # metrics stay bit-identical (tests/test_overlap.py), only the
    # schedule changes. The headline measures the knob ON; the JSON's
    # "overlap" sub-object carries the off/on A/B on the same workload.
    compute_dtype="bfloat16", eval_batch_size=2048,
    pipeline_rounds=True, overlap_eval=True)


# --poison-cost lane (VERDICT Weak #5): the SAME headline workload with the
# distributed backdoor on — 4 scheduled adversaries (the cifar_params.yaml
# stripe geometry), poisoning every timed round, scale_weights 1 so the
# model trajectory stays numerically tame — vs the benign headline. The
# delta isolates what the attack path costs end-to-end: the poison-batch
# injection inside the train step plus the 4-part local eval battery
# (clean / poison-pre / poison-post / per-agent trigger) vs benign's
# clean-only battery.
POISON_COST_CONFIG = dict(
    BENCH_CONFIG, is_poison=True,
    internal_poison_epochs=BENCH_CONFIG["internal_epochs"],
    poisoning_per_batch=5, poison_label_swap=2, poison_lr=0.05,
    scale_weights_poison=1.0, alpha_loss=1.0, trigger_num=4,
    is_random_adversary=False, adversary_list=[0, 1, 2, 3],
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2], [0, 3], [0, 4], [0, 5]],
       "1_poison_pattern": [[0, 9], [0, 10], [0, 11], [0, 12], [0, 13],
                            [0, 14]],
       "2_poison_pattern": [[4, 0], [4, 1], [4, 2], [4, 3], [4, 4], [4, 5]],
       "3_poison_pattern": [[4, 9], [4, 10], [4, 11], [4, 12], [4, 13],
                            [4, 14]]},
    **{f"{i}_poison_epochs": list(range(1, 400)) for i in range(4)})


# Second lane (VERDICT r4 ask 7): the Tiny-ImageNet workload — imagenet stem
# (7×7/s2 + maxpool), standard 64-base widths, global pool, 200 classes
# (reference models/resnet_tinyimagenet.py:40-238) — different conv/layout
# behavior than the narrow-CIFAR headline. Synthetic tiny, 10 clients.
# 10k images (123 MB): the dataset is closed over by the round program and
# rides in its executable. Workload note in the JSON.
TINY_CONFIG = dict(
    BENCH_CONFIG, type="tiny-imagenet-200",
    synthetic_train_size=10000, synthetic_test_size=2000)


# --async lane (README "Asynchronous federation"): the headline workload
# through the buffered-async engine (fl/async_rounds.py) — 10-client
# cohorts, merge every 5 arrivals, polynomial staleness weighting, a
# jittered arrival process with a straggler tail. The FedBuff-native
# throughput unit is sustained client updates absorbed per second
# (merges/sec × buffer_k); pipeline_rounds is a lockstep-loop knob and is
# ignored by the streaming driver.
ASYNC_CONFIG = dict(
    BENCH_CONFIG, mode="async", buffer_k=5,
    staleness_weighting="polynomial", staleness_alpha=0.5,
    arrival_rate=2.0, arrival_jitter=0.5, straggler_tail=0.1,
    straggler_factor=5.0)


# --multihost lane (ROADMAP item 5): the 2-process DCN configuration the
# multi-host tests prove (tests/test_multihost.py) — 2 × 4 virtual CPU
# devices = one 8-device clients mesh spanning a process boundary — timed
# end-to-end so the scale-out path has a perf trajectory in the BENCH_*
# JSON, not just a correctness bit. sync_latency is the host-visible
# scalar-fetch round trip through the cross-process runtime.
MULTIHOST_CONFIG = dict(
    type="mnist", lr=0.1, batch_size=32, epochs=12, no_models=8,
    number_of_total_participants=8, eta=0.8, aggregation_methods="mean",
    internal_epochs=1, is_poison=False, synthetic_data=True,
    synthetic_train_size=512, synthetic_test_size=256, momentum=0.9,
    decay=0.0005, sampling_dirichlet=False, local_eval=False,
    random_seed=1, num_devices=-1)


def _multihost_worker(process_id: int, coordinator: str,
                      timed_rounds: int) -> int:
    """One process of the 2-process bench world. Env must be set before
    jax imports, hence the subprocess re-entry."""
    import os
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["JAX_COORDINATOR_ADDRESS"] = coordinator
    os.environ["JAX_NUM_PROCESSES"] = "2"
    os.environ["JAX_PROCESS_ID"] = str(process_id)
    import jax
    from dba_mod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from dba_mod_tpu.config import Params
    from dba_mod_tpu.fl.experiment import Experiment
    import jax.numpy as jnp

    exp = Experiment(Params.from_dict(MULTIHOST_CONFIG),
                     save_results=False)
    assert jax.process_count() == 2
    exp.run_round(1)  # compile
    lat = min(timeit(lambda: jax.device_get(jnp.float32(1.0) + 1))
              for _ in range(3))
    t0 = time.perf_counter()
    pending = None
    for i in range(2, 2 + timed_rounds):
        fl = exp.dispatch_round(i)
        if pending is not None:
            exp.finalize_round(pending)
        pending = fl
    exp.finalize_round(pending)
    spr = (time.perf_counter() - t0) / timed_rounds
    if process_id == 0:
        print(json.dumps({
            "metric": "multihost_2proc_rounds_per_sec",
            "value": round(1.0 / spr, 4), "unit": "rounds/sec",
            "sync_latency_s": round(lat, 4),
            "world": {"processes": 2, "devices": int(jax.device_count())},
            "workload": "synthetic mnist, 8 clients/round, 2-process DCN "
                        "over 2x4 virtual CPU devices "
                        "(tests/test_multihost.py configuration)"}),
            flush=True)
    return 0


def measure_multihost(timed_rounds: int) -> dict:
    """Spawn the 2-process world and collect process 0's JSON line."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coord = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()
    import os
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_NUM_PROCESSES", "JAX_PROCESS_ID",
                        "JAX_COORDINATOR_ADDRESS")}
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["TF_CPP_MIN_LOG_LEVEL"] = "3"
    procs = [subprocess.Popen(
        [sys.executable, str(REPO / "bench.py"), "--multihost-worker",
         str(pid), coord, str(timed_rounds)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=str(REPO)) for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=1800)[0])
    except subprocess.TimeoutExpired:
        # one wedged worker (startup race, gloo hang) must not take the
        # whole bench down or orphan its sibling — same contract as the
        # tiny lane: the headline number always prints
        for p in procs:
            if p.poll() is None:
                p.kill()
        return {"error": "multihost worker timed out after 1800s; "
                         "workers killed"}
    for pid, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            return {"error": f"multihost worker {pid} rc={p.returncode}: "
                             f"{out[-2000:]}"}
    for line in reversed(outs[0].strip().splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    return {"error": f"no JSON line from worker 0: {outs[0][-2000:]}"}


def _make_experiment(config=None):
    import jax
    # persistent compile cache: the round + eval programs compile once per
    # machine, not once per bench run
    from dba_mod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from dba_mod_tpu.config import Params
    from dba_mod_tpu.fl.experiment import Experiment
    exp = Experiment(Params.from_dict(config or BENCH_CONFIG),
                     save_results=False)
    exp.run_round(1)          # compile eval/aggregate programs
    exp.telemetry.mark_warm()  # further XLA compiles are regressions
    return exp


def _make_async_experiment(config=None):
    """The --async lane's experiment: same toolchain setup as
    _make_experiment, but warmed by the streaming driver itself (the
    lockstep run_round warm would consume the RNG streams the first wave
    dispatch expects)."""
    from dba_mod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from dba_mod_tpu.config import Params
    from dba_mod_tpu.fl.experiment import Experiment
    return Experiment(Params.from_dict(config or ASYNC_CONFIG),
                      save_results=False)


def measure_ours(exp, timed_rounds: int) -> float:
    """End-to-end seconds/round, pipelined: round N+1 dispatches before round
    N's blocking fetch."""
    t0 = time.time()
    pending = None
    for i in range(2, 2 + timed_rounds):
        fl = exp.dispatch_round(i)
        if pending is not None:
            exp.finalize_round(pending)
        pending = fl
    exp.finalize_round(pending)
    return (time.time() - t0) / timed_rounds


def model_flops() -> dict:
    """Per-sample FLOPs of the bench model (fwd eval; fwd+bwd+update train
    step), from XLA cost analysis on the CPU backend (in a child forced onto
    the CPU, so the parent keeps the chip). Cached: the numbers only change
    with the model."""
    if FLOPS_CACHE.exists():
        return json.loads(FLOPS_CACHE.read_text())
    code = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
sys.path.insert(0, %r)
from bench import BENCH_CONFIG
from dba_mod_tpu.config import Params
from dba_mod_tpu.models import build_model
p = Params.from_dict(BENCH_CONFIG)
md = build_model(p)
v = md.init_vars(jax.random.key(0))
B = int(p["batch_size"])
x = jnp.zeros((B, 32, 32, 3), jnp.bfloat16)
y = jnp.zeros((B,), jnp.int32)
def fwd(v, x):
    logits, _ = md.apply(v, x, train=False)
    return logits
def train_step(v, x, y):
    def loss(vv):
        logits, bn = md.apply(vv, x, train=True,
                              dropout_rng=jax.random.key(0))
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, y[:, None], -1)), bn
    (l, bn), g = jax.value_and_grad(loss, has_aux=True)(v)
    newp = jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, v, g)
    return newp
def flops_of(f, *args):
    ca = jax.jit(f).lower(*args).compile().cost_analysis()
    return float(ca["flops"])
print(json.dumps({
    "fwd_per_sample": flops_of(fwd, v, x) / B,
    "train_step_per_sample": flops_of(train_step, v, x, y) / B}))
""" % str(REPO)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    data = json.loads(out.stdout.strip().splitlines()[-1])
    FLOPS_CACHE.write_text(json.dumps(data, indent=1))
    return data


def measure_phases(exp) -> dict:
    """Per-phase device seconds via cumulative dispatch + scalar sync."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tasks_seq, idx_seq, mask_seq, ns, lane = exp.build_static_round_inputs(
        999)
    rng_t, rng_a = jax.random.split(jax.random.key(3))
    tasks_last = jax.tree_util.tree_map(lambda l: l[-1], tasks_seq)
    real_samples = int(np.asarray(ns).sum()) * exp.epochs_max

    def upto(k):
        train = exp.engine.train_fn(exp.global_vars, tasks_seq, idx_seq,
                                    mask_seq, lane, rng_t)
        if k == 0:
            return train.delta_norms[0]
        from dba_mod_tpu.fl.rounds import nbt_client_deltas
        res = exp.engine.aggregate_fn(
            exp.global_vars, exp.fg_state, train.deltas, train.fg_grads,
            train.fg_feature, tasks_last.participant_id, ns, rng_a,
            nbt_client_deltas(mask_seq, tasks_seq.scale))
        if k == 1:
            return res.wv[0]
        prev = jax.tree_util.tree_map(jnp.zeros_like, train.deltas)
        lev = exp.engine.local_evals_fn(exp.global_vars, train.deltas,
                                        tasks_seq, prev)
        if k == 2:
            return lev.clean.acc[0]
        gev = exp.engine.global_evals_fn(res.new_vars)
        return gev.clean.acc

    lat = min(timeit(lambda: jax.device_get(jnp.float32(1.0) + 1))
              for _ in range(3))
    cums = []
    for k in range(4):
        jax.device_get(upto(k))  # warm any fresh compile
        cums.append(min(timeit(lambda: jax.device_get(upto(k)))
                        for _ in range(2)) - lat)
    names = ["train", "aggregate", "local_eval", "global_eval"]
    phases = {"sync_latency_s": round(lat, 4)}
    prev = 0.0
    for k, n in enumerate(names):
        phases[n + "_s"] = round(max(cums[k] - prev, 0.0), 4)
        prev = cums[k]
    phases["_real_train_samples"] = real_samples
    return phases


def timeit(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def host_peak_rss_bytes():
    """Process peak resident-set high-water (bytes) — the memory ceiling
    that matters on CPU backends, where device_peak_bytes is None. Like the
    allocator stat it is monotone over the process lifetime: in the width
    lane each point's value subsumes every smaller config measured before
    it, so the last (widest) point is the series' ceiling."""
    import resource
    return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024


def device_peak_bytes():
    """Device-memory high-water (bytes) from the runtime's allocator stats.
    None where the backend publishes none (CPU). NOTE: peak_bytes_in_use is
    monotone over the PROCESS lifetime — in the width lane below, each
    point's peak subsumes the smaller configs measured before it, so read
    the series as a running high-water, exact only at the widest point."""
    import jax
    stats = jax.local_devices()[0].memory_stats()
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak else None


def baseline_seconds_per_round(skip: bool) -> float | None:
    if CACHE.exists():
        return json.loads(CACHE.read_text())["seconds_per_round"]
    if skip:
        return None
    from benchmarks.torch_reference import measure_torch_reference_round
    secs = measure_torch_reference_round(
        num_clients=BENCH_CONFIG["no_models"], samples_per_client=500,
        batch_size=BENCH_CONFIG["batch_size"],
        internal_epochs=BENCH_CONFIG["internal_epochs"])
    CACHE.write_text(json.dumps({
        "seconds_per_round": secs,
        "what": "reference-style sequential torch loop, same work, this "
                "host's CPU (see benchmarks/torch_reference.py)"}, indent=1))
    return secs


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--multihost-worker":
        # subprocess re-entry: env vars must precede jax import
        return _multihost_worker(int(sys.argv[2]), sys.argv[3],
                                 int(sys.argv[4]))
    ap = argparse.ArgumentParser()
    # 12 timed rounds: host-side sync-latency jitter averages down as 1/√n
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--skip-baseline", action="store_true")
    ap.add_argument("--no-phases", action="store_true")
    ap.add_argument("--no-tiny", action="store_true",
                    help="skip the Tiny-ImageNet second lane")
    ap.add_argument("--tiny-rounds", type=int, default=4)
    ap.add_argument("--multihost", action="store_true",
                    help="add the 2-process DCN lane (2x4 virtual CPU "
                         "devices, tests/test_multihost.py configuration): "
                         "rounds/sec + sync_latency into the JSON under "
                         "'multihost_lane'")
    ap.add_argument("--multihost-rounds", type=int, default=8)
    ap.add_argument("--poison-cost", action="store_true",
                    help="add the poison-cost lane: the headline workload "
                         "with the 4-adversary DBA + full 4-part local eval "
                         "battery on, and the rounds/sec delta vs the "
                         "benign headline (VERDICT Weak #5)")
    ap.add_argument("--poison-rounds", type=int, default=8)
    ap.add_argument("--width", action="store_true",
                    help="add the width lane: clients*rounds/sec at "
                         "C = 10/50/100 clients/round with the device "
                         "memory high-water per point (ROADMAP item 1's "
                         "measurement half)")
    ap.add_argument("--width-rounds", type=int, default=4)
    ap.add_argument("--async", dest="async_lane", action="store_true",
                    help="add the buffered-async lane: the headline "
                         "workload through the streaming engine "
                         "(fl/async_rounds.py) — sustained updates/sec "
                         "and merges/sec under the arrival process, under "
                         "'async_lane'")
    ap.add_argument("--async-rounds", type=int, default=12,
                    help="timed aggregation steps for the --async lane")
    ap.add_argument("--forensics-cost", action="store_true",
                    help="add the forensics-cost lane: the headline "
                         "workload with `forensics: true` and the overhead "
                         "%% vs the forensics-off headline (the <=5%% "
                         "acceptance gate)")
    ap.add_argument("--forensics-rounds", type=int, default=8)
    ap.add_argument("--telemetry", metavar="DIR", default="",
                    help="enable the telemetry layer (utils/telemetry.py): "
                         "writes telemetry.jsonl + Chrome-trace trace.json "
                         "to DIR and prints the phase summary to stderr. "
                         "NOTE: with the exporters on the round loop runs "
                         "sequentially (no pipelining/overlap), so the "
                         "headline rounds/sec is NOT comparable to a run "
                         "without the flag")
    args = ap.parse_args()

    config = dict(BENCH_CONFIG)
    if args.telemetry:
        config.update(telemetry=True, telemetry_dir=args.telemetry)
    exp = _make_experiment(config)
    # the warmup round ran through the overlap path too — zero the hidden-
    # time clocks so the overlap sub-object reports the timed window only
    exp._overlap_rounds = 0
    exp._overlap_hidden_s = exp._overlap_wait_s = 0.0
    ours = measure_ours(exp, args.rounds)
    # snapshot now: the phases probe below intentionally compiles the
    # static-plan-shape programs post-warmup, which would pollute the
    # steady-state regression count reported in out["telemetry"]
    steady_recompiles = exp.telemetry.counter(
        "xla/recompiles_after_warmup").value
    base = baseline_seconds_per_round(args.skip_baseline)
    rounds_per_sec = 1.0 / ours
    vs = (base / ours) if base else 1.0

    out = {"metric": "cifar10_fl_rounds_per_sec",
           "value": round(rounds_per_sec, 4),
           "unit": "rounds/sec",
           "vs_baseline": round(vs, 2),
           "baseline_note": (
               "vs reference-style sequential torch loop on this host's "
               "single CPU core (benchmarks/torch_reference.py) — the only "
               "runnable reference form in this zero-egress GPU-less image; "
               "NOT the north-star PyTorch-GPU denominator" if base else
               "baseline skipped (--skip-baseline, no cache); vs_baseline "
               "is a 1.0 placeholder, not a measurement")}

    # overlap A/B (README "Round pipelining"): the identical workload with
    # overlap_eval OFF — the knob's contract is bit-identical recorded
    # metrics, so the whole delta is schedule. hidden_eval_s is the
    # cumulative eval+fetch wall time that ran behind the next round's
    # dispatch; eval_wait_s is what finalize still had to block on.
    try:
        oexp = _make_experiment(dict(config, overlap_eval=False))
        off_spr = measure_ours(oexp, args.rounds)
        del oexp
        hidden = float(exp._overlap_hidden_s)
        wait = float(exp._overlap_wait_s)
        out["overlap"] = {
            "rounds_per_sec_off": round(1.0 / off_spr, 4),
            "rounds_per_sec_on": round(rounds_per_sec, 4),
            "speedup": round(off_spr / ours, 3),
            "hidden_eval_s": round(hidden, 4),
            "eval_wait_s": round(wait, 4),
            "hidden_fraction": (round(hidden / (hidden + wait), 4)
                                if hidden + wait > 0 else None),
            "dispatch_ahead_depth": 1,
            "recompiles_after_warmup": steady_recompiles,
            "note": "off/on the same process+cache; hidden_fraction = "
                    "hidden / (hidden + still-blocking finalize) over the "
                    "timed window"}
    except Exception as e:  # noqa: BLE001 — lanes never break
        out["overlap_error"] = str(e)  # the headline number

    if not args.no_phases:
        try:
            fl = model_flops()
            ph = measure_phases(exp)
            real = ph.pop("_real_train_samples")
            n_test = exp.device_data.num_test
            C = int(exp.params["no_models"])
            train_fl = real * fl["train_step_per_sample"]
            eval_fl = (C * n_test + n_test) * fl["fwd_per_sample"]
            out["phases"] = ph
            denom = max(ph["train_s"], 1e-9)
            out["mfu"] = {
                "train": round(train_fl / denom / PEAK_BF16, 4),
                "eval": round(eval_fl / max(
                    ph["local_eval_s"] + ph["global_eval_s"], 1e-9)
                    / PEAK_BF16, 4),
                "peak_bf16_flops": PEAK_BF16,
                "note": "useful-work FLOPs (padding excluded) / phase "
                        "device-time; phase times at the STATIC plan shape"}
        except Exception as e:  # noqa: BLE001 — diagnostics must not
            out["phases_error"] = str(e)  # break the headline number

    if args.telemetry:
        # final trace/summary flush for the headline lane (the tiny lane
        # below builds its own un-instrumented Experiment); summary goes to
        # stderr — stdout stays the single JSON line
        exp.telemetry.record_memory()
        exp.telemetry.close()
        print(exp.telemetry.summary_table(), file=sys.stderr)
        out["telemetry"] = {
            "dir": args.telemetry,
            "recompiles_after_warmup": steady_recompiles,
            "note": "per-phase device syncs active; value above is NOT "
                    "comparable to an uninstrumented run"}

    if not args.no_tiny:
        # lane 2: heavier per-round, fewer timed rounds amortize fine
        try:
            texp = _make_experiment(TINY_CONFIG)
            tiny_spr = measure_ours(texp, args.tiny_rounds)
            out["tiny_lane"] = {
                "metric": "tiny_imagenet_fl_rounds_per_sec",
                "value": round(1.0 / tiny_spr, 4), "unit": "rounds/sec",
                "workload": "synthetic tiny-imagenet (10k imgs, Dirichlet "
                            "a=0.5), 10 clients/round, torchvision-style "
                            "ResNet-18 (200 classes)"}
        except Exception as e:  # noqa: BLE001 — the second lane must not
            out["tiny_lane_error"] = str(e)  # break the headline number

    if args.poison_cost:
        # poison-cost lane: benign denominator = the headline measurement
        # above (identical config apart from the attack keys)
        try:
            pexp = _make_experiment(POISON_COST_CONFIG)
            pspr = measure_ours(pexp, args.poison_rounds)
            out["poison_cost_lane"] = {
                "metric": "cifar10_poison_round_cost",
                "benign_rounds_per_sec": round(rounds_per_sec, 4),
                "poison_rounds_per_sec": round(1.0 / pspr, 4),
                "poison_overhead_pct": round(
                    100.0 * (pspr - ours) / ours, 2),
                "workload": "headline config + 4 scheduled DBA adversaries "
                            "poisoning every timed round; overhead = poison "
                            "injection in-train + the 4-part local eval "
                            "battery vs benign's clean-only battery"}
        except Exception as e:  # noqa: BLE001 — lanes never break
            out["poison_cost_lane_error"] = str(e)  # the headline number

    if args.width:
        # width lane: throughput in clients*rounds/sec vs clients-per-round
        # (C is the vmapped client axis of the fused round program). The
        # C=1000 point is the ROADMAP scale target: the participant pool
        # grows to match, fewer timed rounds amortize the heavier program,
        # and the memory high-water ceiling across the whole sweep is
        # reported alongside the per-point series.
        try:
            pts = []
            for C in (10, 50, 100, 1000):
                wexp = _make_experiment(dict(
                    BENCH_CONFIG, no_models=C,
                    number_of_total_participants=max(
                        int(BENCH_CONFIG["number_of_total_participants"]),
                        C)))
                spr = measure_ours(
                    wexp, args.width_rounds if C <= 100 else
                    max(1, args.width_rounds // 2))
                pts.append({
                    "clients_per_round": C,
                    "rounds_per_sec": round(1.0 / spr, 4),
                    "clients_rounds_per_sec": round(C / spr, 4),
                    "device_peak_bytes": device_peak_bytes(),
                    "host_peak_rss_bytes": host_peak_rss_bytes()})
                del wexp
            out["width_lane"] = {
                "metric": "clients_rounds_per_sec_vs_width",
                "points": pts,
                "memory_ceiling_bytes": {
                    "device": pts[-1]["device_peak_bytes"],
                    "host_rss": pts[-1]["host_peak_rss_bytes"]},
                "note": "device_peak_bytes/host_peak_rss_bytes are process-"
                        "lifetime high-waters (monotone across points; "
                        "device is null on backends without memory_stats) — "
                        "memory_ceiling_bytes is the widest point's "
                        "high-water, the sweep's ceiling"}
        except Exception as e:  # noqa: BLE001
            out["width_lane_error"] = str(e)

    if args.async_lane:
        # async lane: the buffered streaming engine's sustained throughput —
        # merges/sec and client updates absorbed/sec (merges x buffer_k).
        # Fresh experiment + driver; two untimed merges warm the wave-train
        # + merge + eval programs before the clock starts.
        try:
            aexp = _make_async_experiment()
            from dba_mod_tpu.fl.async_rounds import AsyncDriver
            drv = AsyncDriver(aexp)
            drv.run_steps(2)
            t0 = time.time()
            drv.run_steps(args.async_rounds)
            wall = time.time() - t0
            K = drv.K
            # merge-pipelining A/B: same workload, overlap_eval off — the
            # serial dispatch+finalize composition per merge
            aoff = _make_async_experiment(dict(ASYNC_CONFIG,
                                               overlap_eval=False))
            drv_off = AsyncDriver(aoff)
            drv_off.run_steps(2)
            t0 = time.time()
            drv_off.run_steps(args.async_rounds)
            wall_off = time.time() - t0
            del drv_off, aoff
            out["async_lane"] = {
                "metric": "async_buffered_updates_per_sec",
                "merges_per_sec": round(args.async_rounds / wall, 4),
                "updates_per_sec": round(args.async_rounds * K / wall, 4),
                "overlap": {
                    "merges_per_sec_off": round(
                        args.async_rounds / wall_off, 4),
                    "updates_per_sec_off": round(
                        args.async_rounds * K / wall_off, 4),
                    "speedup": round(wall_off / wall, 3),
                    "hidden_finalize_s": drv.stats()["hidden_finalize_s"],
                    "note": "merge S's host finalize (device fetch + row "
                            "recording) pipelined behind step S+1's "
                            "fill/merge compute"},
                "buffer_k": K,
                "cohort_clients": int(aexp.params["no_models"]),
                "staleness_weighting": str(
                    aexp.params["staleness_weighting"]),
                # self-healing observability (driver counters over the
                # timed window + warmup): virtual-time merge latency p95,
                # admission-control high-water, and the starvation/TTL
                # drop counts — all zero with the knobs at defaults
                "health": drv.stats(),
                "workload": "headline config through the buffered-async "
                            "engine: 10-client cohorts, merge every 5 "
                            "arrivals, polynomial staleness, jittered "
                            "arrivals with a straggler tail "
                            "(fl/async_rounds.py)"}
        except Exception as e:  # noqa: BLE001 — lanes never break
            out["async_lane_error"] = str(e)  # the headline number

    if args.forensics_cost:
        # forensics-cost lane: identical workload, forensics on. The writer
        # stays in-memory (save_results=False), so the measured delta is
        # the device-side ForensicStats computation + the bigger fetch +
        # host row assembly — the acceptance gate is <= 5%.
        try:
            fexp = _make_experiment(dict(BENCH_CONFIG, forensics=True))
            fspr = measure_ours(fexp, args.forensics_rounds)
            out["forensics_cost_lane"] = {
                "metric": "cifar10_forensics_overhead",
                "off_rounds_per_sec": round(rounds_per_sec, 4),
                "on_rounds_per_sec": round(1.0 / fspr, 4),
                "overhead_pct": round(100.0 * (fspr - ours) / ours, 2),
                "note": "forensics rows assembled in-memory (bench runs "
                        "with save_results off); file I/O is atomic full "
                        "rewrites on real runs"}
        except Exception as e:  # noqa: BLE001
            out["forensics_cost_lane_error"] = str(e)

    if args.multihost:
        # scale-out lane: spawns its own 2-process world (a process that
        # already initialized jax cannot join one), so it must not touch
        # this process's experiment — and, like the tiny lane, must never
        # break the headline number
        try:
            out["multihost_lane"] = measure_multihost(args.multihost_rounds)
        except Exception as e:  # noqa: BLE001
            out["multihost_lane"] = {"error": str(e)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
