"""chipbench: one run of one cell of BENCHMARK.json, in a process of its own.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Fails at once without a TPU or with fewer devices than the cell's `chips`;
finds the configuration's model family by the name its file gives
(`chipbench/families/`); builds the configuration's `Experiment`; compiles and
warms the cell's one round program (the two check rounds and one whole round);
then measures the program's own sequential loop over **whole periods of the
traffic's schedule** (`period_rounds` in the traffic file): periods are
started until `--seconds` have passed and the one in flight is finished.

Two seeds, each for one thing. **`--seed` is the check's**: the two check
rounds run on weights, a selection, a batch order and a device RNG drawn from
it, and after the window they are compared with the plain reference on those
weights, so every run holds the program to the reference on inputs it has not
seen. **The configuration's `population_seed` is the window's**
(`seed_window`): the warm round and the window start from weights, a batch
order and a device RNG (a model's own noise) drawn from it, and at the start
of every period the selection RNG is set from it again, so every period of
every run at every `--seed` selects the same clients and times the same job.
How much work a round holds follows all of these (the steps a lane needs, the
rows a router sends to the held experts), and a run's time is to follow the
program alone; the `window` line names the seed and a fingerprint of the state
the window started from. With `--trace 1` the window rounds the traffic names
(`trace_window_rounds`) are traced and the window ends with the last of them.
After the window the check rounds are compared with the plain reference;
findings go out as JSON lines and, last, the result object of the benchmark's
contract.

`--rehearse` walks the same control flow on whatever backend is there at tiny
sizes (Pallas interpreted): it prints no metric and is never `correct`.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # before anything heavy is imported

import argparse
import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CHECK_STEPS = (1, 3)   # real steps a client takes in the two check rounds
WARM_ROUNDS = 1        # whole rounds through run_round before the window
FIRST_WINDOW_EPOCH = len(CHECK_STEPS) + WARM_ROUNDS + 1


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def load_cell(name: str, benchmark_file=None):
    """BENCHMARK.json names the cell's configuration and traffic; their files
    are found by those names. The harness holds no list of cells."""
    bench = json.loads(Path(benchmark_file or ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def load_readers(bench, cell_name: str):
    """One small reader per per-layer metric, found by the metric's name."""
    readers = []
    for m in bench["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        path = HERE / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{m['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        readers.append((m, mod))
    return readers


class CompileEvents:
    """Compile traffic from jax.monitoring's own events
    (chip_smoke.CacheEvents, extended by the count of backend compiles)."""

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = self.requests = self.backend_compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def _on_duration(self, event: str, _secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def total(self) -> int:
        return self.requests + self.backend_compiles

    def snapshot(self) -> dict:
        return {"cache_hits": self.hits, "cache_misses": self.misses,
                "compile_requests": self.requests,
                "backend_compiles": self.backend_compiles}


def device_memory(devices) -> list:
    return [{k: (d.memory_stats() or {}).get(k)
             for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
            for d in devices]


def seeded_check_rounds(exp, family, config, traffic, seed, first_window_epoch,
                        events):
    """Weights from `seed`, then the two check rounds through the window's
    own round program, each on those weights and on a selection, a batch order
    and a device RNG seeded from `seed` afresh."""
    import jax
    from chipbench import program
    # one jitted call on the device; kept on the host from here on, so that
    # the device's peak stays the program's
    state0 = jax.device_get(family.init_weights(seed, config["model"]))
    checks = []
    for i, steps in enumerate(CHECK_STEPS):
        program.seed_state(exp, seed, state0, family.to_program)
        # the first check round is at the epoch the traffic poisons first
        rounds = traffic.get("poison_window_rounds") or []
        epoch = (first_window_epoch - 1 + rounds[0]
                 if (i == 0 and traffic["is_poison"] and rounds) else i + 1)
        got = family.check_round(exp, epoch, steps)
        got["state"] = family.from_program(got.pop("new_vars"), list(state0))
        checks.append(got)
        emit(phase="check_round", index=i, epoch=epoch, real_steps=steps,
             seconds=got["seconds"], compile=events.snapshot())
    return state0, checks


def fingerprint(state) -> dict:
    """The three largest leaves of a state (ties by name), each by name with
    the float64 sum of its values: enough to see in two runs' logs that they
    started from one state."""
    import numpy as np
    largest = sorted(state, key=lambda n: (-np.size(state[n]), n))[:3]
    return {n: float(np.sum(np.asarray(state[n]), dtype=np.float64))
            for n in largest}


def seed_window(exp, family, config, population) -> dict:
    """The job the warm round and the window time, which is the
    configuration's and not `--seed`'s: weights from `population_seed` as a
    trained model would carry them (the family's rule: running statistics of
    the population's own), and the program's RNGs (selection, batch order,
    device noise) from the same seed. Returns what the `window` line says of
    it. The weights are made once the check rounds' state has left the device
    and are not kept on the host."""
    import jax
    from chipbench import program
    seed, model = int(config["population_seed"]), config["model"]
    said = {"window_seed": seed}

    def window_state():
        state = jax.device_get(family.window_state(
            family.init_weights(seed, model), population, model))
        said["window_fingerprint"] = fingerprint(state)
        return state

    program.seed_state(exp, seed, window_state, family.to_program)
    jax.block_until_ready(exp.global_vars)
    return said


def judge(family, raw, model, state0, population, checks, lim,
          precision="default", every=False):
    """Each number compared, beside its limit (`lim`; `every`: also those
    without one). `raw`: the parameters as run, `model`: the configuration's."""
    from chipbench import check
    compared = []
    for got in checks:
        want = family.reference_round(raw, model, state0, population, got,
                                      precision)
        for name, value in check.compare(state0, got["state"], got, want,
                                         family.is_stat).items():
            key = f"{name}.k{got['real_steps']}"
            row = {"number": key, "value": value}
            if key in lim:
                row.update(limit=lim[key],
                           ok=bool(math.isfinite(value) and value <= lim[key]))
            if key in lim or every:
                compared.append(row)
    return compared


def end_to_end(rounds_s, failed, no_models, window_s, peak_bytes, setup_s):
    """The end-to-end arithmetic: a rate over all the work and all the time of
    the window, and the tail of all rounds."""
    finished = len(rounds_s) - failed
    return {"client_updates_per_s": (finished * no_models / window_s, "updates/s"),
            "round_s_max": (max(rounds_s), "s"),
            "peak_hbm_gib": (peak_bytes / 2 ** 30, "GiB"),
            "setup_s": (setup_s, "s")}


def trace_span_of(traffic) -> tuple:
    """(first, last) window round of the traced span: the profiler runs from
    the start of the first round the traffic names to the end of the last."""
    rounds = [int(r) for r in traffic["trace_window_rounds"]]
    if not rounds or min(rounds) < 1 or max(rounds) > int(traffic["period_rounds"]):
        raise SystemExit("chipbench: the traffic's trace_window_rounds are not "
                         "rounds of its period")
    return min(rounds), max(rounds)


def run_window(exp, seconds, first_epoch, period, periods_max, selection_seed,
               trace_span=None, trace_dir=None, spans=None):
    """The window: the program's own sequential loop over whole periods of the
    traffic's schedule. A period is started while `seconds` have not passed
    (and fewer than `periods_max`, the periods the schedule was laid over, have
    run) and is always finished. At the start of every period the selection is
    seeded from `selection_seed`, the population's: every period of every run
    selects the same clients in the same order.

    `trace_span` (first, last window round) makes it a traced window: every
    round is split into dispatch / device wait / finalize under the harness's
    clocks (`spans`) and annotations, the profiler runs over the rounds of the
    span, and the window ends with the last of them (writing the profile takes
    longer than the window; a traced run prints no end-to-end metric)."""
    import jax
    from chipbench import program
    rounds_s, results, failed, traced = [], [], 0, None
    t_window = time.perf_counter()
    periods = 0
    while (traced is None and periods < periods_max
           and time.perf_counter() - t_window < seconds):
        program.seed_selection(exp, selection_seed)
        for r in range(1, period + 1):
            epoch = first_epoch + periods * period + r - 1
            if trace_span and periods == 0 and r == trace_span[0]:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # annotations only: no hook
                jax.profiler.start_trace(       # on the planner's Python calls
                    str(trace_dir), profiler_options=options)
                t_trace = time.perf_counter()
            t0 = time.perf_counter()
            try:
                if trace_span:
                    with jax.profiler.TraceAnnotation("chipbench/dispatch"):
                        fl = exp.dispatch_round(epoch)
                    t1 = time.perf_counter()
                    with jax.profiler.TraceAnnotation("chipbench/device_wait"):
                        jax.block_until_ready((fl.payload, exp.global_vars))
                    t2 = time.perf_counter()
                    with jax.profiler.TraceAnnotation("chipbench/finalize"):
                        res = exp.finalize_round(fl)
                    t3 = time.perf_counter()
                    spans["dispatch"].append(t1 - t0)
                    spans["device_wait"].append(t2 - t1)
                    spans["finalize"].append(t3 - t2)
                else:
                    res = exp.run_round(epoch)
                exp.save_model(epoch)
                res["global_loss"] = exp.last_global_loss
                if not math.isfinite(res["global_loss"]):
                    failed += 1
                results.append(res)
            except Exception as e:  # a round that raised is a failed round
                emit(phase="round_failed", epoch=epoch, error=repr(e))
                failed += 1
            rounds_s.append(time.perf_counter() - t0)
            if trace_span and periods == 0 and r == trace_span[1]:
                jax.block_until_ready(exp.global_vars)
                window_rounds = list(range(trace_span[0], trace_span[1] + 1))
                traced = {"window_s": time.perf_counter() - t_trace,
                          "rounds": len(window_rounds),
                          "window_rounds": window_rounds}
                jax.profiler.stop_trace()
                break
        periods += 1
    jax.block_until_ready(exp.global_vars)
    return {"rounds_s": rounds_s, "results": results, "failed": failed,
            "window_s": time.perf_counter() - t_window, "periods": periods,
            "traced": traced}


def selection_repeats(agents, period) -> bool:
    """Every period of the window selected what its first period did."""
    return all(names == agents[i % period] for i, names in enumerate(agents))


def run_cell(args, sabotage=None) -> dict:
    """One run. `sabotage(exp)` is for chipbench/tests only: it breaks the
    timed path underneath the harness. Returns the result object."""
    import jax
    bench, cell, config, traffic = load_cell(
        args.workload, getattr(args, "benchmark_file", None))
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    emit(phase="device", device=device, rehearsal=args.rehearse)
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit(f"chipbench: no TPU (platform {dev.platform!r})")
    if len(devices) < cell["chips"] and not args.rehearse:
        raise SystemExit(f"chipbench: the cell needs {cell['chips']} devices, "
                         f"JAX sees {len(devices)}")
    devices = devices[:cell["chips"]]

    from chipbench import check, families, program, steps, trace as trace_mod
    family = families.of(config)
    events = CompileEvents()
    cache_dir = program.enable_cache()
    out_dir = HERE / "_out" / f"{args.workload}.{args.seed}.{args.trace}"
    if out_dir.exists():
        shutil.rmtree(out_dir)
    out_dir.mkdir(parents=True)

    # ---- set-up: build (population from the configuration's own seed)
    cut = None
    if args.rehearse:
        rehearsal = json.loads((HERE / "rehearsal.json").read_text())
        # a configuration may carry its own cut of size; the others share one
        cut = (config.get("rehearsal") or {}).get("cut") or {
            **rehearsal["cut"],
            **rehearsal["by_type"].get(config["params"]["type"], {})}
        traffic = {**traffic, **rehearsal["by_traffic"].get(cell["traffic"], {})}
    first_window_epoch = FIRST_WINDOW_EPOCH
    overrides = dict(getattr(args, "overrides", None) or {})
    if getattr(args, "override", None):
        if not args.rehearse:
            raise SystemExit("chipbench: --override is for rehearsals only")
        overrides.update(json.loads(args.override))
    params, raw = program.make_params(config, traffic, out_dir,
                                      first_window_epoch, cut, overrides)
    exp, build_s = program.build_experiment(params)
    spans = {"build": [build_s]}
    emit(phase="build", seconds=build_s, steps_per_epoch=exp.steps_per_epoch,
         epochs_max=exp.epochs_max, no_models=int(params["no_models"]),
         memory=device_memory(devices), cache_dir=cache_dir)
    if sabotage is not None:
        sabotage(exp)

    # ---- set-up: the check rounds, all of them --seed's; they compile and
    # warm the window's one round program
    marks = {"build": time.perf_counter() - T_PROCESS}  # where set-up's time goes
    state0, checks = seeded_check_rounds(exp, family, config, traffic,
                                         args.seed, first_window_epoch, events)
    marks["check_rounds"] = time.perf_counter() - T_PROCESS
    # the warm round and the window: the population's job, at every --seed
    population = family.population_of(exp)
    window_job = seed_window(exp, family, config, population)
    marks["seed_window"] = time.perf_counter() - T_PROCESS
    spans["first_round"] = [checks[0]["seconds"]]
    spans["steady_round"] = [c["seconds"] for c in checks[1:]]
    for epoch in range(len(CHECK_STEPS) + 1, first_window_epoch):
        exp.run_round(epoch)
        exp.save_model(epoch)
    jax.block_until_ready(exp.global_vars)
    marks["warm_round"] = time.perf_counter() - T_PROCESS
    # the window times the rounds, not the collector: tracing a round program
    # leaves some 230,000 long-lived objects, and a full collection over them
    # (0.12-0.14 s) fell into one window of six before they were frozen
    gc.collect()
    gc.freeze()  # until the window has closed
    compiles_before = events.total()
    setup_s = time.perf_counter() - T_PROCESS

    # ---- the window: the program's own sequential loop, in whole periods
    period = int(traffic["period_rounds"])
    spans.update(dispatch=[], device_wait=[], finalize=[])
    trace_dir = out_dir / "trace"
    won = run_window(exp, args.seconds, first_window_epoch, period,
                     int(traffic["periods_max"]), int(config["population_seed"]),
                     trace_span_of(traffic) if args.trace else None,
                     trace_dir, spans)
    gc.unfreeze()
    rounds_s, results, failed = won["rounds_s"], won["results"], won["failed"]
    window_s, traced = won["window_s"], won["traced"]
    compiles_in_window = events.total() - compiles_before
    memory = device_memory(devices)
    attempted = len(rounds_s)
    if not program.all_finite(exp.global_vars):
        failed = max(failed, 1)
    rows = program.recorded_rows(exp)
    window_rows = [r for r in rows if r["epoch"] >= first_window_epoch]
    engine = program.engine_report(exp, dev.platform == "tpu",
                                   family.engine_conditions(exp))
    agents = [[str(a) for a in r["agents"]] for r in results]
    emit(phase="window", **window_job, setup_marks_s=marks, window_s=window_s,
         rounds_s=rounds_s,
         global_acc=[r["global_acc"] for r in results],
         global_loss=[r["global_loss"] for r in results],
         backdoor_acc=[r["backdoor_acc"] for r in results],
         periods=won["periods"], period_rounds=period, agents=agents,
         recorded_rows=len(window_rows), compiles_in_window=compiles_in_window,
         compile=events.snapshot(), engine=engine, memory=memory)

    # ---- the output check, after the window: the reference follows the
    # check rounds' feeds on the same rows of the population
    t0 = time.perf_counter()
    lim = check.limits(cell["config"], cell["traffic"])
    compared = judge(family, raw, config["model"], state0, population, checks,
                     lim)
    check_ok = bool(compared) and all(row["ok"] for row in compared)
    emit(phase="check", seconds=time.perf_counter() - t0, compared=compared)

    conditions = {
        "device": (dev.platform == "tpu" and device["count"] >= cell["chips"]),
        "no_compile_in_window": compiles_in_window == 0,
        "rows_recorded": len(window_rows) == attempted - failed
        and [r["epoch"] for r in window_rows]
        == list(range(first_window_epoch, first_window_epoch + len(window_rows))),
        "no_failed_round": failed == 0 and attempted > 0,
        # an untraced window is whole periods of one selection
        "whole_periods": bool(args.trace) or (
            attempted == won["periods"] * period
            and (failed > 0 or selection_repeats(agents, period))),
        "engine": engine["ok"],
        "agrees_with_reference": check_ok,
    }
    emit(phase="conditions", **conditions)
    correct = all(conditions.values()) and not args.rehearse

    # ---- the numbers
    peak = max((m["peak_bytes_in_use"] or 0) for m in memory)
    numbers = end_to_end(rounds_s, failed, int(params["no_models"]), window_s,
                         peak, setup_s)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": dict(device, count=cell["chips"],
                                            memory_peak_bytes=peak)}
    if args.rehearse:
        result["rehearsal"] = True
        result["device"] = device
        result["check_ok"] = check_ok
        result["conditions"] = conditions
    elif not args.trace:
        names = {m["name"] for m in bench["end_to_end"]
                 if "workloads" not in m or args.workload in m["workloads"]}
        result["metrics"] = {k: {"value": v, "unit": u}
                             for k, (v, u) in numbers.items() if k in names}
    else:
        counters = {"compile_cache_hits": events.hits,
                    "compile_cache_misses": events.misses}
        reduced = trace_mod.reduce(trace_mod.find_xplane(trace_dir),
                                   cell["chips"])
        ctx = {"spans": spans, "counters": counters, "trace": reduced,
               "traced": traced}
        for m, mod in load_readers(bench, args.workload):
            value = mod.read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
        emit(phase="spans", medians={k: statistics.median(v)
                                     for k, v in spans.items() if v},
             counters=counters, traced=traced,
             plan_counts=steps.window_counts(ctx))
    # every number compared beside its limit: the result's last key
    result["compared"] = {
        **{row["number"]: {"value": row["value"], "limit": row["limit"]}
           for row in compared},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
        "failed_rounds": {"value": failed, "limit": 0},
        "rows_not_recorded": {"value": attempted - failed - len(window_rows),
                              "limit": 0}}
    shutil.rmtree(out_dir, ignore_errors=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--override", default=None,
                    help="JSON of parameters laid over the cell's (rehearsals only)")
    ap.add_argument("--benchmark-file", default=None,
                    help="another file of BENCHMARK.json's form (tests)")
    args = ap.parse_args(argv)
    result = run_cell(args)
    print(json.dumps(result), flush=True)
    for name, row in result["compared"].items():
        print(f"chipbench: {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
