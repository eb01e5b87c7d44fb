"""Chip probe of the local battery's two forms (fl/rounds.py::
make_local_battery): are the rows the job loop gives the rows the stacked
`vmap` gives, and what does each form cost, for which model?

    chiprun --timeout 1500 -- python -m benchmarks.local_battery_probe --seed N
    chiprun --timeout 1500 -- python -m benchmarks.local_battery_probe --times \
        chipbench/configs/cifar_resnet18_dba.json configs/loan_params.yaml
    JAX_PLATFORMS=cpu python -m benchmarks.local_battery_probe --rehearse

The benchmark's output check does not compare the local battery's rows
(ROADMAP B6), and two compiles of a round program train models that differ
from the fourth digit on (PR 27), so whole runs cannot be compared either.
This probe builds, on one `Experiment`'s data, the engine with every lane's
clean test a single-model job (`lanes_as_jobs` true) and the one with the
clean part stacked (false: the battery as it was before the clean jobs),
and gives both the same models:

- **the proof** (the cell's configuration only; skipped with `--times`): the
  ten models `train_fn` trains on window round 3's plan (one adversary) from
  weights of `--seed`, through both batteries on that round's tasks:
  `correct` and `count` of all four parts in every slot must be equal, and
  `loss` within 1e-6 relative.
- **the times** (any configuration, as benchmarks/narrow_tail_probe.py reads
  them): both batteries on a clean round's tasks and on the poisoned
  round's, which differ by a known number of poison jobs, so a poison job,
  the clean part, and with them a C-model step and a single-model step
  come out as differences and quotients. The `table` line is a row of
  PERF.md section 7's eval-step table: `lanes_as_jobs` rests on it.

Prints one JSON line per reading and a last line `{"ok": ...}`; exits
non-zero when a compared number is outside its limit. With `--rehearse` (the
benchmark's own cuts, on the CPU) nothing printed is a device time.
"""
from __future__ import annotations

import argparse
import gc
import statistics
import sys
import time
from pathlib import Path

from benchmarks.narrow_tail_probe import (emit, engine_under, load_config,
                                          rehearsal_cut)

OUT = Path(__file__).resolve().parent.parent / "chiprun_out" / "local_battery_probe"
LOSS_RTOL = 1e-6


def engines_of(exp):
    """(clean part as jobs, clean part stacked): the experiment's own engine
    and one built beside it under the other answer of the rule."""
    other = not exp.engine.clean_jobs
    found = {exp.engine.clean_jobs: exp.engine,
             other: engine_under(exp, "lanes_as_jobs", other)}
    return found[True], found[False]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="tiny_dba_attack")
    ap.add_argument("--times", nargs="+", metavar="CONFIG",
                    help="the times alone, for each of these configuration "
                         "files (chipbench/configs/*.json, configs/*.yaml)")
    ap.add_argument("--seed", type=int, default=2147800001)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)

    import jax
    from chipbench import program

    dev = jax.devices()[0]
    emit(phase="device", platform=dev.platform, kind=dev.device_kind,
         rehearsal=args.rehearse)
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit(f"local_battery_probe: no TPU (platform {dev.platform!r})")
    if args.rehearse:
        program.enable_cache()
    OUT.mkdir(parents=True, exist_ok=True)
    ok = True
    for path in args.times or [None]:
        config, traffic, _ = load_config(path, args.workload)
        ok &= probe(args, config, traffic, prove=path is None)
        jax.clear_caches()   # the last configuration's programs and data
        gc.collect()
    emit(ok=ok, rehearsal=args.rehearse)
    return 0 if ok else 1


def probe(args, config, traffic, prove: bool) -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import program, run as harness
    from dba_mod_tpu.fl.evaluation import battery_eval_counts

    first = harness.FIRST_WINDOW_EPOCH
    params, _ = program.make_params(
        config, traffic, OUT, first,
        rehearsal_cut(config) if args.rehearse else None)
    exp, build_s = program.build_experiment(params)
    eng_jobs, eng_stacked = engines_of(exp)
    C = exp.engine.hyper.no_models
    plans = exp.eval_plans
    steps = {"clean": int(plans.clean_idx.shape[0]),
             "poison": int(plans.poison_idx.shape[0])}
    emit(phase="build", config=config["name"], seconds=build_s, lanes=C,
         rule_gives_jobs=exp.engine.clean_jobs, eval_steps=steps,
         eval_batch=int(plans.clean_idx.shape[1]))

    # the window's draws, in its order: a clean round, then a poisoned one
    program.seed_selection(exp, int(config["population_seed"]))
    poisoned_round = traffic["poison_window_rounds"][0]
    feeds = {}
    for r in range(1, poisoned_round + 1):
        feeds[r] = exp.build_static_round_inputs(first - 1 + r)
    tasks_clean = feeds[poisoned_round - 1][0]
    tasks_seq, idx_seq, mask_seq, _, lane = feeds[poisoned_round]
    poisoning = np.asarray(jax.device_get(tasks_seq).poisoning_per_batch)
    if not (poisoning > 0).any() or (np.asarray(jax.device_get(
            tasks_clean).poisoning_per_batch) > 0).any():
        raise SystemExit("local_battery_probe: the rounds are not a clean "
                         "one and a poisoned one")

    zeros = jax.tree_util.tree_map(
        lambda l: jnp.zeros((C,) + l.shape, l.dtype), exp.global_vars)
    deltas = zeros
    if prove:
        deltas = jax.block_until_ready(exp.engine.train_fn(
            exp.global_vars, tasks_seq, idx_seq, mask_seq, lane,
            jax.random.key(args.seed % (2 ** 31 - 1))).deltas)

    def battery(eng, tasks):
        return jax.block_until_ready(eng.local_evals_fn(
            exp.global_vars, deltas, tasks, zeros))

    ok = True
    if prove:
        got, want = (jax.device_get(battery(e, tasks_seq))
                     for e in (eng_jobs, eng_stacked))
        for part in got._fields:
            g, w = getattr(got, part), getattr(want, part)
            # a x100 model's loss overflows in both forms alike: such a row
            # is recorded as `nan` by either
            rel = np.where(np.isnan(g.loss) & np.isnan(w.loss), 0.0,
                           np.abs(g.loss - w.loss)
                           / np.maximum(np.abs(w.loss), 1e-30))
            same = bool((g.correct == w.correct).all()
                        and (g.count == w.count).all())
            part_ok = same and bool((rel <= LOSS_RTOL).all())
            ok &= part_ok
            emit(phase="proof", part=part, ok=part_ok, counts_equal=same,
                 slots_run=int(np.count_nonzero(w.count)),
                 loss_rel_max=float(rel.max()), loss_rtol=LOSS_RTOL,
                 correct=w.correct.tolist(), count=w.count.tolist(),
                 loss_jobs=g.loss.tolist(), loss_stacked=w.loss.tolist())

    def timed(eng, tasks):
        battery(eng, tasks)
        secs = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            battery(eng, tasks)
            secs.append(time.perf_counter() - t0)
        return statistics.median(secs), secs

    def poison_jobs(tasks) -> int:
        """A listed adversary off its schedule still gets its trigger row:
        a clean round has poison jobs too."""
        rows = jax.device_get(tasks)
        counts = battery_eval_counts(
            [jax.tree_util.tree_map(lambda l: l[s], rows)
             for s in range(rows.adv_slot.shape[0])], True,
            bool(exp.params["baseline"]), exp.engine.forensics, True)
        return counts["battery_evals_run"] - counts["battery_clean_evals"]

    read, jobs = {}, {}
    for form, eng in (("jobs", eng_jobs), ("stacked", eng_stacked)):
        for feed, tasks in (("clean_round", tasks_clean),
                            ("poisoned_round", tasks_seq)):
            read[form, feed], secs = timed(eng, tasks)
            jobs[feed] = poison_jobs(tasks)
            emit(phase="time", config=config["name"], form=form, feed=feed,
                 poison_jobs=jobs[feed], seconds=secs)
    more = jobs["poisoned_round"] - jobs["clean_round"]

    def clean_part(form):
        """(a poison job, the clean part): the poisoned round runs `more`
        poison jobs beyond the clean round's, everything else alike."""
        job = (read[form, "poisoned_round"] - read[form, "clean_round"]) / more
        return job, read[form, "clean_round"] - jobs["clean_round"] * job

    (job_j, clean_j), (job_s, clean_s) = clean_part("jobs"), clean_part("stacked")
    stacked_step = clean_s / steps["clean"]
    single_step = clean_j / (C * steps["clean"])
    emit(phase="table", rehearsal=args.rehearse, config=config["name"],
         lanes=C, eval_batch=int(plans.clean_idx.shape[1]),
         stacked_step_ms=1e3 * stacked_step, single_step_ms=1e3 * single_step,
         stacked_over_single=stacked_step / single_step,
         clean_part_ms_stacked=1e3 * clean_s, clean_part_ms_jobs=1e3 * clean_j,
         poison_job_ms_stacked=1e3 * job_s, poison_job_ms_jobs=1e3 * job_j,
         poison_jobs=jobs,
         clean_round_ms_stacked=1e3 * read["stacked", "clean_round"],
         clean_round_ms_jobs=1e3 * read["jobs", "clean_round"],
         poisoned_round_ms_stacked=1e3 * read["stacked", "poisoned_round"],
         poisoned_round_ms_jobs=1e3 * read["jobs", "poisoned_round"],
         rule_gives_jobs=exp.engine.clean_jobs)
    return ok


if __name__ == "__main__":
    sys.exit(main())
