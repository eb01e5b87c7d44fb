"""Chip probe of the client step's two loops (fl/client.py::split_steps): is a
step run by the width-1 job loop the step the full-width loop runs, and what
does each cost, for which model?

    chiprun --timeout 1800 -- python -m benchmarks.narrow_tail_probe --seed N
    chiprun --timeout 1800 -- python -m benchmarks.narrow_tail_probe --times \
        chipbench/configs/cifar_resnet18_dba.json configs/mnist_params.yaml
    JAX_PLATFORMS=cpu python -m benchmarks.narrow_tail_probe --rehearse

No cell runs the full-width loop since PR 31 (the engine makes every lane of
a convolutional model a job, fl/rounds.py::wide_from_of), and the benchmark's
output check (chipbench/check.py) drives every lane's first 1 and 3 steps as
ten jobs. This probe builds, beside the engine the configuration gets, one
with `wide_from = 2` (PR 28's program: the full-width loop up to the last
step two lanes share) and one with `wide_from = C + 1` (every lane a job),
on the same data, and runs with weights from `--seed`:

- **the proof** (the cell's configuration only; skipped with `--times`): one
  lane's first 1 and 3 steps from one start state (a) in the check's feed,
  where every lane is real (the `wide_from = 2` engine's full-width loop runs
  them), and (b) in a feed where only that lane holds data (every step a
  job's), each against the plain reference's `client_steps` for that lane,
  in the check's own quantities (`chipbench/check.py::compare` of that
  lane's delta) beside the cell's limits. Two lanes: the adversary's
  (stamped batches, `poison_lr`, the replacement scale) and the first benign
  one.
- **the times** (any configuration: a `chipbench/configs/*.json`, or a
  `configs/*.yaml` run on the synthetic backend without its checkpoint, each
  under the `attack_rounds` schedule): `train_fn` of the `wide_from = 2`
  engine on the plan of window round 3 (the population's selection, as the
  window draws it) cut five ways — nothing real, the steps every lane's
  benign epochs hold, the whole round, the adversary's lane alone, and alone
  for half its epochs — so that the full-width step, the width-1 step and a
  job's fixed cost come out as differences; then the whole round through the
  `wide_from = C + 1` engine, every lane a job. The `table` line is a row
  of PERF.md section 7's table: the rule of `wide_from_of` rests on it.

Prints one JSON line per reading and a last line `{"ok": ...}`; exits
non-zero when a compared number of either form is outside its limit. With
`--rehearse` (the benchmark's own cuts, on the CPU) nothing printed is a
device time.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import statistics
import sys
import time
from pathlib import Path

OUT = Path(__file__).resolve().parent.parent / "chiprun_out" / "narrow_tail_probe"


def emit(**row):
    print(json.dumps(row), flush=True)


def cut_mask(mask, lanes, epochs=None, steps=None):
    """A copy of mask [1,C,E,S,B] with only `lanes` real, and of those only
    the first `epochs` epochs and, in the first epoch alone, `steps` steps."""
    import numpy as np
    out = np.zeros_like(mask)
    out[:, lanes] = mask[:, lanes]
    if epochs is not None:
        out[:, :, epochs:] = False
    if steps is not None:
        out[:, :, 1:] = False
        out[:, :, 0, steps:] = False
    return out


def load_config(path, workload):
    """(configuration, traffic, limits or None): the cell's own files, or
    the named configuration file under the attack schedule. A YAML becomes
    the `params` of a configuration on the synthetic backend, with no
    checkpoint to resume, its population seeded as the YAML says."""
    from chipbench import check, run as harness
    _, cell, config, traffic = harness.load_cell(workload)
    if path is None:
        return config, traffic, check.limits(cell["config"], cell["traffic"])
    path = Path(path)
    if path.suffix == ".json":
        return json.loads(path.read_text()), traffic, None
    import yaml
    raw = dict(yaml.safe_load(path.read_text()),
               synthetic_data=True, resumed_model=False, save_model=False)
    return ({"name": path.stem, "params": raw,
             "population_seed": int(raw.get("random_seed", 1))},
            traffic, None)


def rehearsal_cut(config):
    """The benchmark's own cuts of size for a `--rehearse` on the CPU."""
    from chipbench import run as harness
    rehearsal = json.loads((harness.HERE / "rehearsal.json").read_text())
    cut = {**rehearsal["cut"],
           **rehearsal["by_type"].get(config["params"]["type"], {})}
    if config["params"]["type"] == "loan":   # its clients are states
        cut = {k: cut[k] for k in ("batch_size", "test_batch_size",
                                   "no_models", "scale_weights_poison")}
    return cut


def engine_under(exp, rule: str, answer):
    """An engine beside the experiment's own, on its data, built with
    `fl/rounds.py::<rule>` answering `answer` whatever the model."""
    import dba_mod_tpu.fl.rounds as rounds_mod
    kept = getattr(rounds_mod, rule)
    setattr(rounds_mod, rule, lambda *_: answer)
    try:
        return rounds_mod.RoundEngine(
            exp.params, exp.model_def, exp.device_data, exp.eval_plans,
            mesh=None, num_segments=exp.interval)
    finally:
        setattr(rounds_mod, rule, kept)


def engines_of(exp):
    """{wide_from: engine} for 2 and C + 1 on the experiment's own data: the
    engine it was built with, and one built beside it under the other rule."""
    C = exp.engine.hyper.no_models
    found = {exp.engine.wide_from: exp.engine}
    for want in (2, C + 1):
        if want not in found:
            found[want] = engine_under(exp, "wide_from_of", want)
    return found[2], found[C + 1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="tiny_dba_attack")
    ap.add_argument("--times", nargs="+", metavar="CONFIG",
                    help="the times alone, for each of these configuration "
                         "files (chipbench/configs/*.json, configs/*.yaml)")
    ap.add_argument("--seed", type=int, default=2147800001)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)

    import jax
    from chipbench import program

    dev = jax.devices()[0]
    emit(phase="device", platform=dev.platform, kind=dev.device_kind,
         rehearsal=args.rehearse)
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit(f"narrow_tail_probe: no TPU (platform {dev.platform!r})")
    if args.rehearse:
        # (on the chip every call starts without a cache, and writing the
        # entry of a program that carries Tiny-ImageNet costs gigabytes of
        # the host's memory beside the compile)
        program.enable_cache()
    OUT.mkdir(parents=True, exist_ok=True)
    ok = True
    for path in args.times or [None]:
        ok &= probe(args, *load_config(path, args.workload))
        jax.clear_caches()   # the last configuration's programs and data
        gc.collect()
    emit(ok=ok, rehearsal=args.rehearse)
    return 0 if ok else 1


def probe(args, config, traffic, lim) -> bool:
    """One configuration: the proof where `lim` (the cell's limits) is
    given, then the times."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import program, run as harness
    from dba_mod_tpu.data.batching import plan_step_counts
    from dba_mod_tpu.fl.client import STEP_CHUNK, split_steps

    first = harness.FIRST_WINDOW_EPOCH
    params, raw = program.make_params(
        config, traffic, OUT, first,
        rehearsal_cut(config) if args.rehearse else None)
    exp, build_s = program.build_experiment(params)
    eng_2, eng_jobs = engines_of(exp)
    C = exp.engine.hyper.no_models
    emit(phase="build", config=config["name"], seconds=build_s, lanes=C,
         wide_from=exp.engine.wide_from, fused_pallas=eng_2.fused_pallas,
         steps_per_epoch=exp.steps_per_epoch, epochs_max=exp.epochs_max)
    rng_t = jax.random.key(args.seed % (2 ** 31 - 1))

    def train(eng, tasks_seq, idx_seq, mask, lane):
        out = eng.train_fn(exp.global_vars, tasks_seq, idx_seq,
                           jnp.asarray(mask), lane, rng_t)
        return jax.block_until_ready(out)

    ok = True
    if lim is not None:
        ok = proof(args, config, traffic, raw, lim, exp,
                   lambda *feed: train(eng_2, *feed))

    # ------------------------------------------------------------- the times
    program.seed_selection(exp, int(config["population_seed"]))
    period_round = traffic["poison_window_rounds"][0]
    for r in range(1, period_round + 1):   # the window's draws, in its order
        tasks_seq, idx_seq, mask_seq, _, lane = exp.build_static_round_inputs(
            first - 1 + r)
    mask = np.asarray(mask_seq)
    poisoning = np.asarray(jax.device_get(tasks_seq).poisoning_per_batch)[0]
    adv = int(np.argmax(poisoning))
    if poisoning[adv] <= 0:
        raise SystemExit("narrow_tail_probe: no poisoning lane in the round")
    epochs = mask[0].any(axis=(2, 3)).sum(axis=1)       # [C]
    benign_epochs = int(np.max(np.delete(epochs, adv)))
    feeds = {
        "nothing": cut_mask(mask, []),
        "shared": cut_mask(mask, np.arange(C), epochs=benign_epochs),
        "round": mask,
        "solo": cut_mask(mask, [adv]),
        "solo_half": cut_mask(mask, [adv], epochs=int(epochs[adv]) // 2),
    }

    def timed(eng, name):
        m = feeds[name]
        counts = plan_step_counts([m[0]], STEP_CHUNK, eng.wide_from)
        counts["jobs"] = int(split_steps(jnp.asarray(m[0]), eng.wide_from).n_jobs)
        train(eng, tasks_seq, idx_seq, m, lane)
        secs = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            train(eng, tasks_seq, idx_seq, m, lane)
            secs.append(time.perf_counter() - t0)
        emit(phase="time", config=config["name"], wide_from=eng.wide_from,
             feed=name, seconds=secs,
             **{k: counts[k] for k in ("steps_run", "lane_steps_real",
                                       "steps_wide", "lane_steps_narrow",
                                       "jobs")})
        return statistics.median(secs), counts

    read = {name: timed(eng_2, name) for name in feeds}
    (t_none, _), (t_shared, c_shared), (t_round, c_round), (t_solo, c_solo), \
        (t_half, c_half) = (read[n] for n in ("nothing", "shared", "round",
                                              "solo", "solo_half"))
    narrow_s = (t_solo - t_half) / max(
        c_solo["lane_steps_narrow"] - c_half["lane_steps_narrow"], 1)
    # the solo round less its steps: a row out, a row in, the loop
    job_s = t_solo - t_none - c_solo["lane_steps_narrow"] * narrow_s
    jobs_of = lambda c: c["lane_steps_narrow"] * narrow_s + c["jobs"] * job_s
    # the shared epochs less the few steps their longest lanes hold alone
    wide_s = (t_shared - t_none - jobs_of(c_shared)) / max(
        c_shared["steps_wide"], 1)
    if eng_2 is not exp.engine:
        # two programs that each carry the dataset: let the first go before
        # the second compiles (the host ran out of memory at Tiny-ImageNet's
        # size with both, PR 31)
        del eng_2
        jax.clear_caches()
        gc.collect()
    t_none_jobs, _ = timed(eng_jobs, "nothing")
    t_shared_jobs, _ = timed(eng_jobs, "shared")
    t_round_jobs, c_jobs = timed(eng_jobs, "round")
    emit(phase="table", rehearsal=args.rehearse, config=config["name"],
         lanes=C, wide_step_ms=1e3 * wide_s, narrow_step_ms=1e3 * narrow_s,
         job_fixed_ms=1e3 * job_s, nothing_ms=1e3 * t_none,
         wide_over_narrow=wide_s / narrow_s if narrow_s > 0 else None,
         # the whole round less its full-width steps, over its jobs' steps
         narrow_step_ms_round=1e3 * (
             t_round - t_none - c_round["steps_wide"] * wide_s
             - c_round["jobs"] * job_s) / max(c_round["lane_steps_narrow"], 1),
         # the same work through both engines: the epochs every lane holds
         # (the full-width loop's best case), then the whole round
         shared_ms_wide_from_2=1e3 * (t_shared - t_none),
         shared_ms_every_lane_a_job=1e3 * (t_shared_jobs - t_none_jobs),
         round_ms_wide_from_2=1e3 * (t_round - t_none),
         round_ms_every_lane_a_job=1e3 * (t_round_jobs - t_none_jobs),
         round_ms_every_lane_a_job_from_the_fit=1e3 * jobs_of(c_jobs),
         round_counts_wide_from_2=c_round, round_counts_every_lane_a_job=c_jobs,
         engine_wide_from=exp.engine.wide_from)
    return ok


def proof(args, config, traffic, raw, lim, exp, train) -> bool:
    """`train(tasks_seq, idx_seq, mask, lane)`: the `wide_from = 2`
    engine's `train_fn` from the experiment's seeded state."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import check, program, run as harness
    from chipbench.reference import resnet18 as ref
    from dba_mod_tpu.data.batching import plan_step_counts
    from dba_mod_tpu.fl.client import STEP_CHUNK

    first = harness.FIRST_WINDOW_EPOCH
    model = config["model"]
    state0 = jax.device_get(
        ref.init_weights(args.seed, model["variant"], model["num_classes"]))
    names = list(state0)
    population = harness.population_of(exp)
    program.seed_state(exp, args.seed, state0)
    epoch = first - 1 + traffic["poison_window_rounds"][0]
    tasks_seq, idx_seq, mask_seq, _, lane = exp.build_static_round_inputs(epoch)
    tasks = jax.device_get(tasks_seq)
    idx, mask = np.asarray(idx_seq), np.asarray(mask_seq)
    C = mask.shape[1]
    poisoning = np.asarray(tasks.poisoning_per_batch)[0]
    has_data = mask[0].any(axis=(1, 2, 3))
    adv = int(np.argmax(poisoning))
    ben = int(np.flatnonzero((poisoning == 0) & has_data)[0])
    if poisoning[adv] <= 0:
        raise SystemExit("narrow_tail_probe: no poisoning lane at the epoch")

    @functools.partial(jax.jit, static_argnums=(6, 7))
    def reference(state, xs, ys, ms, lr, scale, pixels, first_k):
        return ref.client_steps(
            state, xs, ys, ms, lr, model["variant"],
            momentum=float(raw["momentum"]), decay=float(raw["decay"]),
            pixels=pixels, swap_label=int(raw["poison_label_swap"]),
            first_k=first_k, scale=scale)

    def lane_numbers(got, c, k):
        """check.compare's numbers for lane c alone after its first k steps."""
        rows = idx[0, c, 0, :k]
        pixels = ()
        if poisoning[c] > 0:
            pixels = tuple(tuple(px) for px in raw[
                f"{int(np.asarray(tasks.adv_index)[0, c])}_poison_pattern"])
        delta, loss = reference(
            {n: jnp.asarray(v) for n, v in state0.items()},
            jnp.asarray(population["train_images"][rows]),
            jnp.asarray(population["train_labels"][rows]),
            jnp.asarray(mask[0, c, 0, :k]),
            jnp.float32(np.asarray(tasks.lr_row)[0, c, 0]),
            jnp.float32(np.asarray(tasks.scale)[0, c]), pixels,
            int(poisoning[c]))
        delta = jax.device_get(delta)
        want_norm = math.sqrt(sum(float(np.sum(np.square(v, dtype=np.float64)))
                                  for n, v in delta.items()
                                  if not ref.is_stat(n)))
        got_delta = program.from_program(
            jax.tree_util.tree_map(lambda l: l[c], got.deltas), names)
        plus = lambda d: {n: np.asarray(state0[n], np.float64)
                          + np.asarray(d[n], np.float64) for n in names}
        return check.compare(
            state0, plus(got_delta),
            {"loss_sum": np.asarray(got.metrics.loss_sum)[0, [c], 0],
             "delta_norms": np.asarray(got.delta_norms)[[c]],
             "global_loss": 1.0},
            {"new": plus(delta), "loss_sum": np.array([float(jnp.sum(loss))]),
             "delta_norms": np.array([want_norm]), "global_loss": 1.0})

    ok = True
    for k in harness.CHECK_STEPS:
        wide = train(tasks_seq, idx_seq, cut_mask(mask, np.arange(C), steps=k),
                     lane)
        for who, c in (("adversary", adv), ("benign", ben)):
            tail_mask = cut_mask(mask, [c], steps=k)
            counts = plan_step_counts([tail_mask[0]], STEP_CHUNK, 2)
            assert (counts["steps_wide"], counts["lane_steps_narrow"]) == (0, k)
            tail = train(tasks_seq, idx_seq, tail_mask, lane)
            for form, got in (("wide", wide), ("tail", tail)):
                for name, value in lane_numbers(got, c, k).items():
                    key = f"{name}.k{k}"
                    if key not in lim:
                        continue
                    inside = bool(math.isfinite(value) and value <= lim[key])
                    ok &= inside
                    emit(phase="proof", lane=who, form=form, number=key,
                         value=value, limit=lim[key], ok=inside)
            same = jax.tree_util.tree_map(
                lambda a, b: bool(jnp.array_equal(a[c], b[c])),
                wide.deltas, tail.deltas)
            emit(phase="proof", lane=who, k=k,
                 tail_delta_bit_equal_to_wide=all(
                     jax.tree_util.tree_leaves(same)))

    return ok


if __name__ == "__main__":
    sys.exit(main())
