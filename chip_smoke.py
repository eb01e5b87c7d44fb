"""chip_smoke.py — proof that the main path starts and is right on a TPU v5e.

    python chip_smoke.py             one chip (what the driver runs)
    python chip_smoke.py --chips 4   the `clients` mesh on a four-chip host
                                     against the same rounds on one device —
                                     that phase only
    ... --rehearse                   the same control flow on whatever backend
                                     is there, cut to a tiny size, Pallas in
                                     interpret mode: a rehearsal, never "ok"

One chip: `configs/cifar_params.yaml` as it stands — the reference's narrow
ResNet-18, 100 participants, 10 clients a round, batch 64, Dirichlet 0.5, the
four-adversary pixel trigger, 50,000/10,000 synthetic images — through
`dba_mod_tpu.main.main`: `pretrain` (saves an orbax checkpoint) → a resumed
attack run with poisoned rounds and results saved. Only the schedule is
shortened, and what was changed is printed. Then one clean round with the
fused Pallas update against the same round with the plain jnp update, from
the same checkpoint.

Everything runs in THIS process (the chip belongs to one process at a time).
Any failed check raises; nothing is caught. Earlier stdout lines are one JSON
object each (findings, not metrics); the last line is the verdict:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

printed only after every phase passed on a TPU. Without a TPU the script
exits non-zero before running anything (unless rehearsing).
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import yaml

REPO = Path(__file__).resolve().parent
OUT = REPO / "chiprun_out"   # git-ignored; the run's own folder is wiped

# Clean rounds from a fresh init, then the checkpoint. With eta = 0.1 the
# global model (BatchNorm running statistics included) moves a tenth of the
# way to the clients' each round: on the chip it sat at chance for 29 rounds
# and reached 100 % at round 50 (PR 21's runs). Rounds are ~1.5 s there; the
# compile is what costs.
PRETRAIN_ROUNDS = 60
ATTACK_ROUNDS = 3       # resumed: one clean round, then adversaries 0 and 1
MESH_WARM_ROUNDS = 56   # --chips 4: clean rounds on the mesh to a trained model
MESH_ROUNDS = 3         # then compared: two clean rounds, then adversary 0
# main-task accuracy (percent) the pretrain leg must reach; chance is 10.
# Fixed from the chip runs of PR 21 (CHANGES.md): 10.17 after 8 rounds, 100.0
# after 60 — half of what the 60-round run saw.
PRETRAIN_MIN_ACC = 50.0
# fused Pallas update vs plain jnp update, the first (clean) round after the
# checkpoint, on the chip: relative L2 distance of the applied global update,
# and percentage points of main-task / backdoor accuracy. A clean round: the
# x100 replacement scale of a poisoned one amplifies last-bit differences
FUSED_UPDATE_RTOL = 1e-2
FUSED_ACC_TOL = 1.0
# four-chip mesh vs one device, same seed, same TRAINED start state: relative
# L2 distance of the cumulative global update after each CLEAN round, and
# percentage points of accuracy. From a fresh init no envelope holds at this
# size — PR 21's first four-chip run saw the two programs' updates come out
# uncorrelated (relative L2 1.3 after one round: 38 steps at lr 0.1 from
# random weights amplify last-bit differences without bound), which is why the
# mesh first trains the model the comparison starts from. The poisoned round
# (x100 model replacement) must run and stay finite; its differences are
# reported, not bounded.
MESH_UPDATE_RTOL = 0.1
MESH_ACC_TOL = 4.0

# --rehearse: cuts of size only (the model stays the CIFAR ResNet-18); the
# fused kernel runs in Pallas interpret mode so its path is still walked, and
# the replacement scale follows the smaller cohort (no_models / eta)
REHEARSAL_CUT = dict(
    synthetic_train_size=640, synthetic_test_size=128, batch_size=16,
    test_batch_size=64, no_models=4, number_of_total_participants=12,
    adversary_list=[3, 5, 7, 9], scale_weights_poison=40, internal_epochs=1,
    internal_poison_epochs=2, fused_updates=True, fused_interpret=True)
REHEARSAL_PRETRAIN_ROUNDS = 2
REHEARSAL_MESH_WARM_ROUNDS = 2


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def smoke_config(work: Path, rehearse: bool,
                 first_attack_epoch: int) -> Path:
    """configs/cifar_params.yaml with only the schedule (and the output
    locations) changed, written where the CLI can load it."""
    raw = yaml.safe_load((REPO / "configs/cifar_params.yaml").read_text())
    changed = {
        # the single-shot distributed schedule (203/205/207/209), moved
        # next to the resume point: adversary i poisons one round
        **{f"{i}_poison_epochs": [first_attack_epoch + 1 + i]
           for i in range(4)},
        "run_dir": str(work / "runs"),
        "checkpoint_dir": str(work / "saved_models"),
    }
    if rehearse:
        changed.update(REHEARSAL_CUT)
    raw.update(changed)
    path = work / "cifar_smoke.yaml"
    path.write_text(yaml.safe_dump(raw))
    emit(phase="config", source="configs/cifar_params.yaml", changed=changed,
         cli_overrides="--epochs, --resume, --synthetic", rehearsal=rehearse)
    return path


class CacheEvents:
    """Persistent-compile-cache traffic, from jax.monitoring's own events."""

    def __init__(self, cache_dir: str):
        import jax.monitoring
        self.dir = cache_dir
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        files = [f for f in Path(self.dir).glob("*") if f.is_file()]
        return {"dir": self.dir, "read_hits": self.hits,
                "misses_compiled": self.misses, "entries": len(files),
                "bytes": sum(f.stat().st_size for f in files)}


def memory(devices) -> list:
    return [{k: (d.memory_stats() or {}).get(k)
             for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
            for d in devices]


def probe_block_until_ready() -> None:
    """Does block_until_ready wait for the device here? ~0.5 s of chained
    matmuls: time to return from dispatch, to block, then to fetch a scalar;
    and, on a second dispatch, the scalar fetch alone."""
    n = 8192 if jax.default_backend() == "tpu" else 512

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(
            0, 100, lambda _, y: (y @ x) * (1.0 / n), x)

    x = jnp.ones((n, n), jnp.bfloat16)
    float(work(x)[0, 0])  # compile + warm
    t0 = time.perf_counter()
    y = work(x)
    t_dispatch = time.perf_counter() - t0
    y.block_until_ready()
    t_block = time.perf_counter() - t0
    float(y[0, 0])
    t_fetch_after = time.perf_counter() - t0 - t_block
    t0 = time.perf_counter()
    float(work(x)[0, 0])
    t_fetch_only = time.perf_counter() - t0
    emit(phase="probe_block_until_ready", dispatch_s=t_dispatch,
         block_until_ready_s=t_block, scalar_fetch_after_block_s=t_fetch_after,
         scalar_fetch_alone_s=t_fetch_only,
         blocks=bool(t_block > 0.5 * t_fetch_only))


def spy_on_experiment():
    """main() builds its Experiment itself; a recording subclass lets the
    smoke see the engine it ran and time each round around
    block_until_ready."""
    import dba_mod_tpu.fl.experiment as exp_mod

    class SpiedExperiment(exp_mod.Experiment):
        instances: list = []

        def __init__(self, *a, **kw):
            t0 = time.perf_counter()
            super().__init__(*a, **kw)
            jax.block_until_ready((self.global_vars, self.fg_state))
            self.build_seconds = time.perf_counter() - t0
            self.round_seconds: list = []
            self.results: list = []
            SpiedExperiment.instances.append(self)

        def run_round(self, epoch):
            t0 = time.perf_counter()
            r = super().run_round(epoch)
            jax.block_until_ready(self.global_vars)
            self.round_seconds.append(time.perf_counter() - t0)
            self.results.append(dict(r, global_loss=self.last_global_loss))
            return r

    exp_mod.Experiment = SpiedExperiment
    return SpiedExperiment


def check_engine_is_the_chips(exp, fused: bool = True) -> dict:
    """The TPU engine: donated round program built AND the one dispatched;
    unsharded (`fused`), the compiled — not interpreted — fused Pallas
    update, and the plain jnp update on the mesh."""
    eng = exp.engine
    donated = eng.round_fn_donated
    seen = {"round_fn_donated_built": donated is not None,
            "use_donated_round": bool(exp._use_donated_round),
            "donated_programs_compiled":
                donated._cache_size() if donated is not None else 0,
            "undonated_programs_compiled": eng.round_fn._cache_size(),
            "fused_pallas": bool(eng.fused_pallas),
            "fused_interpret": bool(eng.fused_interpret)}
    check(seen["round_fn_donated_built"] and seen["use_donated_round"]
          and seen["donated_programs_compiled"] >= 1
          and seen["undonated_programs_compiled"] == 0,
          f"the round did not run through round_fn_donated: {seen}")
    check(seen["fused_pallas"] == fused and not seen["fused_interpret"],
          f"fused Pallas update: wanted compiled={fused}, saw {seen}")
    return seen


def leg_report(name: str, exp, wall_s: float) -> dict:
    rounds = exp.round_seconds
    steady = rounds[1:]
    check(all(math.isfinite(r["global_acc"]) for r in exp.results),
          f"{name}: non-finite main-task accuracy")
    out = {"phase": name, "wall_s": wall_s,
           "experiment_build_s": exp.build_seconds,
           "first_round_s_with_compile": rounds[0],
           "steady_round_s": steady,
           "steady_round_s_median": float(np.median(steady)),
           "setup_and_compile_s": wall_s - float(np.sum(steady)),
           "global_acc": [r["global_acc"] for r in exp.results],
           "global_loss": [r["global_loss"] for r in exp.results],
           "backdoor_acc": [r["backdoor_acc"] for r in exp.results],
           "memory": memory(jax.devices()[:1])}
    out["engine"] = (check_engine_is_the_chips(exp) if on_tpu() else
                     "rehearsal: not the chip's engine, not checked")
    return out


def check_saved_results(folder: Path, epochs: range, adversaries) -> dict:
    """Recorder CSVs + metrics.jsonl of the attack run: present, one
    metrics row per round, every number finite — except the loss of an
    adversary's POST-SCALING local model, which the reference evaluates too
    (image_train.py:275-295) and which may overflow: scaling the whole
    state by 100 can push BatchNorm variances below zero. Those rows are
    counted and reported, never hidden."""
    import csv
    rows = [json.loads(l) for l in
            (folder / "metrics.jsonl").read_text().splitlines()]
    check([r["epoch"] for r in rows] == list(epochs),
          f"metrics.jsonl epochs {[r['epoch'] for r in rows]} != "
          f"{list(epochs)}")
    for r in rows:
        for k in ("global_acc", "global_loss", "backdoor_acc"):
            check(math.isfinite(r[k]), f"metrics.jsonl epoch {r['epoch']}: "
                                        f"{k} = {r[k]}")
    adversaries = {str(a) for a in adversaries}
    sizes, overflowed = {}, []
    for name in ("train_result.csv", "test_result.csv", "round_result.csv",
                 "posiontest_result.csv", "poisontriggertest_result.csv"):
        with open(folder / name) as f:
            table = list(csv.reader(f))
        check(len(table) > 1, f"{name} has no rows")
        sizes[name] = len(table) - 1
        for row in table[1:]:
            bad = [c for c in row
                   if c.lower().lstrip("-") in ("nan", "inf")]
            if not bad:
                continue
            check(name.startswith("pos") or name.startswith("poison"),
                  f"non-finite row in {name}: {row}")
            check(row[0] in adversaries,
                  f"non-finite row of a non-adversary in {name}: {row}")
            overflowed.append({"file": name, "row": row})
    return {"folder": str(folder.relative_to(REPO)), "metrics_rows": len(rows),
            "csv_rows": sizes, "overflowed_scaled_adversary_rows": overflowed}


def flat(tree) -> np.ndarray:
    return np.concatenate([np.asarray(l, np.float64).ravel()
                           for l in jax.tree_util.tree_leaves(tree)])


def one_chip(work: Path, rehearse: bool, cache: CacheEvents) -> None:
    from dba_mod_tpu import main as cli
    from dba_mod_tpu.config import Params
    spied = spy_on_experiment()
    probe_block_until_ready()

    pre_rounds = REHEARSAL_PRETRAIN_ROUNDS if rehearse else PRETRAIN_ROUNDS
    first_attack = pre_rounds + 1
    last_attack = pre_rounds + ATTACK_ROUNDS
    cfg_path = smoke_config(work, rehearse, first_attack)
    ckpt_name = f"cifar_pretrain/model_last.pt.tar.epoch_{pre_rounds}"
    check(not (work / "saved_models").exists(), "stale checkpoint dir")

    # ---- leg 1: pretrain through the CLI → orbax checkpoint
    t0 = time.perf_counter()
    rc = cli.main(["pretrain", "--params", str(cfg_path), "--epochs",
                   str(pre_rounds), "--synthetic"])
    wall = time.perf_counter() - t0
    check(rc == 0, f"pretrain exited {rc}")
    check((work / "saved_models" / ckpt_name).is_dir(),
          "pretrain wrote no checkpoint")
    pre = spied.instances[-1]
    emit(**leg_report("pretrain", pre, wall),
         cache=cache.snapshot())
    pretrain_acc = pre.results[-1]["global_acc"]
    pre_vars = jax.device_get(pre.global_vars)
    del pre
    spied.instances.clear()

    # ---- leg 2: resume that checkpoint, attack, save results
    t0 = time.perf_counter()
    rc = cli.main(["--params", str(cfg_path), "--epochs", str(last_attack),
                   "--synthetic", "--resume", ckpt_name])
    wall = time.perf_counter() - t0
    check(rc == 0, f"attack run exited {rc}")
    att = spied.instances[-1]
    check(att.start_epoch == first_attack,
          f"resumed at epoch {att.start_epoch}, expected {first_attack}")
    report = leg_report("attack", att, wall)
    check(len(att.results) == ATTACK_ROUNDS, "attack leg lost rounds")
    cfg_now = Params.from_yaml(cfg_path)
    when = {cfg_now.poison_epochs_for(i)[0]: adv
            for i, adv in enumerate(cfg_now.adversary_list)}
    poisoned = [r for r in att.results if r["epoch"] in when]
    check(len(poisoned) >= 1 and all(
        when[r["epoch"]] in r["agents"] and r["backdoor_acc"] is not None
        and math.isfinite(r["backdoor_acc"]) for r in poisoned),
        "no poisoned round with its adversary and a backdoor accuracy")
    saved = check_saved_results(att.folder,
                                range(first_attack, last_attack + 1),
                                cfg_now.adversary_list)
    emit(**report, poisoned_epochs=[r["epoch"] for r in poisoned],
         saved=saved, cache=cache.snapshot())
    del att
    spied.instances.clear()

    # ---- fused Pallas update vs plain jnp update: the round after the
    # checkpoint the pretrain leg wrote, from that checkpoint
    outs = {}
    for fused in (True, False):
        p = Params.from_yaml(cfg_path)
        p.raw.update(synthetic_data=True, epochs=last_attack,
                     resumed_model=True, resumed_model_name=ckpt_name,
                     fused_updates=fused)
        t0 = time.perf_counter()
        e = spied(p, save_results=False)
        r = e.run_round(first_attack)
        check(e.engine.fused_pallas == fused, "fused_updates not honoured")
        outs[fused] = (jax.device_get(e.global_vars), r,
                       time.perf_counter() - t0)
        del e
    (vf, rf, sf), (vu, ru, su) = outs[True], outs[False]
    start, a, b = flat(pre_vars.params), flat(vf.params), flat(vu.params)
    update = float(np.linalg.norm(b - start))
    rel = float(np.linalg.norm(a - b)) / update
    d_acc = abs(rf["global_acc"] - ru["global_acc"])
    d_bd = abs(rf["backdoor_acc"] - ru["backdoor_acc"])
    emit(phase="fused_vs_unfused", epoch=first_attack,
         update_l2=update, rel_l2_diff_of_update=rel,
         max_abs_param_diff=float(np.max(np.abs(a - b))),
         global_acc=[rf["global_acc"], ru["global_acc"]],
         backdoor_acc=[rf["backdoor_acc"], ru["backdoor_acc"]],
         seconds_with_compile=[sf, su],
         tolerance={"rel_l2": FUSED_UPDATE_RTOL, "acc_points": FUSED_ACC_TOL},
         cache=cache.snapshot())
    check(update > 0 and math.isfinite(rel), "the round applied no update")
    check(rel <= FUSED_UPDATE_RTOL and d_acc <= FUSED_ACC_TOL
          and d_bd <= FUSED_ACC_TOL,
          f"fused and unfused rounds disagree: rel {rel}, acc {d_acc}, "
          f"backdoor {d_bd}")
    # last, so that a model still at chance does not hide the later phases
    if on_tpu():
        check(pretrain_acc >= PRETRAIN_MIN_ACC, "pretrain main-task accuracy "
              f"{pretrain_acc:.2f} < {PRETRAIN_MIN_ACC}")
    shutil.rmtree(work / "saved_models")  # keep chiprun_out small


def four_chips(work: Path, rehearse: bool, cache: CacheEvents) -> None:
    """The `clients` mesh over every device against one device: the mesh
    trains a model from the seed, then both run the same rounds from that
    model with the same seed, in one process."""
    import dba_mod_tpu.parallel.mesh as mesh_mod
    from dba_mod_tpu.config import Params
    from dba_mod_tpu.fl.experiment import Experiment
    devices = jax.devices()
    warm_rounds = REHEARSAL_MESH_WARM_ROUNDS if rehearse else MESH_WARM_ROUNDS
    first, last = warm_rounds + 1, warm_rounds + MESH_ROUNDS
    cfg_path = smoke_config(work, rehearse, first_attack_epoch=last - 1)

    def experiment(num_devices: int):
        p = Params.from_yaml(cfg_path)
        p.raw.update(synthetic_data=True, resumed_model=False, epochs=last,
                     num_devices=num_devices)
        if rehearse:  # interpret-mode Pallas under GSPMD takes minutes a round
            p.raw.update(fused_updates="auto", fused_interpret=False)
        t0 = time.perf_counter()
        e = Experiment(p, save_results=False)
        return e, time.perf_counter() - t0

    def timed_rounds(e, epochs):
        secs, results = [], []
        for epoch in epochs:
            t0 = time.perf_counter()
            results.append(e.run_round(epoch))
            jax.block_until_ready(e.global_vars)
            secs.append(time.perf_counter() - t0)
        return secs, results

    # ---- the mesh trains the common start state (clean rounds)
    e, build = experiment(-1)
    secs, results = timed_rounds(e, range(1, warm_rounds + 1))
    start = jax.device_get(e.global_vars)
    emit(phase="four_chips/mesh_warm", experiment_build_s=build,
         first_round_s_with_compile=secs[0], steady_round_s=secs[1:],
         steady_round_s_median=float(np.median(secs[1:])),
         global_acc=[r["global_acc"] for r in results],
         memory=memory(devices), cache=cache.snapshot())
    if on_tpu():
        check(results[-1]["global_acc"] >= PRETRAIN_MIN_ACC,
              f"the mesh trained to {results[-1]['global_acc']:.2f} % only")
    del e

    # what the mesh run hands the round program, as placed by the program
    placed = []
    real_shard = mesh_mod.shard_round_inputs

    def recording_shard(*a, **kw):
        out = real_shard(*a, **kw)
        placed.append(out)
        return out

    mesh_mod.shard_round_inputs = recording_shard

    runs, spread = {}, {}
    for name, nd in (("one_device", 0), ("mesh", -1)):
        e, build = experiment(nd)
        e.global_vars = (mesh_mod.replicate_for_mesh(e.mesh, start)
                         if e.mesh is not None
                         else jax.tree_util.tree_map(jnp.asarray, start))
        params_after = []
        secs, results = [], []
        for epoch in range(first, last + 1):
            s1, r1 = timed_rounds(e, [epoch])
            secs += s1
            results += r1
            params_after.append(flat(jax.device_get(e.global_vars.params)))
        if on_tpu():
            check_engine_is_the_chips(e, fused=(nd == 0))
        if nd == -1:
            check(e.mesh is not None
                  and e.mesh.devices.size == len(devices),
                  "the mesh does not span every device")
            spread["global_vars_replicated_on"] = sorted(
                {s.device.id for l in jax.tree_util.tree_leaves(e.global_vars)
                 for s in l.addressable_shards})
        runs[name] = (params_after, results)
        emit(phase=f"four_chips/{name}", experiment_build_s=build,
             round_s=secs, global_acc=[r["global_acc"] for r in results],
             backdoor_acc=[r["backdoor_acc"] for r in results],
             agents=[r["agents"] for r in results],
             memory=memory(devices), cache=cache.snapshot())
        del e

    # the stacked client arrays are really spread: every round's task /
    # plan arrays have one shard per device, each a 1/len(devices) slice of
    # the clients axis, and every device holds memory
    check(len(placed) == MESH_ROUNDS, "the mesh run placed no round inputs")
    tasks_seq, idx_seq, mask_seq, ns = placed[-1]
    for label, arr, axis in (("idx_seq", idx_seq, 1), ("mask_seq", mask_seq, 1),
                             ("num_samples", ns, 0),
                             ("tasks.lr_row", tasks_seq.lr_row, 1)):
        shards = arr.addressable_shards
        ids = sorted(s.device.id for s in shards)
        check(ids == sorted(d.id for d in devices),
              f"{label} sits on devices {ids}, not on all of them")
        check(all(s.data.shape[axis] * len(devices) == arr.shape[axis]
                  for s in shards), f"{label} is not split on the clients axis")
        spread[label] = {"shape": list(arr.shape),
                         "shard_shape": list(shards[0].data.shape),
                         "devices": ids}
    mem = memory(devices)
    if on_tpu():
        check(all((m["peak_bytes_in_use"] or 0) > 0 for m in mem),
              f"a device reports no memory in use: {mem}")
    (p1, r1), (p4, r4) = runs["one_device"], runs["mesh"]
    check([r["agents"] for r in r1] == [r["agents"] for r in r4],
          "the two runs selected different clients")
    p0 = flat(start.params)
    rel = [float(np.linalg.norm(b - a) / np.linalg.norm(a - p0))
           for a, b in zip(p1, p4)]
    d_par = [float(np.max(np.abs(b - a))) for a, b in zip(p1, p4)]
    d_acc = [abs(a["global_acc"] - b["global_acc"]) for a, b in zip(r1, r4)]
    d_bd = [abs(a["backdoor_acc"] - b["backdoor_acc"])
            for a, b in zip(r1, r4)]
    emit(phase="four_chips/compare", epochs=[first, last],
         poisoned_epoch=last, spread=spread,
         update_l2_by_round=[float(np.linalg.norm(a - p0)) for a in p1],
         rel_l2_diff_of_update_by_round=rel,
         max_abs_param_diff_by_round=d_par,
         global_acc_diff_by_round=d_acc, backdoor_acc_diff_by_round=d_bd,
         envelope={"clean_rounds_rel_l2": MESH_UPDATE_RTOL,
                   "clean_rounds_acc_points": MESH_ACC_TOL})
    check(all(math.isfinite(r["global_acc"]) and math.isfinite(
        r["backdoor_acc"]) for r in r1 + r4) and all(map(math.isfinite, rel)),
        "non-finite accuracy or parameters")
    clean = MESH_ROUNDS - 1
    if on_tpu():  # a rehearsal's start state is not a trained model
        check(max(rel[:clean]) <= MESH_UPDATE_RTOL
              and max(d_acc[:clean] + d_bd[:clean]) <= MESH_ACC_TOL,
              f"mesh and single-device clean rounds disagree: rel {rel}, "
              f"acc {d_acc}, backdoor {d_bd}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    emit(phase="device", device=device, rehearsal=args.rehearse)
    if not args.rehearse:
        check(dev.platform == "tpu",
              f"no TPU: jax.devices()[0].platform == {dev.platform!r}")
    check(device["count"] >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX sees "
          f"{device['count']}")

    from dba_mod_tpu.utils.compile_cache import enable_compile_cache
    cache = CacheEvents(enable_compile_cache())
    work = OUT / ("chip_smoke" if args.chips == 1 else "chip_smoke_4chips")
    if work.exists():
        shutil.rmtree(work)
    work.mkdir(parents=True)
    emit(phase="cache", **cache.snapshot())

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(work, args.rehearse, cache)
    else:
        one_chip(work, args.rehearse, cache)
    emit(phase="done", total_s=time.perf_counter() - t0,
         cache=cache.snapshot())
    if args.rehearse:
        emit(ok=False, rehearsal=True, device=device)
        return 3
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
