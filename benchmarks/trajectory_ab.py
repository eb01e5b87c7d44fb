"""Converged-regime attack-trajectory A/B (VERDICT r4 ask #1).

The reference's de-facto validation is its paper curves: resume a PRETRAINED
model and watch backdoor injection + persistence/decay over tens of rounds
(/root/reference/main.py:135-231; single-shot schedule
utils/cifar_params.yaml:48-52 resumes epoch 200 and poisons at rounds
203/205/207/209; multi-shot utils/mnist_params.yaml:48-60 poisons every
round with baseline=true, eta=1). The r4 parity matrix proved semantic
agreement 1-4 rounds from near-init — chance-level models. This harness
exercises the ±1% north star where it is hard: a CONVERGED model, the
reference's own attack schedules, and ≥30 subsequent clean rounds of
backdoor decay under each defense.

Method: pretrain the flax engine to stable accuracy on the fabricated
(learnable) dataset; seed BOTH frameworks with the identical converged state
via the exact state converters; drive both with shared batch plans
(benchmarks/parity_ab.py machinery) through the attack schedule; record
per-round clean/backdoor accuracy curves and their gaps. Default platforms:
flax side on the REAL TPU at jax_default_matmul_precision=highest
(f32-accurate convs — the production engine under test), torch twin on CPU
f32; `--platform cpu` forces the all-CPU form that isolates semantics from
backend precision entirely (the identical-state PARITY_AB.md sections
already pin that on CPU; it costs ~3-4× more wall-clock on this box).

Scaled-down analog of the reference configs (same hyper-parameters, smaller
population): 30 participants over 4,000 fabricated CIFAR images (Dirichlet
α=0.5), 10/round, eta=0.1, scale_weights_poison=100 — the same full
model-replacement strength as the reference (eta·scale/no_models = 1) —
with adversaries on nearest-mean shards (pick_adversaries).

Usage: python -m benchmarks.trajectory_ab   (~1.5 h: torch-twin CPU rounds
dominate; writes the `## Trajectory` section of PARITY_AB.md between
markers, incrementally per lane, plus TRAJECTORY_AB.json).
tests/test_trajectory_ab.py runs compressed MNIST lanes.
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional

import numpy as np

from benchmarks.parity_ab import (CONVERTERS, TorchFL, build_round_plans,
                                  _fedavg_apply)  # noqa: F401 (re-export)

BEGIN_MARK = "<!-- TRAJECTORY:BEGIN -->"
END_MARK = "<!-- TRAJECTORY:END -->"

# Reference cifar_params.yaml hyper block, population scaled 100→30 and
# batch 64→32 / 50k→4k images (the torch twin runs f32 on this box's ~1
# CPU core — the full-size analog costs many hours; the scaled one
# preserves the schedule structure, the Dirichlet non-IID partition, and
# the exact model-replacement strength eta·scale/no_models = 1).
# Adversaries are chosen as the 4 nearest-mean shards (pick_adversaries)
# — the reference's own adversaries hold near-mean shards too
# (cifar_params.yaml:33 notes "training img num : 526 - 527 - 496 - 546");
# a tail-of-the-Dirichlet adversary with a handful of samples makes the
# poison client's 6-epoch local training degenerate (measured: a
# 14-sample adversary collapses to a constant predictor on both
# frameworks, in different basins — no science to compare).
# Single-shot schedule offsets from the resume epoch: +3/+5/+7/+9
# (cifar_params.yaml:48-52 with resume at 200).
CIFAR_TRAJ = dict(
    type="cifar", test_batch_size=64, lr=0.1, poison_lr=0.05, momentum=0.9,
    decay=0.0005, batch_size=32, internal_epochs=2, internal_poison_epochs=6,
    poisoning_per_batch=5, aggr_epoch_interval=1,
    aggregation_methods="mean", geom_median_maxiter=10, fg_use_memory=True,
    no_models=10, number_of_total_participants=30, is_random_namelist=True,
    is_random_adversary=False, is_poison=True, baseline=False,
    scale_weights_poison=100, eta=0.1, sampling_dirichlet=True,
    dirichlet_alpha=0.5, poison_label_swap=2,
    adversary_list=[17, 3, 7, 11],  # replaced by pick_adversaries in main
    centralized_test_trigger=True,
    trigger_num=4, alpha_loss=1.0, epochs=300,
    synthetic_data=True, synthetic_train_size=4000, synthetic_test_size=800,
    synthetic_noise_std=90.0,  # plateau below saturation (real-data regime)
    random_seed=11, local_eval=False,
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2], [0, 3], [0, 4], [0, 5]],
       "1_poison_pattern": [[0, 9], [0, 10], [0, 11], [0, 12], [0, 13],
                            [0, 14]],
       "2_poison_pattern": [[4, 0], [4, 1], [4, 2], [4, 3], [4, 4], [4, 5]],
       "3_poison_pattern": [[4, 9], [4, 10], [4, 11], [4, 12], [4, 13],
                            [4, 14]]})

# Reference mnist_params.yaml multi-shot block: baseline=true, eta=1,
# every adversary poisons every round of the ramp (mnist_params.yaml:30-31
# comments pin exactly this switch)
MNIST_TRAJ = dict(
    type="mnist", test_batch_size=64, lr=0.1, poison_lr=0.05,
    poison_step_lr=True, momentum=0.9, decay=0.0005, batch_size=64,
    internal_epochs=1, internal_poison_epochs=10, poisoning_per_batch=20,
    aggr_epoch_interval=1, aggregation_methods="mean",
    geom_median_maxiter=10, fg_use_memory=True, no_models=10,
    number_of_total_participants=30, is_random_namelist=True,
    is_random_adversary=False, is_poison=True, baseline=True,
    scale_weights_poison=100, eta=1.0, sampling_dirichlet=True,
    dirichlet_alpha=0.5, poison_label_swap=2,
    adversary_list=[7, 3, 1, 4], centralized_test_trigger=True,
    trigger_num=4, alpha_loss=1.0, epochs=300,
    synthetic_data=True, synthetic_train_size=1500, synthetic_test_size=600,
    synthetic_noise_std=80.0,  # plateau below saturation (real-data regime)
    random_seed=13, local_eval=False,
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2], [0, 3]],
       "1_poison_pattern": [[0, 6], [0, 7], [0, 8], [0, 9]],
       "2_poison_pattern": [[3, 0], [3, 1], [3, 2], [3, 3]],
       "3_poison_pattern": [[3, 6], [3, 7], [3, 8], [3, 9]]})


def pick_adversaries(overrides: dict, k: int = 4) -> List[int]:
    """The k clients whose Dirichlet shard sizes are nearest the mean —
    the reference's own adversary regime (its cifar adversaries hold
    526/527/496/546 of a 500-sample mean, cifar_params.yaml:33). Uses the
    exact partition the experiment will build (same seed/RNG recipe)."""
    import random as pyrandom

    from dba_mod_tpu.config import Params
    from dba_mod_tpu.data.partition import sample_dirichlet_indices
    from dba_mod_tpu.data.datasets import synthetic_image_dataset

    p = Params.from_dict(overrides)
    seed = int(p.get("random_seed", 1))
    data = synthetic_image_dataset(
        p.type, int(p.get("synthetic_train_size", 0)),
        int(p.get("synthetic_test_size", 0)), seed=seed,
        noise_std=float(p.get("synthetic_noise_std", 25.0)))
    idx = sample_dirichlet_indices(
        data.train_labels, int(p["number_of_total_participants"]),
        float(p["dirichlet_alpha"]), py_rng=pyrandom.Random(seed),
        np_rng=np.random.RandomState(seed))
    mean = np.mean([len(v) for v in idx.values()])
    return sorted(sorted(idx, key=lambda n: abs(len(idx[n]) - mean))[:k])


def single_shot_epochs(resume_epoch: int) -> Dict[str, List[int]]:
    """The cifar_params.yaml:48-52 schedule relative to the resume epoch."""
    return {f"{i}_poison_epochs": [resume_epoch + o]
            for i, o in enumerate((3, 5, 7, 9))}


def multi_shot_epochs(start: int, end: int) -> Dict[str, List[int]]:
    """The mnist_params.yaml:53-60 ramp: every adversary, every round."""
    return {f"{i}_poison_epochs": list(range(start, end + 1))
            for i in range(4)}


def pretrain(overrides: dict, rounds: int, **pretrain_overrides):
    """Clean FedAvg pretraining on the flax engine — the `pretrain`
    subcommand's analog (replaces the reference's Google-Drive artifacts).
    Returns (converged ModelVars, per-round clean accuracy).
    `pretrain_overrides` tune the clean phase only (e.g. the BN-free
    MnistNet needs more local work per round: internal_epochs=4, eta=1)."""
    from dba_mod_tpu.config import Params
    from dba_mod_tpu.fl.experiment import Experiment

    cfg = dict(overrides, is_poison=False, aggregation_methods="mean",
               adversary_list=[])
    cfg.update(dict(eta=0.8), **pretrain_overrides)
    exp = Experiment(Params.from_dict(cfg), save_results=False)
    accs = []
    for ep in range(1, rounds + 1):
        accs.append(exp.run_round(ep)["global_acc"])
    return exp.global_vars, accs


def run_trajectory(overrides: dict, init_vars, start_epoch: int,
                   end_epoch: int, label: str = "") -> dict:
    """Drive both frameworks from the shared `init_vars` state through
    epochs [start_epoch, end_epoch]; returns per-round curves + gaps."""
    import jax
    import jax.numpy as jnp

    from dba_mod_tpu.config import Params
    from dba_mod_tpu.fl.experiment import Experiment
    from dba_mod_tpu.fl.rounds import nbt_client_deltas
    from dba_mod_tpu.fl.selection import select_agents
    from dba_mod_tpu.models import ModelVars
    from dba_mod_tpu.ops.triggers import build_pixel_pattern_bank

    params = Params.from_dict(overrides)
    exp = Experiment(params, save_results=False)
    exp.global_vars = ModelVars(
        params=jax.tree_util.tree_map(jnp.asarray, init_vars.params),
        batch_stats=jax.tree_util.tree_map(jnp.asarray,
                                           init_vars.batch_stats))
    ctor, to_torch = CONVERTERS[params.type]
    data = exp.image_data
    h, w = data.train_images.shape[1:3]
    bank = build_pixel_pattern_bank(params, h, w)
    tfl = TorchFL(params.raw, ctor, to_torch(exp.global_vars),
                  data.train_images, data.train_labels, data.test_images,
                  data.test_labels, bank)

    rounds = []
    for epoch in range(start_epoch, end_epoch + 1):
        agent_names, adv_names = select_agents(
            params, epoch, exp.participants, exp.benign_names,
            exp.select_rng)
        tasks_list, idx_np, mask_np, num_samples = build_round_plans(
            exp, params, agent_names, [epoch])
        C = len(agent_names)
        tasks_seq = jax.tree_util.tree_map(
            lambda *ls: jnp.asarray(np.stack(ls)), *tasks_list)
        lane = jnp.arange(C, dtype=jnp.int32)
        exp.rng_key, round_key = jax.random.split(exp.rng_key)
        rng_t, rng_a = jax.random.split(round_key)
        train = exp.engine.train_fn(exp.global_vars, tasks_seq,
                                    jnp.asarray(idx_np),
                                    jnp.asarray(mask_np), lane, rng_t)
        agg = exp.engine.aggregate_fn(
            exp.global_vars, exp.fg_state, train.deltas, train.fg_grads,
            train.fg_feature, jnp.asarray(tasks_list[0].participant_id),
            jnp.asarray(num_samples), rng_a,
            nbt_client_deltas(jnp.asarray(mask_np),
                              jnp.asarray(np.stack(
                                  [t.scale for t in tasks_list]))))
        exp.global_vars = agg.new_vars
        exp.fg_state = agg.new_fg_state
        g = jax.device_get(exp.engine.global_evals_fn(agg.new_vars))

        tfl.run_round([epoch], agent_names, idx_np, mask_np,
                      num_samples=[int(n) for n in num_samples])
        t_clean, t_bd = tfl.clean_acc(), tfl.backdoor_acc()
        row = {"epoch": epoch,
               "poisoning": [str(a) for a in adv_names],
               "jax_clean": float(g.clean.acc), "torch_clean": t_clean,
               "jax_backdoor": float(g.poison.acc), "torch_backdoor": t_bd,
               "clean_gap": abs(float(g.clean.acc) - t_clean),
               "backdoor_gap": abs(float(g.poison.acc) - t_bd)}
        rounds.append(row)
        print(f"[{label}] epoch {epoch}: clean {row['jax_clean']:.2f}/"
              f"{row['torch_clean']:.2f} backdoor {row['jax_backdoor']:.2f}/"
              f"{row['torch_backdoor']:.2f}"
              + (f" POISON {row['poisoning']}" if adv_names else ""),
              flush=True)
    return {"label": label, "rounds": rounds}


def summarize(traj: dict) -> dict:
    """Whole-run + phase-wise gap statistics. Phases: `pre` = rounds before
    the first poisoning round (the converged steady state), `tail` = the
    last 10 rounds (post-decay steady state). The transient between them —
    scale-100 model replacement and the recovery from it — is a knife-edge
    regime where ANY two runs separate chaotically (the reference's own
    poison LR schedule is flat there: its float milestones 0.2·6/0.8·6
    never fire, ops/sgd.py::_milestone_hits), so per-round gaps inside the
    transient measure the attack's violence, not framework disagreement."""
    rs = traj["rounds"]
    poison_rounds = [i for i, r in enumerate(rs) if r["poisoning"]]
    pre = rs[:poison_rounds[0]] if poison_rounds else rs
    # tail = post-attack rounds only (up to the last 10 AFTER the final
    # poison round) — never mid-attack rounds mislabeled as steady state
    after = rs[poison_rounds[-1] + 1:] if poison_rounds else rs
    tail = after[-10:]

    def gaps(sub, key):
        vals = [r[key] for r in sub]
        if not vals:
            return (float("nan"), float("nan"))  # no such phase in this run
        return float(np.mean(vals)), float(np.max(vals))
    pre_c = gaps(pre, "clean_gap")
    pre_b = gaps(pre, "backdoor_gap")
    tail_c = gaps(tail, "clean_gap")
    tail_b = gaps(tail, "backdoor_gap")
    return {
        "label": traj["label"],
        "n_rounds": len(rs),
        "mean_clean_gap": float(np.mean([r["clean_gap"] for r in rs])),
        "max_clean_gap": float(np.max([r["clean_gap"] for r in rs])),
        "mean_backdoor_gap": float(np.mean([r["backdoor_gap"] for r in rs])),
        "max_backdoor_gap": float(np.max([r["backdoor_gap"] for r in rs])),
        "pre_rounds": len(pre), "tail_rounds": len(tail),
        "pre_mean_clean_gap": pre_c[0], "pre_max_clean_gap": pre_c[1],
        "pre_mean_backdoor_gap": pre_b[0], "pre_max_backdoor_gap": pre_b[1],
        "tail_mean_clean_gap": tail_c[0], "tail_max_clean_gap": tail_c[1],
        "tail_mean_backdoor_gap": tail_b[0],
        "tail_max_backdoor_gap": tail_b[1],
        "final_clean_gap": rs[-1]["clean_gap"],
        "final_backdoor_gap": rs[-1]["backdoor_gap"],
        "jax_peak_backdoor": float(np.max([r["jax_backdoor"] for r in rs])),
        "torch_peak_backdoor": float(
            np.max([r["torch_backdoor"] for r in rs])),
        "jax_final_backdoor": rs[-1]["jax_backdoor"],
        "torch_final_backdoor": rs[-1]["torch_backdoor"],
        "jax_final_clean": rs[-1]["jax_clean"],
        "torch_final_clean": rs[-1]["torch_clean"],
    }


def _fmt_traj(traj: dict, summary: dict) -> str:
    lines = [f"### {traj['label']}", "",
             "| epoch | poisoning | clean acc (jax / torch) | gap | "
             "backdoor acc (jax / torch) | gap |", "|---|---|---|---|---|---|"]
    for r in traj["rounds"]:
        lines.append(
            f"| {r['epoch']} | {','.join(r['poisoning']) or '—'} | "
            f"{r['jax_clean']:.2f} / {r['torch_clean']:.2f} | "
            f"{r['clean_gap']:.2f} | "
            f"{r['jax_backdoor']:.2f} / {r['torch_backdoor']:.2f} | "
            f"{r['backdoor_gap']:.2f} |")
    pre_txt = ("no pre-attack rounds in this run"
               if summary["pre_rounds"] == 0 else
               f"pre-attack ({summary['pre_rounds']} rounds) mean/max clean "
               f"{summary['pre_mean_clean_gap']:.3f}/"
               f"{summary['pre_max_clean_gap']:.3f}")
    tail_txt = ("no post-attack rounds in this run"
                if summary["tail_rounds"] == 0 else
                f"post-attack tail ({summary['tail_rounds']} rounds) "
                f"mean/max clean {summary['tail_mean_clean_gap']:.3f}/"
                f"{summary['tail_max_clean_gap']:.3f}, backdoor "
                f"{summary['tail_mean_backdoor_gap']:.3f}/"
                f"{summary['tail_max_backdoor_gap']:.3f}")
    lines += ["",
              f"Gaps (pct-points): {pre_txt}; {tail_txt}; whole-run mean "
              f"clean {summary['mean_clean_gap']:.3f} / backdoor "
              f"{summary['mean_backdoor_gap']:.3f} (max "
              f"{summary['max_clean_gap']:.3f}/"
              f"{summary['max_backdoor_gap']:.3f}). Peak backdoor "
              f"{summary['jax_peak_backdoor']:.2f} (jax) / "
              f"{summary['torch_peak_backdoor']:.2f} (torch); final "
              f"{summary['jax_final_backdoor']:.2f} / "
              f"{summary['torch_final_backdoor']:.2f}; final clean "
              f"{summary['jax_final_clean']:.2f} / "
              f"{summary['torch_final_clean']:.2f}.", ""]
    return "\n".join(lines)


def extract_trajectory_section(text: str) -> Optional[str]:
    """The marker-delimited section body, or None when absent/malformed.
    Single owner of the marker format — parity_ab.main() uses this too."""
    if BEGIN_MARK in text and END_MARK in text.split(BEGIN_MARK, 1)[1]:
        return text.split(BEGIN_MARK, 1)[1].split(END_MARK, 1)[0]
    return None


def splice_trajectory_section(md_path: str, section_body: str) -> None:
    """Insert/replace the marker-delimited trajectory section of
    PARITY_AB.md (parity_ab.main preserves it when regenerating)."""
    try:
        text = open(md_path).read()
    except FileNotFoundError:
        text = ""
    if extract_trajectory_section(text) is not None:
        head = text.split(BEGIN_MARK, 1)[0]
        tail = text.split(END_MARK, 1)[1]
    else:
        head, tail = (text if text.endswith("\n") or not text
                      else text + "\n"), ""
    with open(md_path, "w") as f:
        f.write(head + BEGIN_MARK + "\n" + section_body + END_MARK + tail)


def main(argv=None) -> int:
    import argparse
    import os
    ap = argparse.ArgumentParser()
    # The flax side runs on the real TPU by default — the production
    # engine, at jax_default_matmul_precision=highest so its f32 convs
    # match CPU-f32 accuracy (the torch twin is CPU f32 either way; the
    # identical-state sections above already isolate pure semantics on
    # CPU-vs-CPU). --platform cpu forces the all-CPU form: ~3-4× more
    # wall-clock per section on this box's ~1-core quota.
    ap.add_argument("--platform", choices=["tpu", "cpu"], default="tpu")
    args = ap.parse_args(argv)
    import jax
    if args.platform == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
    else:
        jax.config.update("jax_default_matmul_precision", "highest")
    from dba_mod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    sections, summaries = [], []
    pre_note = {}

    def flush_artifacts():
        """Incremental splice — a killed run still leaves every completed
        lane in the artifact."""
        body = (
            "\n## Trajectory (converged-regime attack efficacy)\n\n"
            "Generated by `python -m benchmarks.trajectory_ab` (flax side "
            f"on backend `{jax.default_backend()}`, matmul precision "
            "HIGHEST — f32-accurate convs; torch twin on CPU f32). Both "
            "frameworks resume from the SAME converged pretrained state "
            "(flax engine pretrain on the fabricated dataset at "
            "synthetic_noise_std=90/80; measured pretrain clean acc "
            f"{pre_note.get('cifar', float('nan')):.1f}% CIFAR / "
            f"{pre_note.get('mnist', float('nan')):.1f}% MNIST) and "
            "replay the reference's own attack schedules with shared "
            "batch plans: the cifar_params.yaml:48-52 single-shot DBA "
            "under all three defenses, and the mnist_params.yaml "
            "multi-shot ramp. Gaps are |jax − torch| in accuracy "
            "percentage points — read each lane's own phase line; no "
            "blanket claim is made here. Interpretation key: each "
            "framework integrates its own f32 rounding, so agreement is "
            "expected (and measured) in steady regimes, while the "
            "scale-100 replacement transient — 6 FLAT-LR poison epochs "
            "on a converged model (the reference's own float-milestone "
            "quirk: MultiStepLR milestones 0.2·6/0.8·6 never fire, "
            "ops/sgd.py::_milestone_hits) followed by ×100 amplification "
            "— is a measured knife-edge: single-bit differences flip "
            "which basin the poison client lands in, so backdoor "
            "persistence TIMING can diverge qualitatively there, exactly "
            "as two runs of the reference itself would. The "
            "identical-state sections above pin the per-round semantics "
            "tightly; these curves pin the phenomena (attack lands / "
            "decays / is blocked) and the steady-phase gaps.\n\n"
            + "\n".join(sections))
        splice_trajectory_section("PARITY_AB.md", body)
        with open("TRAJECTORY_AB.json", "w") as f:
            json.dump({"summaries": summaries}, f, indent=1)

    # --- CIFAR single-shot, all three defenses from one pretrain ---
    E0 = 25
    advs = pick_adversaries(CIFAR_TRAJ)
    base_cfg = dict(CIFAR_TRAJ, adversary_list=advs)
    print(f"adversaries (nearest-mean shards): {advs}", flush=True)
    init_vars, pre_accs = pretrain(base_cfg, E0)
    pre_note["cifar"] = pre_accs[-1]
    print(f"pretrain: {E0} rounds, clean acc {pre_accs[-1]:.2f} "
          f"(trajectory: {[round(a, 1) for a in pre_accs[::5]]})", flush=True)
    for defense in ("mean", "geom_median", "foolsgold"):
        cfg = dict(base_cfg, aggregation_methods=defense,
                   **single_shot_epochs(E0))
        traj = run_trajectory(
            cfg, init_vars, E0 + 1, E0 + 40,
            label=f"cifar single-shot DBA + {defense} (resume@{E0}, poison "
                  f"@{E0+3}/{E0+5}/{E0+7}/{E0+9}, 31 clean rounds after)")
        s = summarize(traj)
        summaries.append(s)
        sections.append(_fmt_traj(traj, s))
        flush_artifacts()

    # --- MNIST multi-shot ramp (baseline=true, eta=1) ---
    M0 = 10
    madvs = pick_adversaries(MNIST_TRAJ)
    mnist_cfg = dict(MNIST_TRAJ, adversary_list=madvs)
    init_m, pre_m = pretrain(mnist_cfg, M0)
    pre_note["mnist"] = pre_m[-1]
    print(f"mnist pretrain: {M0} rounds, clean acc {pre_m[-1]:.2f} "
          f"advs {madvs}", flush=True)
    cfg = dict(mnist_cfg, **multi_shot_epochs(M0 + 1, M0 + 15))
    traj = run_trajectory(
        cfg, init_m, M0 + 1, M0 + 20,
        label=f"mnist multi-shot ramp (baseline, eta=1; poison rounds "
              f"{M0+1}-{M0+15}, then 5 clean)")
    s = summarize(traj)
    summaries.append(s)
    sections.append(_fmt_traj(traj, s))
    flush_artifacts()
    print(json.dumps({"summaries": summaries}, indent=1))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
