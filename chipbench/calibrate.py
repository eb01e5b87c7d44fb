"""Readings the limits under `limits/` are set from, in one process on the chip
(sound seeds and control seeds in separate processes where the host is short of
memory: two builds of the Tiny-ImageNet population and two 4 GB executables
met the machine's 40 GiB in PR 23):

    python -m chipbench.calibrate --workload <cell> --seeds 11,12,... \
        --control-seeds 21,22,23 [--benchmark-file <file>]

For each seed, the cell's two check rounds through the program's compiled
round program against the plain reference at the device's default matmul
precision (the number held to a limit) and at `highest` (how close the system
comes to exact float32). Then the control: the same program with its own
lower-precision path switched on (`compute_dtype: bfloat16`), on
`--control-seeds`. No window is measured. Prints one JSON line per seed and a
last line with the largest sound and the smallest control reading of each number.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys

from chipbench import run as harness


def readings(cell_name, seeds, overrides, label, precisions,
             benchmark_file=None):
    import jax
    from chipbench import families, program
    bench, cell, config, traffic = harness.load_cell(cell_name, benchmark_file)
    family = families.of(config)
    out_dir = harness.HERE / "_out" / f"calibrate.{cell_name}.{label}"
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    first = harness.FIRST_WINDOW_EPOCH
    params, raw = program.make_params(config, traffic, out_dir, first, None,
                                      overrides)
    exp, build_s = program.build_experiment(params)
    harness.emit(phase="build", label=label, seconds=build_s,
                 memory=harness.device_memory(jax.devices()[:1]))
    events = harness.CompileEvents()
    rows = []
    for seed in seeds:
        state0, checks = harness.seeded_check_rounds(
            exp, family, config, traffic, seed, first, events)
        row = {"label": label, "seed": seed}
        for precision in precisions:
            row[precision] = {r["number"]: r["value"] for r in harness.judge(
                family, raw, config["model"], state0,
                family.population_of(exp), checks, {}, precision, every=True)}
        harness.emit(**row, memory=harness.device_memory(jax.devices()[:1]))
        rows.append(row)
    shutil.rmtree(out_dir, ignore_errors=True)
    del exp
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--benchmark-file", default=None,
                    help="another file of BENCHMARK.json's form (a cell that "
                         "is none of the benchmark's)")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("chipbench.calibrate: no TPU")
    from chipbench import program
    program.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    sound = readings(args.workload, seeds, None, "sound",
                     ("default", "highest"), args.benchmark_file) if seeds else []
    ctrl = readings(args.workload, control, {"compute_dtype": "bfloat16"},
                    "control_bfloat16", ("default",),
                    args.benchmark_file) if control else []
    summary = {}
    for number in (sound or ctrl)[0]["default"]:
        summary[number] = {
            "sound_largest": max((r["default"][number] for r in sound), default=None),
            "sound_vs_highest_largest": max(
                (r["highest"][number] for r in sound), default=None),
            "control_smallest": min((r["default"][number] for r in ctrl),
                                    default=None)}
    print(json.dumps({"workload": args.workload, "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
