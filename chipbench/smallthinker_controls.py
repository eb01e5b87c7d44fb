"""The three controls the limits of `smallthinker_21b_a3b_dba` under
`long_row_phrase_rounds` are held to, in one process on the chip: the cell's
two check rounds through a program that is WRONG in one stated way, against
the plain reference of the configuration as published. Each has to fail at
least one of the cell's limits (`chipbench/limits/`), or the check could not
tell that program from the right one:

- `bfloat16`: the program computes in bfloat16 (`compute_dtype`), the
  precision below the one the configuration states;
- `causal_windows`: every window layer runs causal (`sliding_window_layout`
  all 0): the 512 queries a check feed holds past the window's edge see the
  row's first keys;
- `rope_everywhere`: the global layer rotates q and k like the window layers
  (`rope_layout` all 1) where the published layer carries no position.

    python -m chipbench.smallthinker_controls --run bfloat16=21,22 \
        --run causal_windows=23 --run rope_everywhere=24 [--run sound=11,12]

`sound` is the program as it is (every number has to lie inside its limit).
Each `--run` is its own program (one build and one compile); the readings are
`chipbench.calibrate.readings`'s, at the device's default precision. One JSON
line a (run, seed), and a last line that says, for each run and seed, the
numbers over their limits; the exit code is 0 where every control fails on
every seed and every sound seed passes.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

from chipbench import calibrate, check
from chipbench import run as harness

CELL = "smallthinker_long_row_attack"


def overrides_of(config: dict) -> dict:
    """control -> the parameters laid over the cell's."""
    arch = config["params"]["smallthinker"]
    every = lambda key, value: {"smallthinker": {
        **arch, key: [value] * len(arch[key])}}
    return {"bfloat16": {"compute_dtype": "bfloat16"},
            "causal_windows": every("sliding_window_layout", 0),
            "rope_everywhere": every("rope_layout", 1)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run", action="append", required=True,
                    metavar="LABEL=SEED,SEED", help="sound, or a control")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("chipbench.smallthinker_controls: no TPU")
    from chipbench import program
    program.enable_cache()
    _, cell, config, _ = harness.load_cell(CELL)
    limits = check.limits(cell["config"], cell["traffic"])
    changes, summary, ok = {**overrides_of(config), "sound": None}, {}, True
    for run in args.run:
        label, _, seeds = run.partition("=")
        rows = calibrate.readings(CELL, [int(s) for s in seeds.split(",")],
                                  changes[label], label, ("default",))
        gc.collect()
        over = [{n: r["default"][n] for n in limits
                 if not r["default"][n] <= limits[n]} for r in rows]
        summary[label] = over
        ok = ok and (not any(over) if label == "sound" else all(over))
    print(json.dumps({"workload": CELL, "limits": limits,
                      "over_their_limits": summary, "as_expected": ok}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
