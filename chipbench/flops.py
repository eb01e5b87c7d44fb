"""Operations the forward and backward passes of a configuration's model need
per sample — XLA's cost analysis of the plain reference, made by the
configuration's family (the count `bench.py::model_flops` takes of the
program's model, copied so that the yardstick does not move with the
program). Nothing reads it yet: it is kept
for `train_mfu_pct` (PERF.md, Open questions), with `peaks.json`.

    python -m chipbench.flops <configuration>
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def peak(device_kind: str) -> dict:
    peaks = json.loads((HERE / "peaks.json").read_text())["peaks"]
    if device_kind not in peaks:
        raise KeyError(f"chipbench: no peaks for device kind {device_kind!r}")
    return peaks[device_kind]


def model_flops(config: dict, batch: int = 64) -> dict:
    """{'forward': flops per sample, 'train_step': fwd + bwd per sample}, by
    the configuration's family on its plain reference."""
    from chipbench import families
    return families.of(config).model_flops(config["model"], batch)


if __name__ == "__main__":
    cfg = json.loads((HERE / "configs" / f"{sys.argv[1]}.json").read_text())
    print(json.dumps(model_flops(cfg)))
