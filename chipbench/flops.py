"""Operations the forward and backward passes of a configuration's model need
per sample — XLA's cost analysis of the plain reference (the count
`bench.py::model_flops` takes of the program's model, copied so that the
yardstick does not move with the program). Nothing reads it yet: it is kept
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
    """{'forward': flops per sample, 'train_step': fwd + bwd per sample}."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import resnet18 as ref
    model = config["model"]
    variant, classes = model["variant"], model["num_classes"]
    state = jax.eval_shape(lambda: ref.init_weights(0, variant, classes))
    x = jax.ShapeDtypeStruct((batch, *model["image"]), jnp.float32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)

    def fwd(s, x):
        return ref.forward(s, x, variant, False)[0]

    def train(s, x, y):
        w = {k: v for k, v in s.items() if not ref.is_stat(k)}
        st = {k: v for k, v in s.items() if ref.is_stat(k)}
        return jax.grad(lambda w: jnp.mean(ref.nll(
            ref.forward({**w, **st}, x, variant, True)[0], y)))(w)

    def count(fn, *args):
        cost = jax.jit(fn).lower(*args).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        return float(cost["flops"]) / batch

    return {"forward": count(fwd, state, x), "train_step": count(train, state, x, y)}


if __name__ == "__main__":
    cfg = json.loads((HERE / "configs" / f"{sys.argv[1]}.json").read_text())
    print(json.dumps(model_flops(cfg)))
