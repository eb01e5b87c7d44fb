"""The comparison that decides `correct`: what the window's own compiled round
program produced on the check feeds, against the plain reference.

The reference follows the same feed — the same rows of the population, the
same masks, learning rates and model-replacement scale — with weights the
benchmark made from `--seed`; the configuration's family runs it
(`chipbench/families/<family>.py::reference_round`). Each number compared has
a limit of its own in `chipbench/limits/<configuration>.<traffic>.json`, set
from the chip readings listed there and in PERF.md.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Callable, Dict

import numpy as np

HERE = Path(__file__).resolve().parent


def limits(config: str, traffic: str) -> Dict[str, float]:
    """The limits of one configuration under one traffic mix, with the chip
    readings they were set from: `limits/<configuration>.<traffic>.json`."""
    path = HERE / "limits" / f"{config}.{traffic}.json"
    return json.loads(path.read_text())["limits"]


def compare(state0, got_new, got, want,
            is_stat: Callable[[str], bool]) -> Dict[str, float]:
    """The numbers compared. `got`: the program's check round (host values),
    `got_new`: its new global state under the reference's names; `want`: the
    reference's round; `is_stat`: the family's rule for running statistics
    (a state without any gives no `stats_rel_l2`). U = new - old is the
    applied global update: after one real step it is -eta * lr * mean(first
    gradient as the optimizer gets it)."""
    names = [n for n in state0 if not is_stat(n)]
    stats = [n for n in state0 if is_stat(n)]

    def upd(new, keys):
        return {n: np.asarray(new[n], np.float64) - np.asarray(state0[n], np.float64)
                for n in keys}

    up, ur = upd(got_new, names), upd(want["new"], names)
    leaf_r = {n: float(np.linalg.norm(ur[n])) for n in names}
    floor = float(np.median(list(leaf_r.values())))
    norm_gap = max(abs(float(np.linalg.norm(up[n])) - leaf_r[n])
                   / max(leaf_r[n], floor) for n in names)

    def rel_l2(a, b, keys):
        num = np.sqrt(sum(np.sum(np.square(a[n] - b[n])) for n in keys))
        den = np.sqrt(sum(np.sum(np.square(b[n])) for n in keys))
        return float(num / den)

    numbers = {
        "loss_gap": float(np.max(np.abs(got["loss_sum"] - want["loss_sum"])
                                 / np.abs(want["loss_sum"]))),
        "update_norm_gap": float(norm_gap),
        "update_rel_l2": rel_l2(up, ur, names),
        "stats_rel_l2": rel_l2(upd(got_new, stats), upd(want["new"], stats),
                               stats) if stats else None,
        "delta_norm_gap": float(np.max(
            np.abs(got["delta_norms"] - want["delta_norms"])
            / want["delta_norms"])),
        "global_loss_gap": abs(got["global_loss"] - want["global_loss"])
        / abs(want["global_loss"]),
    }
    return {name: value for name, value in numbers.items() if value is not None}
