"""The per-layer readers of the `sdar_moe` cell (chipbench/sdar_layers.py,
chipbench/lfm2_layers.py and the six files under chipbench/metrics/ that call
them) on hand-made records: from a program without the scopes and counts (the
parent of the PR that brought them) every reader gives nothing and raises
nothing; from a traced run's records each gives the number its docstring
says."""
import importlib.util
import types
from pathlib import Path

import pytest

from chipbench import flops, sdar_layers
from chipbench.reference import sdar as ref
from tests.sdar_cases import ARCH

METRICS = Path(sdar_layers.__file__).parent / "metrics"
READERS = ("sdar_mixer_device_ms", "sdar_experts_device_ms",
           "sdar_noise_device_ms", "sdar_expert_load_max_over_mean",
           "sdar_masked_positions_pct", "sdar_client_step_mfu_pct")
TRAIN = "jit(round_fn)/phase/train/while/body/"
MS = 1e6   # the trace's clock is in nanoseconds


def reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def span(name, **counts):
    return types.SimpleNamespace(name=name, counts=counts or None)


def traced_ctx():
    """Three window rounds clocked, rounds 2 and 3 traced: 1 ms of noise, 4 ms
    of mixer (two operations that overlap by 1 ms), 1 ms of router, 2 ms of a
    conditional without a scope path and 1 ms of experts, and 2 ms outside
    `phase/train` that no reader may count."""
    dot, cond = "%fusion.1 = f32[8,8]{1,0} fusion(%a), kind=kOutput", (
        "%cond.7 = f32[64,64]{1,0} conditional(%p, %a, %b)")
    ops = [(dot, TRAIN + "noise/select_n", 0 * MS, 1 * MS),
           (dot, TRAIN + "layer_0/attn/mixer/dot", 1 * MS, 4 * MS),
           (dot, TRAIN + "transpose(jvp(layer_1))/attn/mixer/dot", 3 * MS, 5 * MS),
           (dot, TRAIN + "layer_1/moe/router/dot", 5 * MS, 6 * MS),
           (cond, "", 6 * MS, 8 * MS),      # the expert layer's two paths
           (dot, TRAIN + "layer_1/moe/experts/dot", 8 * MS, 9 * MS),
           (dot, "jit(round_fn)/phase/global_battery/noise/select_n", 10 * MS,
            11 * MS),
           (cond, "", 11 * MS, 12 * MS)]    # a battery's: no reader's
    plans = [span("round/plan", tokens_step=64, client_steps=c, block_length=4)
             for c in (8, 8, 24)]
    records = [span("round/record", expert_tokens_held=h, expert_tokens_max=m,
                    expert_tokens_mean=mean, positions_masked=masked,
                    positions_scored=scored)
               for h, m, mean, masked, scored in (
                   (500, 30, 15.0, 150, 256), (512, 24, 16.0, 180, 256),
                   (1600, 40, 16.0, 520, 768))]
    return {"spans": {"dispatch": [0.01, 0.01, 0.01]},
            "program_spans": plans + records,
            "traced": {"rounds": 2, "window_rounds": [2, 3]},
            "phases": {"scope_s": {"phase/train": 0.010}},
            "lfm2_ops": ops,
            "sdar_model": {"seq_len": 32, "arch": ARCH}}


def parent_ctx():
    """What the parent's program leaves in this cell's place: the streamed
    round's spans with the expert counts alone (its one token model's), a
    trace without the `noise` scope."""
    return {"spans": {"dispatch": [0.01, 0.01, 0.01]},
            "program_spans": [span("round/plan", steps_run=2)] * 3
            + [span("round/record")] * 3,
            "traced": {"rounds": 2, "window_rounds": [2, 3]},
            "phases": {"scope_s": {"phase/train": 0.010}},
            "lfm2_ops": [("%fusion.1 = f32[8]{0} fusion(%a)",
                          "jit(round_fn)/phase/train/while/body/conv", 0, MS)],
            "sdar_model": {"seq_len": 32, "arch": ARCH}}


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_scopes_and_counts_reads_as_nothing(name):
    assert reader(name).read(parent_ctx()) is None
    assert reader(name).read({"spans": {}, "program_spans": [], "traced": None,
                              "phases": None, "lfm2_ops": None}) is None


@pytest.mark.parametrize("name,want", [
    ("sdar_noise_device_ms", 1.0 / 2),
    ("sdar_mixer_device_ms", 4.0 / 2),   # the union, per traced round
    ("sdar_experts_device_ms", (1.0 + 2.0 + 1.0) / 2),
    ("sdar_expert_load_max_over_mean", (30 / 15 + 24 / 16 + 40 / 16) / 3),
    ("sdar_masked_positions_pct", 100 * (150 + 180 + 520) / (256 + 256 + 768)),
])
def test_the_readers_read_what_their_docstrings_say(name, want):
    assert reader(name).read(traced_ctx()) == pytest.approx(want)


def test_the_steps_share_of_the_peak_counts_the_experts_from_the_counter():
    per = ref.flops_per_position(ARCH, 32, 0.0)
    forward = ((8 + 24) * 64 * per["forward"]
               + (512 + 1600) * ref.expert_pair_flops(ARCH))
    peak = flops.peak("TPU v5 lite")["bf16_flops_per_s"]
    got = reader("sdar_client_step_mfu_pct").read(traced_ctx())
    assert got == pytest.approx(100 * 3 * forward / (0.010 * peak))
    assert per["experts"] == 0.0 and per["forward"] > per["attention"] > 0


def test_the_benchmark_lists_the_cell_for_each_reader():
    import json
    bench = json.loads((METRICS.parents[1] / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        mod = reader(name)
        assert entries[name]["workloads"] == ["sdar_masked_phrase_attack"]
        assert (entries[name]["layer"], entries[name]["unit"],
                entries[name]["moves"]) == (mod.LAYER, mod.UNIT, mod.MOVES)
