"""The per-layer readers of the `lfm2_moe` cell (chipbench/lfm2_layers.py and
the six files under chipbench/metrics/ that call it) on hand-made records:
from a program without the scopes and counts (the parent of the PR that
brought them) every reader gives nothing and raises nothing; from a traced
run's records each gives the number its docstring says."""
import importlib.util
import types
from pathlib import Path

import pytest

from chipbench import flops, lfm2_layers
from chipbench.reference import lfm2 as ref
from tests.lfm2_cases import ARCH

METRICS = Path(lfm2_layers.__file__).parent / "metrics"
READERS = ("mixer_device_ms", "experts_device_ms", "optimizer_device_ms",
           "expert_load_max_over_mean", "client_step_mfu_pct",
           "expert_rows_run_pct")
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
    """Three window rounds clocked, rounds 2 and 3 traced: 4 ms of mixer (two
    operations that overlap by 1 ms), 1 ms of router, 2 ms of a conditional
    without a scope path and 1 ms of experts, 2 ms of optimizer, and 2 ms
    outside `phase/train` that no reader may count."""
    dot, cond = "%fusion.1 = f32[8,8]{1,0} fusion(%a), kind=kOutput", (
        "%cond.7 = f32[64,64]{1,0} conditional(%p, %a, %b)")
    ops = [(dot, TRAIN + "layer_0/mixer/dot", 0 * MS, 3 * MS),
           (dot, TRAIN + "transpose(jvp(layer_1))/mixer/dot", 2 * MS, 4 * MS),
           (dot, TRAIN + "layer_1/moe/router/dot", 4 * MS, 5 * MS),
           (cond, "", 5 * MS, 7 * MS),      # the expert layer's two paths
           (dot, TRAIN + "layer_1/moe/experts/dot", 7 * MS, 8 * MS),
           (dot, TRAIN + "optimizer/add", 8 * MS, 10 * MS),
           (dot, "jit(round_fn)/phase/global_battery/mixer/dot", 10 * MS,
            11 * MS),
           (cond, "", 11 * MS, 12 * MS)]    # a battery's: no reader's
    plans = [span("round/plan", tokens_step=64, client_steps=c)
             for c in (8, 8, 24)]
    records = [span("round/record", expert_tokens_held=h, expert_tokens_max=m,
                    expert_tokens_mean=mean, expert_rows_run=run,
                    expert_rows_all=every)
               for h, m, mean, run, every in (
                   (500, 30, 15.0, 768, 8192), (512, 24, 16.0, 1024, 8192),
                   (1600, 40, 16.0, 3072, 24576))]
    return {"spans": {"dispatch": [0.01, 0.01, 0.01]},
            "program_spans": plans + records,
            "traced": {"rounds": 2, "window_rounds": [2, 3]},
            "phases": {"scope_s": {"phase/train": 0.010}},
            "lfm2_ops": ops,
            "lfm2_model": {"seq_len": 32, "arch": ARCH}}


def parent_ctx():
    """What the parent's program leaves: spans without these counts, a trace
    without these scopes."""
    return {"spans": {"dispatch": [0.01, 0.01, 0.01]},
            "program_spans": [span("round/plan", steps_run=2)] * 3
            + [span("round/record")] * 3,
            "traced": {"rounds": 2, "window_rounds": [2, 3]},
            "phases": {"scope_s": {"phase/train": 0.010}},
            "lfm2_ops": [("%fusion.1 = f32[8]{0} fusion(%a)",
                          "jit(round_fn)/phase/train/while/body/conv", 0, MS)],
            "lfm2_model": {"seq_len": 32, "arch": ARCH}}


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_scopes_and_counts_reads_as_nothing(name):
    assert reader(name).read(parent_ctx()) is None
    assert reader(name).read({"spans": {}, "program_spans": [], "traced": None,
                              "phases": None, "lfm2_ops": None}) is None


@pytest.mark.parametrize("name,want", [
    ("mixer_device_ms", 4.0 / 2),        # the union, per traced round
    ("experts_device_ms", (1.0 + 2.0 + 1.0) / 2),
    ("optimizer_device_ms", 2.0 / 2),
    ("expert_load_max_over_mean", (30 / 15 + 24 / 16 + 40 / 16) / 3),
    ("expert_rows_run_pct", 100 * (768 + 1024 + 3072) / (2 * 8192 + 24576)),
])
def test_the_readers_read_what_their_docstrings_say(name, want):
    assert reader(name).read(traced_ctx()) == pytest.approx(want)


def test_the_steps_share_of_the_peak_counts_the_experts_from_the_counter():
    per = ref.flops_per_token(ARCH, 32, 0.0)
    pair = 3 * 2 * ARCH["hidden_size"] * ARCH["moe_intermediate_size"]
    forward = (8 + 24) * 64 * per["forward"] + (512 + 1600) * pair
    peak = flops.peak("TPU v5 lite")["bf16_flops_per_s"]
    got = reader("client_step_mfu_pct").read(traced_ctx())
    assert got == pytest.approx(100 * 3 * forward / (0.010 * peak))
    assert per["experts"] == 0.0 and per["forward"] > per["mixer"] > 0


@pytest.mark.parametrize("how,want", [("every_row_run", 100.0),
                                      ("a_round_without_the_counts", None),
                                      ("no_row_counted", None)])
def test_the_row_share_reads_the_counts_or_nothing(how, want):
    """`expert_rows_run` over `expert_rows_all`: 100 where every held expert
    ran over every position (any CPU run), nothing where a round of the
    window carries no counts (the parent) or none was counted."""
    ctx = traced_ctx()
    records = [r for r in ctx["program_spans"] if r.name == "round/record"]
    for record in records[:1] if how == "a_round_without_the_counts" else records:
        if how == "every_row_run":
            record.counts["expert_rows_run"] = record.counts["expert_rows_all"]
        elif how == "no_row_counted":
            record.counts.update(expert_rows_run=0, expert_rows_all=0)
        else:
            del record.counts["expert_rows_run"], record.counts["expert_rows_all"]
    assert reader("expert_rows_run_pct").read(ctx) == want


def test_the_benchmark_lists_the_cell_for_the_row_share():
    import json
    bench = json.loads((METRICS.parents[1] / "BENCHMARK.json").read_text())
    entry = [m for m in bench["per_layer"] if m["name"] == "expert_rows_run_pct"]
    mod = reader("expert_rows_run_pct")
    assert entry == [dict(name="expert_rows_run_pct", unit=mod.UNIT,
                          better="lower", source="program_counter",
                          layer=mod.LAYER, moves=mod.MOVES,
                          workloads=["lfm2_split_phrase_attack"])]
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        "expert layer", "%", "client_updates_per_s")
