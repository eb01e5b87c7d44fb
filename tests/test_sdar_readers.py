"""The per-layer readers of the `sdar_moe` cell (chipbench/sdar_layers.py,
chipbench/lfm2_layers.py and the nine files under chipbench/metrics/ that
call them) on hand-made records: from a program without the scopes and counts (the
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
           "sdar_masked_positions_pct", "sdar_client_step_mfu_pct",
           "sdar_attention_device_ms", "sdar_attention_tiles_run_pct",
           "sdar_expert_rows_run_pct")
TRAIN = "jit(round_fn)/phase/train/while/body/"
MS = 1e6   # the trace's clock is in nanoseconds
KERNEL = "%blocked_attention = f32[4,8,2048,128]{3,2,1,0} custom-call(%q)"


def reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def span(name, **counts):
    return types.SimpleNamespace(name=name, counts=counts or None)


def traced_ctx():
    """Three window rounds clocked, rounds 2 and 3 traced: 1 ms of noise, 4 ms
    of mixer (two operations that overlap by 1 ms; inside them 1.5 ms of the
    attention kernel, forward and backward), 1 ms of router, 2 ms of a
    conditional without a scope path and 1 ms of experts, and 2 ms outside
    `phase/train` that no reader may count."""
    dot, cond = "%fusion.1 = f32[8,8]{1,0} fusion(%a), kind=kOutput", (
        "%cond.7 = f32[64,64]{1,0} conditional(%p, %a, %b)")
    ops = [(dot, TRAIN + "noise/select_n", 0 * MS, 1 * MS),
           (dot, TRAIN + "layer_0/attn/mixer/dot", 1 * MS, 4 * MS),
           (dot, TRAIN + "transpose(jvp(layer_1))/attn/mixer/dot", 3 * MS, 5 * MS),
           (KERNEL, TRAIN + "layer_0/attn/mixer/attention/blocked_attention_"
            "forward", 2 * MS, 3 * MS),
           (KERNEL, TRAIN + "transpose(jvp(layer_1))/attn/mixer/attention/"
            "blocked_attention_backward", 4 * MS, 4.5 * MS),
           (dot, TRAIN + "layer_1/moe/router/dot", 5 * MS, 6 * MS),
           (cond, "", 6 * MS, 8 * MS),      # the expert layer's two paths
           (dot, TRAIN + "layer_1/moe/experts/dot", 8 * MS, 9 * MS),
           (dot, "jit(round_fn)/phase/global_battery/noise/select_n", 10 * MS,
            11 * MS),
           (cond, "", 11 * MS, 12 * MS)]    # a battery's: no reader's
    plans = [span("round/plan", tokens_step=64, client_steps=c, block_length=4,
                  attention_tiles_run=1376, attention_tiles_all=2816)
             for c in (8, 8, 24)]
    records = [span("round/record", expert_tokens_held=h, expert_tokens_max=m,
                    expert_tokens_mean=mean, positions_masked=masked,
                    positions_scored=scored, expert_rows_run=run,
                    expert_rows_all=every)
               for h, m, mean, masked, scored, run, every in (
                   (500, 30, 15.0, 150, 256, 1024, 8192),
                   (512, 24, 16.0, 180, 256, 768, 8192),
                   (1600, 40, 16.0, 520, 768, 3072, 24576))]
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
    ("sdar_attention_device_ms", 1.5 / 2),
    ("sdar_attention_tiles_run_pct", 100 * 1376 / 2816),
    ("sdar_expert_rows_run_pct", 100 * (1024 + 768 + 3072) / (2 * 8192 + 24576)),
])
def test_the_readers_read_what_their_docstrings_say(name, want):
    assert reader(name).read(traced_ctx()) == pytest.approx(want)


def test_where_xlas_form_runs_the_attention_readers_read_nothing():
    """The program off the kernel's path counts 0 tiles of 0 and has no
    `mixer/attention` scope: nothing, not 0 and not a division by it."""
    ctx = traced_ctx()
    ctx["lfm2_ops"] = [o for o in ctx["lfm2_ops"] if o[0] != KERNEL]
    for plan in ctx["program_spans"][:3]:
        plan.counts.update(attention_tiles_run=0, attention_tiles_all=0)
    assert reader("sdar_attention_device_ms").read(ctx) is None
    assert reader("sdar_attention_tiles_run_pct").read(ctx) is None
    assert reader("sdar_mixer_device_ms").read(ctx) == pytest.approx(4.0 / 2)


EXPERT_READERS = ("sdar_expert_load_max_over_mean", "sdar_client_step_mfu_pct",
                  "sdar_experts_device_ms", "sdar_masked_positions_pct")


@pytest.mark.parametrize("rows", ["absent", "none_counted", "every_row"])
def test_the_row_counts_read_as_their_share_or_as_nothing(rows):
    """`expert_rows_run` over `expert_rows_all`: nothing from records without
    them (the parent's program) or with no row counted, 100 where every held
    expert ran over every position; and the readers of the expert layer that
    were there read what they read whatever the row counts say."""
    ctx, before = traced_ctx(), {}
    for name in EXPERT_READERS:
        before[name] = reader(name).read(ctx)
    for record in ctx["program_spans"][3:]:
        if rows == "absent":
            del record.counts["expert_rows_run"], record.counts["expert_rows_all"]
        elif rows == "none_counted":
            record.counts.update(expert_rows_run=0, expert_rows_all=0)
        else:
            record.counts["expert_rows_run"] = record.counts["expert_rows_all"]
    got = reader("sdar_expert_rows_run_pct").read(ctx)
    assert got == (100.0 if rows == "every_row" else None)
    for name in EXPERT_READERS:
        assert reader(name).read(ctx) == before[name]


@pytest.mark.parametrize("valid", [True, False])
def test_the_rounds_expert_counts_leave_the_row_counts_out(valid):
    """`fl/streamed.py::fold_counts`: the model's `expert_rows` entries go to
    `rows` alone; `held`, `max` and `cells` fold the tokens given to the held
    experts, as before the row counts were there; a step that was not real
    adds nothing."""
    import jax.numpy as jnp
    from dba_mod_tpu.fl.streamed import ModelCounts, fold_counts
    zero = ModelCounts(jnp.int32(0), jnp.int32(0), jnp.int32(0),
                       jnp.zeros((2,), jnp.int32), {"positions_scored": 7})
    tokens = [jnp.asarray([5, 0, 9, 2], jnp.int32),
              jnp.asarray([1, 1, 1, 1], jnp.int32)]
    rows = [jnp.asarray([512, 4096], jnp.int32),
            jnp.asarray([256, 2048], jnp.int32)]
    with_rows = {f"layer_{i}": {"moe": {"expert_tokens": t, "expert_rows": r}}
                 for i, (t, r) in enumerate(zip(tokens, rows))}
    without = {f"layer_{i}": {"moe": {"expert_tokens": t}}
               for i, t in enumerate(tokens)}
    got = fold_counts(zero, with_rows, jnp.asarray(valid))
    old = fold_counts(zero, without, jnp.asarray(valid))
    assert (int(got.held), int(got.max), int(got.cells)) == (
        (20, 9, 8) if valid else (0, 0, 0))
    assert (int(old.held), int(old.max), int(old.cells)) == (
        int(got.held), int(got.max), int(got.cells))
    assert got.rows.tolist() == ([768, 6144] if valid else [0, 0])
    assert old.rows.tolist() == [0, 0] and got.tallies == zero.tallies


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
