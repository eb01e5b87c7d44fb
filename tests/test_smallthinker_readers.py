"""The per-layer readers of the `smallthinker` cell
(chipbench/smallthinker_layers.py, chipbench/lfm2_layers.py and the twelve
`st_*` files under chipbench/metrics/ that call them) on hand-made records:
from a program without the scopes and counts (the parent of the PR that
brought them) every reader gives nothing and raises nothing; from a traced
run's records each gives the number its docstring says; the two kernels'
operations and bytes against a toy counted by hand."""
import importlib.util
import types
from pathlib import Path

import pytest

from chipbench import flops, smallthinker_layers as layers
from chipbench.reference import smallthinker as ref
from tests.smallthinker_cases import arch

METRICS = Path(layers.__file__).parent / "metrics"
READERS = ("st_mixer_device_ms", "st_attention_full_device_ms",
           "st_attention_window_device_ms", "st_attention_tiles_run_pct",
           "st_experts_device_ms", "st_expert_load_max_over_mean",
           "st_expert_rows_run_pct", "st_head_device_ms",
           "st_optimizer_device_ms", "st_client_step_mfu_pct",
           "st_attention_roofline_pct", "st_experts_roofline_pct")
TRAIN = "jit(round_fn)/phase/train/while/body/"
MS = 1e6   # the trace's clock is in nanoseconds
# one global and one window layer, rows of 32 under a window of 8
ARCH = arch(layers_run=[0, 1])
PAIRS = {"full": 32 * 33 // 2, "window": 8 * 9 // 2 + 24 * 8}


def reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def span(name, **counts):
    return types.SimpleNamespace(name=name, counts=counts or None)


def traced_ctx():
    """Three window rounds clocked, rounds 2 and 3 traced: 6 ms of mixer (in
    it 2 ms of the global layers' kernel and 1.5 ms of the window layers'),
    1 ms of router, 2 ms of experts, 1 ms of head, 0.5 ms of optimizer, and
    2 ms outside `phase/train` that no reader may count."""
    dot = "%fusion.1 = f32[8,8]{1,0} fusion(%a), kind=kOutput"
    call = "%blocked_attention = f32[4,7,8192,128]{3,2,1,0} custom-call(%q)"
    ops = [(dot, TRAIN + "layer_0/attn/mixer/dot", 0 * MS, 6 * MS),
           (call, TRAIN + "layer_0/attn/mixer/attention_full/blocked_"
            "attention_forward", 1 * MS, 2 * MS),
           (call, TRAIN + "transpose(jvp(layer_0))/attn/mixer/attention_full/"
            "blocked_attention_backward", 2 * MS, 3 * MS),
           (call, TRAIN + "checkpoint/layer_1/attn/mixer/attention_window/"
            "blocked_attention_forward", 3 * MS, 4.5 * MS),
           (dot, TRAIN + "layer_1/moe/router/dot", 6 * MS, 7 * MS),
           (dot, TRAIN + "layer_1/moe/experts/grouped_experts_forward",
            7 * MS, 9 * MS),
           (dot, TRAIN + "head/dot", 9 * MS, 10 * MS),
           (dot, TRAIN + "optimizer/add", 10 * MS, 10.5 * MS),
           (dot, "jit(round_fn)/phase/global_battery/layer_0/attn/mixer/dot",
            11 * MS, 13 * MS)]
    plans = [span("round/plan", tokens_step=32, client_steps=c,
                  attention_tiles_run=3680, attention_tiles_all=8192,
                  attention_tiles_full=1088, attention_tiles_window=2592,
                  attention_pairs_full=PAIRS["full"],
                  attention_pairs_window=PAIRS["window"])
             for c in (10, 12, 10)]
    records = [span("round/record", expert_tokens_held=h, expert_tokens_max=m,
                    expert_tokens_mean=mean, expert_rows_run=run,
                    expert_rows_all=every)
               for h, m, mean, run, every in (
                   (480, 30, 15.0, 1024, 8192), (600, 24, 16.0, 768, 8192),
                   (500, 40, 16.0, 3072, 24576))]
    return {"spans": {"dispatch": [0.01, 0.01, 0.01]},
            "program_spans": plans + records,
            "traced": {"rounds": 2, "window_rounds": [2, 3]},
            "phases": {"scope_s": {"phase/train": 0.010}},
            "lfm2_ops": ops,
            "smallthinker_model": {"seq_len": 32, "arch": ARCH}}


def parent_ctx():
    """What the parent's program leaves in this cell's place: the streamed
    round's spans without the attention's counts, a trace without the
    scopes."""
    return {"spans": {"dispatch": [0.01, 0.01, 0.01]},
            "program_spans": [span("round/plan", steps_run=2)] * 3
            + [span("round/record")] * 3,
            "traced": {"rounds": 2, "window_rounds": [2, 3]},
            "phases": {"scope_s": {"phase/train": 0.010}},
            "lfm2_ops": [("%fusion.1 = f32[8]{0} fusion(%a)",
                          "jit(round_fn)/phase/train/while/body/conv", 0, MS)],
            "smallthinker_model": {"seq_len": 32, "arch": ARCH}}


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_scopes_and_counts_reads_as_nothing(name):
    assert reader(name).read(parent_ctx()) is None
    assert reader(name).read({"spans": {}, "program_spans": [], "traced": None,
                              "phases": None, "lfm2_ops": None}) is None


@pytest.mark.parametrize("name,want", [
    ("st_mixer_device_ms", 6.0 / 2),
    ("st_attention_full_device_ms", 2.0 / 2),
    ("st_attention_window_device_ms", 1.5 / 2),
    ("st_attention_tiles_run_pct", 100 * 3680 / 8192),
    ("st_experts_device_ms", (1.0 + 2.0) / 2),
    ("st_expert_load_max_over_mean", (30 / 15 + 24 / 16 + 40 / 16) / 3),
    ("st_expert_rows_run_pct", 100 * (1024 + 768 + 3072) / (2 * 8192 + 24576)),
    ("st_head_device_ms", 1.0 / 2),
    ("st_optimizer_device_ms", 0.5 / 2),
])
def test_the_readers_read_what_their_docstrings_say(name, want):
    assert reader(name).read(traced_ctx()) == pytest.approx(want)


def test_the_kernels_work_is_a_toy_counted_by_hand():
    """One global and one window layer, 4 query heads of 32 over 2 key-value
    heads, rows of 32, a window of 8; 4 held experts of 256 x 64."""
    pair = 2 * 2 * 4 * 32                       # a score and a value, 4 heads
    assert ref.pair_flops(ARCH) == pair
    assert layers.layer_kinds(ARCH) == {"full": 1, "window": 1}
    work = layers.attention_work(ARCH, 32, PAIRS, steps=3)
    # forward twice (remat), backward's four products: four times forward
    assert work["ops"] == 3 * 4 * pair * (528 + 228)
    q, kv = 4 * 32 * 4 * 32, 4 * 32 * 2 * 32    # bytes of q (or o), of k (or v)
    assert work["bytes"] == 3 * 2 * (2 * (2 * q + 2 * kv) + (4 * q + 4 * kv))
    routed = layers.experts_work(ARCH, routed_pairs=100, layer_steps=6)
    assert routed["ops"] == 100 * (6 * 256 * 64) * 4
    row, matrices = 4 * 256, 4 * 4 * 3 * 256 * 64
    assert routed["bytes"] == (2 * (2 * 100 * row + 6 * matrices)
                               + (3 * 100 * row + 2 * 6 * matrices))
    peak = flops.peak("TPU v5 lite")
    assert layers.roofline_pct({"ops": peak["bf16_flops_per_s"], "bytes": 0},
                               2.0) == pytest.approx(50.0)
    assert layers.roofline_pct({"ops": 0, "bytes": peak["hbm_bytes_per_s"]},
                               4.0) == pytest.approx(25.0)


def test_the_roofline_shares_divide_the_traced_rounds_work_by_their_time():
    ctx, peak = traced_ctx(), flops.peak("TPU v5 lite")
    steps = 12 + 10                             # the traced rounds' plans
    work = layers.attention_work(ARCH, 32, PAIRS, steps)
    bound = max(work["ops"] / peak["bf16_flops_per_s"],
                work["bytes"] / peak["hbm_bytes_per_s"])
    assert reader("st_attention_roofline_pct").read(ctx) == pytest.approx(
        100 * bound / 3.5e-3)                   # 2 ms + 1.5 ms of kernels
    work = layers.experts_work(ARCH, 600 + 500, steps * 2)
    bound = max(work["ops"] / peak["bf16_flops_per_s"],
                work["bytes"] / peak["hbm_bytes_per_s"])
    assert reader("st_experts_roofline_pct").read(ctx) == pytest.approx(
        100 * bound / 2e-3)


def test_the_steps_share_of_the_peak_counts_the_experts_from_the_counter():
    per = ref.flops_per_token(ARCH, 32, 0.0)
    forward = ((12 + 10) * 32 * per["forward"]
               + (600 + 500) * ref.expert_pair_flops(ARCH))
    peak = flops.peak("TPU v5 lite")["bf16_flops_per_s"]
    got = reader("st_client_step_mfu_pct").read(traced_ctx())
    assert got == pytest.approx(100 * 3 * forward / (0.010 * peak))
    assert per["experts"] == 0.0 and per["forward"] > per["attention"] > 0


def test_where_xlas_forms_run_the_kernels_readers_read_nothing():
    """Off the kernels' path the program counts 0 tiles of 0 and every held
    expert over every position: the tile share reads nothing, the row share
    100, the device times of the sub-scopes what the trace holds."""
    ctx = traced_ctx()
    for plan in ctx["program_spans"][:3]:
        plan.counts.update(attention_tiles_run=0, attention_tiles_all=0)
    for record in ctx["program_spans"][3:]:
        record.counts["expert_rows_run"] = record.counts["expert_rows_all"]
    assert reader("st_attention_tiles_run_pct").read(ctx) is None
    assert reader("st_expert_rows_run_pct").read(ctx) == 100.0


def test_the_benchmark_lists_the_cell_for_each_reader():
    import json
    bench = json.loads((METRICS.parents[1] / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(READERS[0])     # the twelve stand together, in order
    assert names[first:first + len(READERS)] == list(READERS)
    for name in READERS:
        mod = reader(name)
        assert entries[name]["workloads"] == ["smallthinker_long_row_attack"]
        assert (entries[name]["layer"], entries[name]["unit"],
                entries[name]["moves"]) == (mod.LAYER, mod.UNIT, mod.MOVES)
    cell = [w for w in bench["workloads"]
            if w["name"] == "smallthinker_long_row_attack"]
    assert cell == [dict(cell[0], config="smallthinker_21b_a3b_dba",
                         traffic="long_row_phrase_rounds", chips=1)]
