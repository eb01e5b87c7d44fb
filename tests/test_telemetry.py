"""Telemetry subsystem (utils/telemetry.py) + the recorder's crash-safe
saves.

Covers: span nesting and timing monotonicity, the always-on span records
(name, clock, parent, round) of a run with the knob off, Chrome-trace JSON
schema, counter/gauge/histogram flush semantics (cumulative counters,
windowed histograms), the XLA listeners (recompile alarm; compile stages per
jitted function with the knob off), no-op mode adding no files, idempotent
logging setup, the recorder's atomic save (a failure mid-write leaves the
previous file intact), the names the device trace needs (the round program's
four `phase/` scopes, the fused update's kernel name), the benchmark's
reduction of them (chipbench/phases.py), the end-to-end Experiment wiring
— `telemetry: true` runs the same fused program as off, to the bit, and only
adds the exporters' files — and a round's account of its own wall clock: the
`round/wait` leaf, the host counters on `round/finalize`, the reduction to one
row a round, the slow-round line and the benchmark's readers of the rows
(chipbench/accounts.py).
"""
import csv
import gc
import json
import logging
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dba_mod_tpu.config import Params
from dba_mod_tpu.fl.experiment import Experiment
from dba_mod_tpu.utils import telemetry as tel
from dba_mod_tpu.utils.recorder import ROUND_HEADER, Recorder

SMOKE = dict(
    type="mnist", lr=0.1, batch_size=16, epochs=2, no_models=4,
    number_of_total_participants=10, eta=0.8, aggregation_methods="mean",
    internal_epochs=1, is_poison=False, synthetic_data=True,
    synthetic_train_size=600, synthetic_test_size=256, momentum=0.9,
    decay=0.0005, sampling_dirichlet=False, local_eval=True, random_seed=1)


@pytest.fixture
def enabled_tel(tmp_path):
    t = tel.configure(enabled=True, folder=tmp_path)
    yield t
    tel.configure(enabled=False)


# ------------------------------------------------------------------- spans
def test_span_nesting_and_timing_monotonicity(enabled_tel):
    with tel.span("outer"):
        time.sleep(0.01)
        with tel.span("inner"):
            time.sleep(0.01)
    records = {r.name: r for r in enabled_tel.own_spans()}
    outer, inner = records["outer"], records["inner"]
    assert inner.end_ns > inner.start_ns
    assert inner.parent == "outer" and outer.parent is None
    # containment: the inner span starts no earlier and ends no later
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    # spans feed duration histograms
    assert enabled_tel.histogram("span/outer").total_count == 1
    assert enabled_tel.histogram("span/inner").total_count == 1


def test_span_stack_feeds_phase_context(enabled_tel):
    assert enabled_tel.phase() == "-"
    with tel.span("round/dispatch"):
        assert enabled_tel.phase() == "round/dispatch"
        with tel.span("eval/global"):
            assert enabled_tel.phase() == "eval/global"
        assert enabled_tel.phase() == "round/dispatch"
    assert enabled_tel.phase() == "-"


def test_chrome_trace_schema(enabled_tel, tmp_path):
    with tel.span("a"):
        with tel.span("b"):
            pass
    enabled_tel.write_trace()
    doc = json.loads((tmp_path / "trace.json").read_text())
    assert isinstance(doc["traceEvents"], list)
    complete = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in complete} == {"a", "b"}
    for e in complete:
        for key in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert key in e
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    # metadata record present (process naming for Perfetto)
    assert any(e.get("ph") == "M" for e in doc["traceEvents"])


def test_sync_returns_payload(enabled_tel):
    x = jnp.ones((3,)) * 2
    assert tel.sync(x) is x
    np.testing.assert_array_equal(np.asarray(x), 2.0)


# ---------------------------------------------------------------- registry
def test_counter_histogram_flush_and_window_reset(enabled_tel, tmp_path):
    enabled_tel.counter("rounds").inc()
    enabled_tel.counter("rounds").inc(2)
    enabled_tel.histogram("delta_norm").observe(1.0)
    enabled_tel.histogram("delta_norm").observe(3.0)
    enabled_tel.gauge("g").set(7.0)
    enabled_tel.flush_round(1)
    enabled_tel.flush_round(2)
    lines = [json.loads(line) for line in
             (tmp_path / "telemetry.jsonl").read_text().splitlines()]
    assert [ln["epoch"] for ln in lines] == [1, 2]
    assert lines[0]["counters"]["rounds"] == 3
    h = lines[0]["histograms"]["delta_norm"]
    assert h["count"] == 2 and h["min"] == 1.0 and h["max"] == 3.0
    assert h["p95"] == 3.0 and h["sum"] == 4.0
    assert lines[0]["gauges"]["g"] == 7.0
    # histograms are windowed per flush; counters are cumulative
    assert "delta_norm" not in lines[1]["histograms"]
    assert lines[1]["counters"]["rounds"] == 3


# ----------------------------------------------------------- XLA listeners
def test_recompile_listener_fires_on_retrace_not_cache_hit(enabled_tel):
    salt = np.float32(time.time() % 97)  # defeat any persistent jit reuse

    @jax.jit
    def f(x):
        return x * 2.0 + salt

    f(jnp.ones((4,)))  # warmup compile
    assert enabled_tel.counter("xla/compiles").value >= 1
    enabled_tel.mark_warm()
    f(jnp.ones((4,)))  # jit cache hit: must stay silent
    assert enabled_tel.counter("xla/recompiles_after_warmup").value == 0
    f(jnp.ones((5,)))  # new shape: forced retrace, counted loudly
    assert enabled_tel.counter("xla/recompiles_after_warmup").value >= 1


def test_mark_warm_is_idempotent(enabled_tel):
    enabled_tel.mark_warm()
    enabled_tel.mark_warm()
    assert enabled_tel._warm
    assert enabled_tel.counter("xla/recompiles_after_warmup").value == 0


def test_record_memory_never_raises(enabled_tel):
    enabled_tel.record_memory()  # CPU backend reports None → no-op


# ------------------------------------------------------------- no-op mode
def test_noop_mode_adds_no_files_and_no_state(tmp_path):
    t = tel.configure(enabled=False, folder=tmp_path)
    assert t is tel.NULL and not t.enabled
    with tel.span("x"):
        pass
    tel.count("c")
    tel.sync(jnp.ones((2,)))
    t.flush_round(1)
    t.write_trace()
    t.close()
    assert list(tmp_path.iterdir()) == []


def test_instrument_is_passthrough_when_disabled(enabled_tel):
    calls = []

    def f(x):
        calls.append(x)
        return x + 1

    tel.configure(enabled=False)
    g = tel.instrument(f, "probe", batches=5)
    assert g(1) == 2
    t2 = tel.configure(enabled=True)
    assert g(2) == 3
    assert calls == [1, 2]
    assert t2.counter("eval/batches").value == 5
    assert t2.histogram("span/probe").total_count == 1
    tel.configure(enabled=False)


# ------------------------------------------------------------ logging setup
def test_logging_setup_is_idempotent_and_replaces_run_file(tmp_path):
    lg = tel.setup_logging(tmp_path)
    n = len(lg.handlers)
    assert tel.setup_logging(tmp_path) is lg
    assert len(lg.handlers) == n  # same folder: nothing added
    other = tmp_path / "other"
    other.mkdir()
    tel.setup_logging(other)
    run_files = [h for h in lg.handlers
                 if getattr(h, "_dba_run_file", False)]
    assert len(run_files) == 1  # replaced, not stacked
    assert run_files[0].baseFilename.endswith(str(other / "log.txt"))
    assert lg.propagate is False


# --------------------------------------------------- recorder atomic saves
def test_recorder_atomic_save_keeps_previous_csv_on_failure(tmp_path):
    rec = Recorder(tmp_path)
    rec.add_test("global", 1, 0.5, 90.0, 9, 10)
    rec.add_round_json(epoch=1, global_acc=90.0, round_time=0.1,
                       dispatch_time=0.08, finalize_time=0.02)
    rec.save(is_poison=False)
    before_csv = (tmp_path / "round_result.csv").read_text()
    before_jsonl = (tmp_path / "metrics.jsonl").read_text()

    class Poison:
        def __str__(self):
            raise RuntimeError("boom mid-write")

    rec.round_result.append([Poison()])
    with pytest.raises(RuntimeError):
        rec.save(is_poison=False)
    # the interrupted rewrite left the previous files byte-identical
    assert (tmp_path / "round_result.csv").read_text() == before_csv
    assert (tmp_path / "metrics.jsonl").read_text() == before_jsonl
    assert not list(tmp_path.glob("*.tmp"))


def test_recorder_atomic_save_keeps_previous_jsonl_on_failure(tmp_path):
    rec = Recorder(tmp_path)
    rec.add_round_json(epoch=1, global_acc=1.0)
    rec.save(is_poison=False)
    before = (tmp_path / "metrics.jsonl").read_text()
    rec._jsonl_rows.append({"bad": object()})  # not JSON-serializable
    with pytest.raises(TypeError):
        rec.save(is_poison=False)
    assert (tmp_path / "metrics.jsonl").read_text() == before
    assert not list(tmp_path.glob("*.tmp"))


def test_round_header_carries_split_times():
    assert ROUND_HEADER[-3:] == ["round_time", "dispatch_time",
                                 "finalize_time"]


# ------------------------------------------------------------- end-to-end
ROUND_SPANS = {"round/dispatch": ("round/plan", "round/stage",
                                  "round/enqueue"),
               "round/finalize": ("round/wait", "round/fetch",
                                  "round/record")}


def _state_and_rows(e):
    leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(e.global_vars)]
    rows = [{k: v for k, v in row.items() if not k.endswith("time")}
            for row in e.recorder._jsonl_rows]
    return leaves, rows


@pytest.fixture(scope="module")
def off_run(tmp_path_factory):
    """Two rounds with the knob off, shared read-only: (experiment, the span
    records it made, the compile stages of the process after it)."""
    n0 = len(tel.spans())
    tmp = tmp_path_factory.mktemp("tel_off")
    e = Experiment(Params.from_dict(dict(
        SMOKE, run_dir=str(tmp / "runs"), save_model=True,
        checkpoint_dir=str(tmp / "saved_models"))))
    e.run()
    return e, tel.spans(n0), tel.compile_stages()


def test_span_records_carry_name_clock_parent_round(off_run):
    _, records, _ = off_run
    names = {r.name for r in records}
    assert {"setup/data", "setup/device_put", "setup/partition",
            "engine/build", "round/checkpoint"} <= names
    for parent, children in ROUND_SPANS.items():
        assert {parent, *children} <= names
    now = time.time_ns()
    for r in records:
        assert now - 3600 * 10 ** 9 < r.start_ns <= r.end_ns <= now
        if r.name.startswith("round/"):
            assert r.round in (1, 2)
        if r.name.startswith("setup/"):
            assert r.round is None and r.parent is None


def test_round_children_lie_inside_dispatch_and_finalize(off_run):
    _, records, _ = off_run
    for rnd in (1, 2):
        of = {r.name: r for r in records if r.round == rnd}
        for parent, children in ROUND_SPANS.items():
            p = of[parent]
            at = p.start_ns
            for name in children:   # in order, inside, not overlapping
                c = of[name]
                assert c.parent == parent
                assert at <= c.start_ns <= c.end_ns <= p.end_ns
                at = c.end_ns
        assert of["round/checkpoint"].start_ns >= of["round/finalize"].end_ns


def test_experiment_telemetry_off_writes_no_files(off_run):
    e, records, _ = off_run
    assert records  # spans exist with the knob off
    assert not (e.folder / "telemetry.jsonl").exists()
    assert not (e.folder / "trace.json").exists()
    assert e.telemetry is tel.NULL


def test_compile_stages_count_with_the_knob_off(off_run):
    e, _, stages = off_run
    assert e.engine.round_fn._cache_size() == 1
    got = stages["round_fn"]
    assert {"xla/trace_secs", "xla/lower_secs", "xla/compile_secs"} <= set(got)
    assert all(v > 0 for v in got.values())
    # the batteries' and the reference's compiles are told from the round's
    assert "round_fn" not in [k for k in stages if k != "round_fn"]
    assert len(stages) > 1


def test_experiment_telemetry_end_to_end(tmp_path, off_run):
    """`telemetry: true` adds the exporters' files to a run of the SAME
    fused program: the per-round spans of the fused path, no split-phase
    span, one compiled round program, numbers equal to the knob-off run's."""
    e = Experiment(Params.from_dict(dict(
        SMOKE, telemetry=True, run_dir=str(tmp_path))))
    try:
        e.run()
        folder = e.folder
        assert (folder / "telemetry.jsonl").exists()
        assert (folder / "trace.json").exists()
        doc = json.loads((folder / "trace.json").read_text())
        names = {ev["name"] for ev in doc["traceEvents"]
                 if ev.get("ph") == "X"}
        assert {"round/dispatch", "round/plan", "round/stage",
                "round/enqueue", "round/finalize", "round/wait",
                "round/fetch", "round/record", "engine/build",
                "setup/data"} <= names
        assert not {"round/train", "round/aggregate", "eval/local",
                    "eval/global"} & names
        assert e.engine.round_fn._cache_size() == 1
        assert e.engine.train_fn._cache_size() == 0
        lines = [json.loads(line) for line in
                 (folder / "telemetry.jsonl").read_text().splitlines()]
        assert [ln["epoch"] for ln in lines] == [1, 2]
        last = lines[-1]
        # per-round span durations: the flush comes after the round's last
        # span has ended, so a line holds its own round's, once each
        for span in ("span/round/dispatch", "span/round/plan",
                     "span/round/wait", "span/round/fetch",
                     "span/round/finalize", "span/round/record"):
            assert last["histograms"][span]["count"] == 1
        assert last["counters"]["rounds"] == 2
        # the round's own account, as the reduction of the records gives it
        assert [ln["account"]["round"] for ln in lines] == [1, 2]
        assert last["account"] == tel.round_accounts(
            records=[r for r in e.telemetry.own_spans()
                     if r.round == 2])[0]
        assert last["account"]["counts"]["compiles"] == 0
        assert lines[0]["account"]["counts"]["compiles"] >= 1
        # no retraces once the first full round has compiled everything
        assert last["counters"]["xla/recompiles_after_warmup"] == 0
        # the recorder carries the honest split times
        with open(folder / "round_result.csv") as f:
            rows = list(csv.reader(f))
        assert rows[0] == ROUND_HEADER
        times = dict(zip(rows[0], rows[1]))
        assert float(times["dispatch_time"]) > 0
        assert float(times["finalize_time"]) > 0
        summary = e.telemetry.summary_table()
        assert "round/dispatch" in summary and "xla compiles" in summary
        assert "round 1: host_ms" in summary  # it compiled: the largest
        leaves_on, rows_on = _state_and_rows(e)
    finally:
        tel.configure(enabled=False)
    leaves_off, rows_off = _state_and_rows(off_run[0])
    assert rows_on == rows_off
    for a, b in zip(leaves_on, leaves_off):
        np.testing.assert_array_equal(a, b)


def test_split_path_falls_back_after_takeover(tmp_path):
    """There is no split path to fall back from: after a later configure()
    (another Experiment taking over the process-wide instance) the first
    experiment still runs the fused program, still records its spans, and
    still flushes per-round metrics on its own instance."""
    e = Experiment(Params.from_dict(dict(
        SMOKE, telemetry=True, telemetry_dir=str(tmp_path / "t"))),
        save_results=False)
    try:
        tel.configure(enabled=False)  # a second experiment takes over
        n0 = len(tel.spans())
        r = e.run_round(1)
        assert r["dispatch_time"] > 0
        assert e.engine.round_fn._cache_size() == 1
        assert e.engine.train_fn._cache_size() == 0
        assert {"round/dispatch", "round/enqueue", "round/fetch"} <= {
            s.name for s in tel.spans(n0)}
        lines = [json.loads(line) for line in
                 (tmp_path / "t" / "telemetry.jsonl").read_text()
                 .splitlines()]
        assert lines and lines[-1]["counters"]["rounds"] == 1
    finally:
        tel.configure(enabled=False)


def test_telemetry_split_path_matches_fused_metrics(tmp_path):
    """telemetry=true and telemetry=false dispatch the same round program
    and record equal results — exactly, not to a tolerance."""
    e_off = Experiment(Params.from_dict(dict(SMOKE)), save_results=False)
    r_off = e_off.run_round(1)
    e = Experiment(Params.from_dict(dict(
        SMOKE, telemetry=True, telemetry_dir=str(tmp_path / "t"))),
        save_results=False)
    try:
        r_on = e.run_round(1)
    finally:
        tel.configure(enabled=False)
    timed = ("round_time", "dispatch_time", "finalize_time")
    assert ({k: v for k, v in r_on.items() if k not in timed}
            == {k: v for k, v in r_off.items() if k not in timed})
    for on, off in zip(*(_state_and_rows(x)[0] for x in (e, e_off))):
        np.testing.assert_array_equal(on, off)
    for eng in (e.engine, e_off.engine):
        assert eng.round_fn._cache_size() == 1
        assert eng.train_fn._cache_size() == 0


# ------------------------------------------- names the device trace needs
def test_round_program_holds_the_four_phase_scopes(off_run):
    e, _, _ = off_run
    tasks_seq, idx_seq, mask_seq, ns, lane = e.build_static_round_inputs(3)
    rng_t, rng_a = jax.random.split(jax.random.key(0))
    text = e.engine.round_fn.lower(
        e.global_vars, e.fg_state, tasks_seq, idx_seq, mask_seq, lane, ns,
        rng_t, rng_a).as_text(debug_info=True)
    for scope in ("phase/train", "phase/aggregate", "phase/local_battery",
                  "phase/global_battery"):
        assert f"jit(round_fn)/{scope}/" in text, scope


def test_fused_update_pallas_call_carries_the_kernel_name():
    from dba_mod_tpu.ops.fused_update import (KERNEL_NAME,
                                              make_fused_step_update)
    fused = make_fused_step_update(0.9, 5e-4, False, use_pallas=True,
                                   interpret=True)
    w = {"a": jnp.ones((3, 40)), "b": jnp.ones((3, 8, 16))}

    def step(w, g):
        lr, valid = jnp.full((3,), 0.1), jnp.ones((3,), bool)
        return jax.vmap(fused)(lr, valid, w, g, g, {}, {}, {})

    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from pallas_calls(sub)

    calls = list(pallas_calls(jax.make_jaxpr(step)(w, w).jaxpr))
    assert calls
    for i, eqn in enumerate(calls):
        assert eqn.params["name"] == f"{KERNEL_NAME}_{i}"


# --------------------------------- the benchmark's reduction of the names
@pytest.mark.parametrize("case", ["nested_while", "unnamed_while",
                                  "gap_in_fetch", "gap_in_no_span"])
def test_phases_reduction_on_synthetic_events(case):
    from chipbench import phases, selfcheck_phases
    r = phases.reduce_events(*selfcheck_phases.synthetic_events())
    if case == "nested_while":
        # %while.30 [0.5, 6.5] s covers its body's operations: counted once
        assert r["scope_s"]["phase/train"] == pytest.approx(6.0)
        assert r["kernel_s"] == pytest.approx(0.75)
        assert r["unattributed_s"] == pytest.approx(0.1)
        (op, secs), = r["unattributed_ops"]
        assert op.startswith("%copy.1") and secs == pytest.approx(0.1)
        assert sum(r["scope_s"].values()) + r["unattributed_s"] == \
            pytest.approx(r["busy_s"])
    elif case == "unnamed_while":
        # %while.27 [6.6, 7.6] s has no scope path; 0.9 s of it is named
        # local-battery work, so the loop, its gap and its unnamed
        # %reverse.1 count there and nothing of it is left unattributed
        assert r["scope_s"]["phase/local_battery"] == pytest.approx(1.0)
        assert not [op for op, _ in r["unattributed_ops"]
                    if op.startswith(("%while.27", "%reverse.1"))]
    elif case == "gap_in_fetch":
        assert r["idle_by_program_span"]["round/fetch"] == pytest.approx(0.2)
        assert r["idle_by_program_span"]["round/plan"] == pytest.approx(0.5)
    else:
        assert r["idle_by_program_span"]["no_span"] == pytest.approx(1.7)
        assert r["idle_attributed_pct"] == pytest.approx(100 * 0.7 / 2.4)


def test_phases_selfcheck_reduces_the_recorded_trace():
    """chipbench/testdata/phases_sample.xplane.pb (recorded on a TPU v5e):
    scope paths from the event metadata, the named kernel, the program's
    spans; and every reader of them on a synthetic and an empty context."""
    from chipbench import selfcheck_phases
    assert selfcheck_phases.main() == 0


# ----------------------------------- the plan's step counts and their readers
def test_span_counts_go_onto_the_record_and_the_chrome_trace(enabled_tel,
                                                             tmp_path):
    with tel.span("round/plan", round=3) as sp:
        sp.count(steps_plan=8, steps_run=6)
        sp.count(lane_steps_real=7)
    with tel.span("round/stage", round=3):
        pass
    plan, stage = enabled_tel.own_spans()
    assert plan.counts == {"steps_plan": 8, "steps_run": 6,
                           "lane_steps_real": 7}
    assert stage.counts is None
    enabled_tel.write_trace()
    events = {e["name"]: e for e in json.loads(
        (tmp_path / "trace.json").read_text())["traceEvents"]
        if e["ph"] == "X"}
    assert events["round/plan"]["args"]["steps_run"] == 6
    assert "steps_run" not in events["round/stage"]["args"]


@pytest.mark.parametrize("reader", ["train_steps_run_pct",
                                    "train_lane_fill_pct"])
@pytest.mark.parametrize("records", ["counted", "bare", "none"])
def test_step_count_readers(reader, records):
    """chipbench/metrics/train_*_pct.py on the program's own span records: a
    window of three rounds after one of set-up; nothing (not zero) from a
    program that does not count."""
    from chipbench import selfcheck_steps as sc
    _, mod = sc.readers()[reader]  # found by name, as the harness finds it
    made = sc.synthetic_records()
    if records == "counted":
        want = {"train_steps_run_pct": 20.0, "train_lane_fill_pct": 35.0}
        assert mod.read(sc.context(made, 3)) == pytest.approx(want[reader])
        # the real record type, through the program's own store
        n0 = len(tel.spans())
        for r in made:
            with tel.span(r.name, round=r.round) as sp:
                if r.counts:
                    sp.count(**r.counts)
        ctx = sc.context(tel.spans(n0), 3)
        assert mod.read(ctx) == pytest.approx(want[reader])
    elif records == "bare":
        bare = [sc.BareSpan(*r[:5]) for r in made]
        assert mod.read(sc.context(bare, 3)) is None
    else:
        assert mod.read(sc.context(None, 0)) is None
        assert mod.read(sc.context([], 3)) is None


def test_steps_selfcheck_reads_the_recorded_sample():
    """chipbench/testdata/steps_sample.json (the `round/plan` records of a run
    of `tiny_dba_attack` on a TPU v5e): both readers against counts made by
    hand, and BENCHMARK.json's entries for them."""
    from chipbench import selfcheck_steps
    assert selfcheck_steps.main() == 0


# ------------------------------- a round's account of its own wall clock
BOUNDARY_COUNTS = ("wall_ns", "cpu_ns", "proc_cpu_ns", "nvcsw", "nivcsw",
                   "majflt", "inblock", "oublock", "gc_collections",
                   "gc_pause_ns", "gc_gen2", "compiles", "compile_ns")


class _SlowLines(logging.Handler):
    """The `slow round` warnings of the program's logger, parsed."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.lines = []

    def emit(self, record):
        if record.getMessage().startswith("slow round "):
            self.lines.append(json.loads(record.args[0]))

    def __enter__(self):
        logging.getLogger("dba_mod_tpu").addHandler(self)
        return self

    def __exit__(self, *exc):
        logging.getLogger("dba_mod_tpu").removeHandler(self)


def _run_rounds(tmp, rounds, in_save=None):
    """`rounds` rounds of the smoke experiment with the knob off;
    `in_save(epoch)` runs inside every `Recorder.save`, so inside the
    round's `round/record`. The collector is off meanwhile (a full
    collection of a test process's heap is a stall of its own, and the
    slow-round line says so), and the `host_ms` rule's floor is 150 ms, not
    50: beside five busy workers the round thread loses its core for tens
    of milliseconds. -> (records, rows, slow lines)."""
    e = Experiment(Params.from_dict(dict(
        SMOKE, epochs=rounds, run_dir=str(tmp / "runs"))))
    gc.collect()
    # the rounds' counts start here, after that collection
    e._boundary = tel.RoundBoundary()
    save = e.recorder.save

    def patched(is_poison):
        if in_save is not None:
            in_save(tel.open_round())
        return save(is_poison)

    e.recorder.save = patched
    n0 = len(tel.spans())
    floor, tel.SLOW_HOST_MS = tel.SLOW_HOST_MS, 150.0
    gc.disable()
    try:
        with _SlowLines() as slow:
            for epoch in range(1, rounds + 1):
                e.run_round(epoch)
    finally:
        gc.enable()
        tel.SLOW_HOST_MS = floor
    return tel.spans(n0), tel.round_accounts(n0), slow.lines


@pytest.fixture(scope="module")
def account_run(tmp_path_factory):
    """Eight quiet rounds, shared read-only."""
    return _run_rounds(tmp_path_factory.mktemp("tel_accounts"), 8)


def test_wait_and_fetch_are_siblings_and_the_wait_comes_first(account_run):
    records, _, _ = account_run
    for rnd in range(1, 9):
        of = {r.name: r for r in records if r.round == rnd}
        wait, fetch = of["round/wait"], of["round/fetch"]
        assert wait.parent == fetch.parent == "round/finalize"
        assert wait.end_ns <= fetch.start_ns
        # on this backend the round's compute is inside the wait, and the
        # transfer alone is short
        assert (wait.end_ns - wait.start_ns) > (fetch.end_ns - fetch.start_ns)


def test_accounts_tile_the_process_time(account_run):
    records, rows, _ = account_run
    assert [a["round"] for a in rows] == list(range(1, 9))
    ends = []
    for a in rows:
        parts = (sum(a["leaves"].values()) + sum(a["self"].values())
                 + a["between_ms"])
        assert parts == pytest.approx(a["extent_ms"], abs=1e-3)
        assert set(a["self"]) == {"round/dispatch", "round/finalize"}
        assert set(a["leaves"]) == {"round/plan", "round/stage",
                                    "round/enqueue", "round/wait",
                                    "round/fetch", "round/record"}
        assert a["wait_ms"] == a["leaves"]["round/wait"]
        assert a["host_ms"] == pytest.approx(
            a["extent_ms"] - a["wait_ms"] - a["between_ms"], abs=1e-6)
        assert 0 <= a["between_ms"] < 1.0  # run_round: nothing in between
        ends.append(a["start_ns"] + a["extent_ms"] * 1e6)
    for a, b, end in zip(rows, rows[1:], ends):
        assert end <= b["start_ns"]  # sequential rounds do not overlap
        # the counts' tile runs from one finalize's end to the next one's
        assert b["counts"]["wall_ns"] / 1e6 == pytest.approx(
            (b["start_ns"] - end) / 1e6 + b["extent_ms"], abs=1.0)


def test_boundary_counts_ride_the_finalize_and_record_spans(account_run):
    records, rows, _ = account_run
    for rnd in range(1, 9):
        of = {r.name: r for r in records if r.round == rnd}
        counts = of["round/finalize"].counts
        assert set(BOUNDARY_COUNTS) <= set(counts)
        assert set(counts) <= set(BOUNDARY_COUNTS) | {"runq_wait_ns"}
        assert all(isinstance(v, int) and v >= 0 for v in counts.values())
        assert 0 < counts["cpu_ns"] <= counts["wall_ns"]
        written = of["round/record"].counts
        assert written["files"] >= 3 and written["bytes"] > 0
    # the first round compiled the round program, the later ones nothing
    assert rows[0]["counts"]["compiles"] >= 1
    assert rows[0]["counts"]["compile_ns"] > 0
    assert all(a["counts"]["compiles"] == 0 for a in rows[2:])
    # the recorder rewrites its files whole: a round writes more than the last
    sizes = [a["counts"]["bytes"] for a in rows]
    assert sizes == sorted(sizes) and sizes[0] < sizes[-1]


def test_quiet_rounds_print_no_slow_round_line(account_run):
    _, rows, slow = account_run
    assert len(rows) == 8 and slow == []


def test_forced_collection_shows_in_its_round_only(tmp_path):
    _, rows, _ = _run_rounds(  # no collection but the forced one
        tmp_path, 4, lambda epoch: gc.collect() if epoch == 3 else None)
    for a in rows:
        c = a["counts"]
        if a["round"] == 3:
            assert c["gc_collections"] == 1 and c["gc_gen2"] == 1
            assert 0 < c["gc_pause_ns"] < c["wall_ns"]
            assert c["gc_pause_ns"] / 1e6 <= a["leaves"]["round/record"]
        else:
            assert (c["gc_collections"], c["gc_pause_ns"],
                    c["gc_gen2"]) == (0, 0, 0)


def test_a_stall_in_the_recorder_gives_one_slow_round_line(tmp_path):
    _, rows, slow = _run_rounds(
        tmp_path, 12, lambda epoch: time.sleep(0.4) if epoch == 9 else None)
    assert len(slow) == 1
    line, = slow
    assert line["round"] == 9 and line["rule"] == "host_ms"
    assert line["leaf"] == "round/record"
    assert line["account"] == rows[8]
    assert line["account"]["leaves"]["round/record"] >= 400
    assert line["median"]["leaves"]["round/record"] < 100
    assert line["median_of"] >= tel.SLOW_HOST_MIN_ROUNDS
    # every count, beside its running median
    assert set(BOUNDARY_COUNTS) | {"files", "bytes"} <= (
        set(line["account"]["counts"]) & set(line["median"]["counts"]))
    # the thread slept: the stall is wall time, not CPU time
    c = line["account"]["counts"]
    assert c["wall_ns"] - c["cpu_ns"] >= 400e6 - 5e6


def _spans_of_a_round(boundary, rnd, wait_s=0.0, record_s=0.0):
    with tel.span("round/dispatch", round=rnd):
        with tel.span("round/plan", round=rnd):
            pass
    with tel.span("round/finalize", round=rnd) as fin:
        with tel.span("round/wait", round=rnd):
            time.sleep(wait_s)
        with tel.span("round/record", round=rnd):
            time.sleep(record_s)
        fin.count(**boundary.counts())
    return boundary.close(rnd)


@pytest.mark.parametrize("case", ["late_device", "late_device_too_early",
                                  "host_after_two", "host_under_50_ms"])
def test_slow_round_rules(case):
    """The two rules on hand-made rounds: a late device needs eight rounds
    behind it (the extent rule), a stall of the host two."""
    tel.install_xla_listeners()
    boundary = tel.RoundBoundary()
    before = {"late_device": 8, "late_device_too_early": 7,
              "host_after_two": 2, "host_under_50_ms": 2}[case]
    with _SlowLines() as slow:
        for rnd in range(1, before + 1):
            _spans_of_a_round(boundary, rnd, wait_s=0.02)
        assert slow.lines == []
        if case.startswith("late_device"):
            row = _spans_of_a_round(boundary, before + 1, wait_s=0.06)
        else:
            row = _spans_of_a_round(
                boundary, before + 1, wait_s=0.02,
                record_s=0.08 if case == "host_after_two" else 0.01)
    if case == "late_device":
        line, = slow.lines
        assert (line["rule"], line["leaf"]) == ("extent_ms", "round/wait")
        assert line["account"] == row and row["host_ms"] < 5
    elif case == "host_after_two":
        line, = slow.lines
        assert (line["rule"], line["leaf"]) == ("host_ms", "round/record")
        assert line["median_of"] == 2
    else:
        assert slow.lines == []


def test_span_length_survives_a_step_of_the_wall_clock(monkeypatch):
    real = time.time_ns
    n0 = len(tel.spans())
    with tel.span("clock/stepped"):
        # the wall clock goes back 10 s while the span is open
        monkeypatch.setattr(time, "time_ns", lambda: real() - 10 ** 10)
        time.sleep(0.02)
    monkeypatch.undo()
    record, = tel.spans(n0)
    assert 0.02e9 <= record.end_ns - record.start_ns < 1e9
    assert abs(record.start_ns - real()) < 5e9  # the start: the wall clock's


@pytest.mark.parametrize("case", ["nested", "between", "round_comes_again",
                                  "checkpoint_stays", "no_round"])
def test_round_accounts_arithmetic_on_synthetic_records(case):
    from chipbench import selfcheck_accounts as sc
    ms = 10 ** 6
    if case == "nested":
        # a parent with two children, one of them a parent itself
        a, = tel.round_accounts(records=[
            sc.Span("leaf/a", 10 * ms, 14 * ms, "inner", 1),
            sc.Span("inner", 8 * ms, 16 * ms, "outer", 1),
            sc.Span("leaf/b", 17 * ms, 19 * ms, "outer", 1),
            sc.Span("outer", 5 * ms, 25 * ms, None, 1)])
        assert a["leaves"] == {"leaf/a": 4.0, "leaf/b": 2.0}
        assert a["self"] == {"inner": 4.0, "outer": 10.0}
        assert (a["extent_ms"], a["between_ms"], a["host_ms"]) == (20, 0, 20)
    elif case == "between":
        a, = tel.round_accounts(records=sc.synthetic_records()[-8:])
        assert a["between_ms"] == pytest.approx(sc.BETWEEN_MS)
        assert a["wait_ms"] == pytest.approx(sc.WAIT_MS)
        assert a["host_ms"] == pytest.approx(
            a["extent_ms"] - sc.BETWEEN_MS - sc.WAIT_MS)
    elif case == "round_comes_again":
        one = sc.round_records(1, 0, host_ms=20)
        again = sc.round_records(1, 10 ** 10, host_ms=30)
        first, second = tel.round_accounts(records=one + again)
        assert first["round"] == second["round"] == 1
        assert second["start_ns"] - first["start_ns"] == 10 ** 10
        assert second["host_ms"] - first["host_ms"] == pytest.approx(10)
    elif case == "checkpoint_stays":
        recs = sc.round_records(4, 0, host_ms=20)
        end = max(r.end_ns for r in recs)
        recs.append(sc.Span("round/checkpoint", end + ms, end + 6 * ms, None,
                            4))
        a, = tel.round_accounts(records=recs)
        assert a["leaves"]["round/checkpoint"] == 5.0
        assert a["between_ms"] == pytest.approx(sc.BETWEEN_MS + 1.0)
    else:
        assert tel.round_accounts(records=[
            sc.Span("setup/data", 0, 5 * ms, None, None)]) == []


ACCOUNT_READERS = ("round_host_ms", "host_stall_ms_max", "host_offcpu_ms",
                   "gc_pause_ms", "record_kib", "idle_in_wait_ms")


@pytest.mark.parametrize("reader", ACCOUNT_READERS)
@pytest.mark.parametrize("records", ["counted", "bare", "none"])
def test_round_account_readers(reader, records):
    """chipbench/metrics/<reader>.py over the program's own reduction of
    synthetic records: the warm round and a window of three, two of them
    traced; nothing (not zero) from records without the span or the count a
    reader needs. `idle_in_wait_ms` reads the trace alone."""
    from chipbench import selfcheck_accounts as sc
    _, mod = sc.readers()[reader]  # found by name, as the harness finds it
    if records == "counted":
        got = mod.read(sc.context(sc.synthetic_records()))
        assert got == pytest.approx(sc.WANT[reader])
        return
    if records == "bare":   # a program before the wait and the counts
        ctx = sc.context(sc.synthetic_records(wait=False, counts=False))
    else:
        ctx = sc.context(None)
    if reader == "idle_in_wait_ms":
        assert mod.read(ctx) == pytest.approx(sc.WANT[reader])
        assert mod.read(dict(ctx, trace=None)) is None
    else:
        assert mod.read(ctx) is None
        assert mod.read(dict(ctx, program_spans=[])) is None


def test_accounts_selfcheck_reads_the_recorded_sample():
    """chipbench/testdata/accounts_sample.json (the round records of a traced
    run on a TPU v5e): the account arithmetic by hand, the six readers
    against what that run printed, and BENCHMARK.json's entries for them."""
    from chipbench import selfcheck_accounts
    assert selfcheck_accounts.main() == 0


def test_watchdog_soft_stall_prints_the_round_account(caplog):
    from dba_mod_tpu.utils.run_guard import Watchdog
    lg = logging.getLogger("dba_mod_tpu")
    prev_propagate, lg.propagate = lg.propagate, True
    wd = Watchdog(soft_s=0.05, hard_s=0.0)
    try:
        with tel.span("round/dispatch", round=77):
            with tel.span("round/plan", round=77):
                pass
        with caplog.at_level("ERROR", logger="dba_mod_tpu"), \
                tel.span("round/finalize", round=77), \
                wd.zone("round/finalize"):
            stalled = lambda: [r for r in caplog.records
                               if "stalled" in r.getMessage()]
            deadline = time.monotonic() + 5.0
            while not stalled() and time.monotonic() < deadline:
                time.sleep(0.01)  # the line comes after the count
    finally:
        lg.propagate = prev_propagate
    stall, = stalled()
    assert stall.args[4] == ["round/finalize"]  # the open spans at entry
    account = json.loads(stall.args[5])
    assert account["round"] == 77
    assert set(account["leaves"]) == {"round/plan"}
    assert set(account["self"]) == {"round/dispatch"}
