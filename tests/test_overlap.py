"""Round pipelining (overlap_eval — README "Round pipelining"): the
bit-identity contract of the split-phase sync round and the pipelined async
merge. Overlap ON must record byte-identical outputs (modulo the wall-clock
VOLATILE_KEYS) to the serial path on every lane — plain, robust retry,
health sentinel, and across a kill/--resume auto boundary — and overlap OFF
(the default) must be a strict no-op. The multi-lane and resume rehearsals
are slow-marked; tier 1 keeps one fast A/B per engine plus the contract
guards."""
import json
from pathlib import Path

import numpy as np
import pytest

import jax

from dba_mod_tpu.config import Params
from dba_mod_tpu.fl.experiment import Experiment
from dba_mod_tpu.utils.recorder import VOLATILE_KEYS, canonical_run_outputs

BASE = dict(
    type="mnist", lr=0.1, batch_size=16, epochs=3, no_models=4,
    number_of_total_participants=10, eta=0.8, aggregation_methods="mean",
    internal_epochs=1, is_poison=False, synthetic_data=True,
    synthetic_train_size=600, synthetic_test_size=256, momentum=0.9,
    decay=0.0005, sampling_dirichlet=False, local_eval=True, random_seed=1)

RECORDER_LISTS = ("train_result", "test_result", "posiontest_result",
                  "poisontriggertest_result", "weight_result",
                  "scale_temp_one_row", "scale_result")


def _run(cfg, **over):
    e = Experiment(Params.from_dict(dict(cfg, **over)), save_results=False)
    e.run()
    return e


def _rows(e):
    return [{k: v for k, v in r.items() if k not in VOLATILE_KEYS}
            for r in e.recorder._jsonl_rows]


def _bitwise_equal(a, b):
    la, lb = (jax.tree_util.tree_leaves(t) for t in (a, b))
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(la, lb))


def _assert_ab(off, on):
    assert _rows(off) == _rows(on)
    for name in RECORDER_LISTS:
        assert getattr(off.recorder, name) == getattr(on.recorder, name), \
            f"recorder.{name} differs under overlap_eval"
    assert _bitwise_equal(off.global_vars, on.global_vars)


# ------------------------------------------------------------ sync engine
def test_sync_overlap_bit_identical():
    """The tentpole contract: the split core + overlapped batteries record
    the same stream as the fused serial round, bit for bit."""
    off = _run(BASE, epochs=2)
    on = _run(BASE, epochs=2, overlap_eval=True)
    _assert_ab(off, on)
    assert on._overlap and on._overlap_rounds == 2


@pytest.mark.slow
def test_sync_overlap_robust_retry_lane():
    """Fault-injected + screened rounds retry inside the core program; the
    re-dispatched train deltas are identical per epoch, so the single eval
    dispatch after acceptance stays bit-identical — and a retry 'cancels'
    cleanly (no battery is ever in flight for a rejected attempt)."""
    cfg = dict(BASE, fault_injection=True, fault_corrupt_prob=0.4,
               screen_updates=True, fault_seed=7)
    _assert_ab(_run(cfg), _run(cfg, overlap_eval=True))


@pytest.mark.slow
def test_sync_overlap_sentinel_rollback_lane():
    """The health sentinel observes round N's merged model BEFORE round
    N+1's commit: a tight band forces rollbacks, and the rolled-back global
    battery (evaluated on the rollback target) must match the serial path
    exactly, degraded column included."""
    cfg = dict(BASE, epochs=4, model_health_check=True, health_norm_band=1e-9,
               rollback_ring=2, health_warmup_merges=1)
    off, on = _run(cfg), _run(cfg, overlap_eval=True)
    _assert_ab(off, on)
    degraded = [r["degraded"] for r in off.recorder._jsonl_rows]
    assert any(degraded)            # the lane actually exercised a rollback


@pytest.mark.slow
def test_sync_overlap_poison_lane():
    """Backdoor run: seg-epoch local batteries, poison/trigger rows, and
    the scale stream all ride the overlapped path bit-identically."""
    cfg = dict(BASE, epochs=2, internal_poison_epochs=2, is_poison=True,
               poison_label_swap=2, poisoning_per_batch=8, poison_lr=0.05,
               scale_weights_poison=4.0, adversary_list=[0, 1],
               trigger_num=2, alpha_loss=1.0,
               **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2], [0, 3]],
                  "1_poison_pattern": [[3, 0], [3, 1], [3, 2], [3, 3]],
                  "0_poison_epochs": [1, 2], "1_poison_epochs": [2]})
    _assert_ab(_run(cfg), _run(cfg, overlap_eval=True))


@pytest.mark.slow
def test_sync_overlap_resume_mid_overlap(tmp_path):
    """kill -9 between rounds of an overlapped run, --resume auto: the
    checkpoint written from dispatch-time capture resumes into a stream
    byte-identical to an uninterrupted SERIAL run (canonical view — wall
    clocks stripped)."""
    cfg = dict(BASE, epochs=5, save_model=True)
    ref = Experiment(Params.from_dict(dict(
        cfg, run_dir=str(tmp_path / "ref"))), save_results=True)
    ref.run()
    a = Experiment(Params.from_dict(dict(
        cfg, overlap_eval=True, epochs=3,
        run_dir=str(tmp_path / "ab"))), save_results=True)
    a.run()
    folder = a.folder
    del a
    b = Experiment(Params.from_dict(dict(
        cfg, overlap_eval=True, resumed_model="auto",
        run_dir=str(tmp_path / "ab"))), save_results=True)
    assert str(b.folder) == str(folder)
    b.run()
    assert canonical_run_outputs(folder) == canonical_run_outputs(ref.folder)


def test_sync_overlap_telemetry_forces_sequential():
    """Per-phase span attribution is only honest when phases do not
    overlap: with telemetry on the loop runs the split program
    SEQUENTIALLY — still bit-identical, and the overlap/ metric family is
    emitted from the serial clocks."""
    cfg = dict(BASE, epochs=2)
    off = _run(cfg)
    on = _run(cfg, overlap_eval=True, telemetry=True)
    _assert_ab(off, on)
    t = on.telemetry
    assert t.enabled and t.counter("overlap/rounds").value == 2


def test_donated_round_gate_off_on_cpu_and_under_overlap():
    """round_fn donation is only sound when nobody re-reads the donated
    buffers: never on CPU (rounds.py keeps the gate off there), never with
    the sentinel armed (rollback re-reads vars_before), never
    under overlap (the core path owns the buffers)."""
    e = Experiment(Params.from_dict(dict(BASE, epochs=1)),
                   save_results=False)
    assert jax.default_backend() == "cpu"
    assert e.engine.round_fn_donated is None
    assert e._use_donated_round is False


def test_donated_round_never_feeds_a_pipelined_checkpoint(tmp_path,
                                                          monkeypatch):
    """Found on the chip's path (PR 21): under pipeline_rounds the checkpoint
    of round N reads RoundInFlight.vars_after after round N+1 was dispatched
    — with the donated twin that is "Array has been deleted". XLA:CPU does
    donate, so telling the engine it is not on the CPU reproduces it here:
    the gate must fall back to round_fn exactly when a pipelined run saves,
    and donate (bit-identically) when it does not."""
    monkeypatch.setattr(jax, "default_backend", lambda: "not-cpu")
    cfg = dict(BASE, pipeline_rounds=True, run_dir=str(tmp_path / "runs"),
               save_on_epochs=[2])
    saving = Experiment(Params.from_dict(dict(cfg, save_model=True)),
                        save_results=True)
    assert saving.engine.round_fn_donated is not None
    assert saving._use_donated_round is False
    saving.run()
    assert (saving.folder / "model_last.pt.tar.epoch_2").is_dir()
    plain = _run(cfg)
    assert plain._use_donated_round is True
    assert plain.engine.round_fn_donated._cache_size() == 1
    assert plain.engine.round_fn._cache_size() == 0
    _assert_ab(saving, plain)


# ----------------------------------------------------------- async engine
def test_async_overlap_bit_identical():
    """Merge pipelining: host finalize of merge S hidden behind step S+1's
    fill/merge — recorded stream and final model bit-identical."""
    cfg = dict(BASE, mode="async", buffer_k=3,
               staleness_weighting="polynomial", staleness_alpha=0.5,
               arrival_rate=3.0, arrival_jitter=0.7, straggler_tail=0.25,
               straggler_factor=6.0, async_steps=4)
    off, on = _run(cfg), _run(cfg, overlap_eval=True)
    _assert_ab(off, on)


@pytest.mark.slow
def test_async_overlap_selfhealing_lane():
    """Deadline merges, TTL expiry, backpressure flushes, and fault retry
    all pipeline bit-identically (deferred wave rows replay in resolution
    order; the sentinel ring commits at dispatch)."""
    cfg = dict(BASE, mode="async", buffer_k=3, async_steps=5,
               arrival_jitter=0.5, fault_injection=True,
               fault_drop_prob=0.2, fault_corrupt_prob=0.3,
               screen_updates=True, fault_seed=7, arrival_ttl_v=2.0,
               merge_timeout_v=1.5, merge_min_k=1, max_outstanding_waves=3,
               starvation_policy="carry")
    _assert_ab(_run(cfg), _run(cfg, overlap_eval=True))
    cfg = dict(BASE, mode="async", buffer_k=3, async_steps=5,
               model_health_check=True, health_norm_band=1.5,
               rollback_ring=2, health_warmup_merges=1)
    _assert_ab(_run(cfg), _run(cfg, overlap_eval=True))


@pytest.mark.slow
def test_async_overlap_resume_mid_overlap(tmp_path):
    """Kill between pipelined merges, --resume auto: the dispatch-time
    snapshot restores heap/buffer/cohorts into a stream byte-identical to
    the uninterrupted serial run."""
    cfg = dict(BASE, epochs=6, save_model=True, mode="async", buffer_k=2,
               arrival_rate=2.0, arrival_jitter=0.6, straggler_tail=0.25,
               straggler_factor=4.0, staleness_weighting="polynomial",
               async_steps=8, random_seed=3)
    ref = Experiment(Params.from_dict(dict(
        cfg, run_dir=str(tmp_path / "ref"))), save_results=True)
    ref.run()
    a = Experiment(Params.from_dict(dict(
        cfg, overlap_eval=True, async_steps=4,
        run_dir=str(tmp_path / "ab"))), save_results=True)
    a.run()
    folder = a.folder
    del a
    b = Experiment(Params.from_dict(dict(
        cfg, overlap_eval=True, resumed_model="auto",
        run_dir=str(tmp_path / "ab"))), save_results=True)
    assert str(b.folder) == str(folder)
    assert (b._resume_aux or {}).get("async_state") is not None
    b.run()
    assert canonical_run_outputs(folder) == canonical_run_outputs(ref.folder)


def test_async_pipeline_gates():
    """The async pipeline stands down where its contracts cannot hold:
    telemetry's split-phase mode, and the poisoned LOAN probe (whose
    last-finalized-backdoor-acc read would go one merge more stale)."""
    from dba_mod_tpu.fl.async_rounds import AsyncDriver
    e = Experiment(Params.from_dict(dict(
        BASE, mode="async", buffer_k=3, async_steps=2, overlap_eval=True,
        telemetry=True)), save_results=False)
    assert AsyncDriver(e)._pipeline is False
    e2 = Experiment(Params.from_dict(dict(
        BASE, mode="async", buffer_k=3, async_steps=2, overlap_eval=True)),
        save_results=False)
    d = AsyncDriver(e2)
    assert d._pipeline is True
    d.run_steps(2)                  # drains its own in-flight handle
    assert d.stats()["pipelined_merges"] == 2
