"""Defense-forensics layer (utils/forensics.py + the ForensicStats payload
slot in fl/rounds.py).

Coverage:
  1. schema golden — client_forensics.csv column names and per-column dtypes
     are pinned (downstream notebooks parse by name);
  2. strict no-op when off — `forensics: false` writes no forensic files and
     the recorded metrics trajectory is byte-identical to a forensics-on run
     (the flag must not perturb the round math);
  3. screening forensics — injected-fault runs mark quarantined clients with
     verdict 0 and the right reason code, consistent with the round's
     robust counters;
  4. e2e FoolsGold sybil — two adversaries submitting the same trigger get
     measurably lower aggregation weights than benign clients in the
     emitted CSV (the ISSUE acceptance gate);
  5. the `report` renderer produces a self-contained HTML round-audit;
  6. telemetry parity — `telemetry: true` keeps the fused round program,
     whose ForensicStats slot fills the same forensic record.

Experiment builds dominate the wall clock here, so the benign-FedAvg and
sybil-FoolsGold runs are module-scoped fixtures shared by every test that
only READS their artifacts.
"""
import csv
import json
import math

import numpy as np
import pytest

from dba_mod_tpu.config import Params
from dba_mod_tpu.fl.experiment import Experiment
from dba_mod_tpu.fl.rounds import REASON_NAMES
from dba_mod_tpu.utils.forensics import FORENSICS_HEADER

BASE = dict(
    type="mnist", lr=0.1, batch_size=16, epochs=6, no_models=4,
    number_of_total_participants=10, eta=0.8, aggregation_methods="mean",
    internal_epochs=1, is_poison=False, synthetic_data=True,
    synthetic_train_size=600, synthetic_test_size=256, momentum=0.9,
    decay=0.0005, sampling_dirichlet=False, local_eval=False, random_seed=1)

# the forensics-smoke geometry: two sybils sharing one trigger pattern and
# schedule with full-poison batches — FoolsGold's detection target.
# internal_poison_epochs kept at 2 (epochs_max sizes the compiled round
# program; 4 triples this module's wall clock for no extra signal).
SYBIL = dict(
    BASE, epochs=3, aggregation_methods="foolsgold", is_poison=True,
    local_eval=True, internal_poison_epochs=2, poisoning_per_batch=16,
    poison_label_swap=2, poison_lr=0.05, scale_weights_poison=1.0,
    adversary_list=[0, 1], trigger_num=2, alpha_loss=1.0,
    is_random_adversary=False, sampling_dirichlet=True, dirichlet_alpha=0.5,
    synthetic_train_size=400, synthetic_test_size=128,
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2], [0, 3]],
       "1_poison_pattern": [[0, 0], [0, 1], [0, 2], [0, 3]],
       "0_poison_epochs": [1, 2, 3], "1_poison_epochs": [1, 2, 3]})


def _run_to_folder(tmp_path, cfg, rounds, sub="run"):
    p = Params.from_dict(dict(cfg, run_dir=str(tmp_path / sub)))
    e = Experiment(p)
    results = [e.run_round(i) for i in range(1, rounds + 1)]
    return e, results


def _read_csv(folder):
    with open(folder / "client_forensics.csv", newline="") as f:
        rows = list(csv.reader(f))
    return rows[0], rows[1:]


@pytest.fixture(scope="module")
def mean_run(tmp_path_factory):
    """Benign FedAvg, forensics on, 2 rounds — shared read-only."""
    tmp = tmp_path_factory.mktemp("forensics_mean")
    return _run_to_folder(tmp, dict(BASE, forensics=True), 2)


@pytest.fixture(scope="module")
def sybil_run(tmp_path_factory):
    """FoolsGold sybil attack, forensics on, 3 rounds — shared read-only."""
    tmp = tmp_path_factory.mktemp("forensics_sybil")
    return _run_to_folder(tmp, dict(SYBIL, forensics=True), 3)


# ------------------------------------------------------------ schema golden
def test_schema_golden(mean_run):
    """Column names and dtypes of client_forensics.csv are STABLE."""
    e, _ = mean_run
    header, rows = _read_csv(e.folder)
    assert header == FORENSICS_HEADER
    assert len(rows) == 2 * 4  # rounds x clients, one row each
    int_cols = ["epoch", "client", "participant_id", "adversary", "verdict"]
    float_cols = ["delta_norm", "recv_norm", "cosine_to_agg", "agg_weight",
                  "fg_max_sim", "rfa_distance", "poison_acc"]
    for row in rows:
        rec = dict(zip(header, row))
        for c in int_cols:
            assert rec[c] == str(int(rec[c])), (c, rec[c])
        for c in float_cols:  # float-typed: blank (n/a) or parseable
            if rec[c] != "":
                float(rec[c])
        assert rec["reason"] in REASON_NAMES.values()
        assert rec["name"] != ""
    # benign FedAvg: every client aggregated, no defense weights, no battery
    for row in rows:
        rec = dict(zip(header, row))
        assert rec["verdict"] == "1" and rec["reason"] == "ok"
        assert rec["agg_weight"] == "" and rec["poison_acc"] == ""


def test_jsonl_round_records(mean_run):
    e, _ = mean_run
    recs = [json.loads(l) for l in
            (e.folder / "forensics.jsonl").read_text().splitlines()]
    assert [r["epoch"] for r in recs] == [1, 2]
    for r in recs:
        assert r["aggregation"] == "mean"
        assert len(r["clients"]) == 4 == len(r["delta_norm"])
        assert r["n_quarantined"] == 0 and not r["degraded"]
        assert r["oracle_calls"] == 1  # no Weiszfeld under FedAvg
        # jsonl must be valid JSON end-to-end: no bare NaN tokens
        assert all(v is None or math.isfinite(v) for v in r["delta_norm"])


# -------------------------------------------------- forensics off: no-op
def test_off_is_strict_noop_and_bit_identical(tmp_path, mean_run):
    """`forensics: false` (the default) writes no forensic files, and the
    flag itself must not perturb the trajectory: recorded metrics from an
    off run and an on run are byte-identical (timing columns excluded)."""
    e_on, r_on = mean_run
    e_off, r_off = _run_to_folder(tmp_path, dict(BASE), 2, "off")
    assert e_off.forensics_writer is None
    assert not (e_off.folder / "forensics.jsonl").exists()
    assert not (e_off.folder / "client_forensics.csv").exists()
    for name in ("train_result.csv", "test_result.csv"):
        assert ((e_off.folder / name).read_bytes()
                == (e_on.folder / name).read_bytes()), name
    assert ([r["global_acc"] for r in r_off]
            == [r["global_acc"] for r in r_on])


# ------------------------------------------------- screening verdict rows
def test_quarantined_clients_marked(tmp_path):
    """Injected NaN payloads: the forensic rows carry verdict 0 with reason
    'nonfinite', consistent with the round's robust counters."""
    e, results = _run_to_folder(
        tmp_path, dict(BASE, forensics=True, fault_injection=True,
                       fault_corrupt_prob=0.4, fault_seed=3), 3)
    header, rows = _read_csv(e.folder)
    recs = [dict(zip(header, r)) for r in rows]
    quarantined = [r for r in recs if r["verdict"] == "0"]
    assert quarantined, "corrupt_prob=0.4 over 3x4 lanes must quarantine"
    assert all(r["reason"] == "nonfinite" for r in quarantined)
    assert (len(quarantined)
            == sum(r["n_quarantined"] for r in results))
    per_epoch = {int(r["epoch"]): 0 for r in recs}
    for r in quarantined:
        per_epoch[int(r["epoch"])] += 1
    for res in results:
        assert per_epoch[res["epoch"]] == res["n_quarantined"]


def test_dropped_clients_marked(tmp_path):
    """Total dropout: every row is verdict 0 / reason 'dropped' and the
    round-level record carries the degradation."""
    e, results = _run_to_folder(
        tmp_path, dict(BASE, forensics=True, fault_injection=True,
                       fault_dropout_prob=1.0), 1)
    header, rows = _read_csv(e.folder)
    recs = [dict(zip(header, r)) for r in rows]
    assert all(r["verdict"] == "0" and r["reason"] == "dropped"
               for r in recs)
    jl = [json.loads(l) for l in
          (e.folder / "forensics.jsonl").read_text().splitlines()]
    assert jl[0]["degraded"] and jl[0]["n_quarantined"] == 4


# ----------------------------------------------------- e2e FoolsGold sybil
def test_foolsgold_sybil_attackers_get_lower_weights(sybil_run):
    """ISSUE acceptance gate: attacker rows in the emitted CSV show
    measurably lower FoolsGold weights than benign rows."""
    e, _ = sybil_run
    header, rows = _read_csv(e.folder)
    recs = [dict(zip(header, r)) for r in rows]
    att = [float(r["agg_weight"]) for r in recs if r["adversary"] == "1"]
    ben = [float(r["agg_weight"]) for r in recs if r["adversary"] == "0"]
    assert att and ben
    assert np.mean(att) < np.mean(ben) - 0.3, (np.mean(att), np.mean(ben))
    # the similarity evidence behind the verdict is recorded too
    sims = [float(r["fg_max_sim"]) for r in recs
            if r["adversary"] == "1" and r["fg_max_sim"] != ""
            and math.isfinite(float(r["fg_max_sim"]))]
    assert max(sims) > 0.9  # sybils are near-identical in feature space
    # poison battery columns populated for the poisoning clients
    assert any(r["poison_acc"] != "" for r in recs)


def test_report_html(sybil_run):
    e, _ = sybil_run
    from dba_mod_tpu.utils.forensics import write_report
    out = write_report(e.folder)
    html = out.read_text()
    assert html.startswith("<!DOCTYPE html>")
    assert "<svg" in html and "suspicion" in html
    assert "foolsgold" in html
    # self-contained: no external fetches (the SVG xmlns URI is a
    # namespace identifier, not a fetch)
    stripped = html.replace("http://www.w3.org/2000/svg", "")
    assert "http://" not in stripped and "https://" not in stripped


# ------------------------------------------ fused dispatch under telemetry
def test_split_dispatch_fills_forensics(tmp_path, mean_run):
    """`telemetry: true` no longer splits the round: the fused program's own
    ForensicStats slot fills the record (the standalone forensic_fn is
    sequential_debug's alone), with the numbers of a telemetry-off run."""
    from dba_mod_tpu.utils import telemetry as tel
    try:
        e, _ = _run_to_folder(
            tmp_path, dict(BASE, forensics=True, telemetry=True), 2)
    finally:
        tel.configure(enabled=False)
    assert e.engine.round_fn._cache_size() == 1
    assert e.engine.forensic_fn._cache_size() == 0
    assert e.engine.train_fn._cache_size() == 0  # no split-phase program
    header, rows = _read_csv(e.folder)
    assert (header, rows) == _read_csv(mean_run[0].folder)
    assert len(rows) == 2 * 4
    recs = [dict(zip(header, r)) for r in rows]
    assert all(r["verdict"] == "1" and r["reason"] == "ok" for r in recs)
    assert all(float(r["recv_norm"]) > 0 for r in recs)


def test_in_memory_writer_without_folder():
    """save_results=False (the bench path): rows accumulate in memory, no
    files are written, save() is a no-op."""
    e = Experiment(Params.from_dict(dict(BASE, forensics=True)),
                   save_results=False)
    e.run_round(1)
    w = e.forensics_writer
    assert w is not None and w.folder is None
    assert len(w.rows) == 4 and len(w.round_rows) == 1
