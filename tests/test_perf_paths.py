"""Performance-path semantics: the bench-mode knobs (round pipelining,
clients-per-device stacking) must not change numerics.

These are the TPU-native throughput levers (no reference counterpart — the
reference's sequential loop has nothing to pipeline); the contract tested
here is exact-parity with the plain path."""
import numpy as np
import pytest

import jax

from dba_mod_tpu.config import Params
from dba_mod_tpu.fl.experiment import Experiment

# Dirichlet sampling → unequal client sizes → per-round max steps varies
BASE = dict(
    type="mnist", lr=0.1, batch_size=8, epochs=4, no_models=4,
    number_of_total_participants=12, eta=0.8, aggregation_methods="mean",
    internal_epochs=2, is_poison=False, synthetic_data=True,
    synthetic_train_size=600, synthetic_test_size=128, momentum=0.9,
    decay=0.0005, sampling_dirichlet=True, dirichlet_alpha=0.5,
    local_eval=False, random_seed=3)


def _params_of(e):
    return np.concatenate([np.asarray(l).ravel() for l in
                           jax.tree_util.tree_leaves(e.global_vars.params)])


def test_pipelined_rounds_bitexact():
    """Depth-1 round pipelining (fetch N while computing N+1) reorders only
    host transfers, never device math."""
    e_p = Experiment(Params.from_dict(dict(BASE, pipeline_rounds=True,
                                           local_eval=True)),
                     save_results=False)
    e_n = Experiment(Params.from_dict(dict(BASE, local_eval=True)),
                     save_results=False)
    last_p = e_p.run()
    last_n = e_n.run()
    assert last_p["epoch"] == last_n["epoch"]
    assert last_p["global_acc"] == last_n["global_acc"]
    np.testing.assert_array_equal(_params_of(e_p), _params_of(e_n))
    assert e_p.recorder.train_result == e_n.recorder.train_result
    assert len(e_p.recorder.test_result) == len(e_n.recorder.test_result)


def test_wide_round_stacks_clients_per_device():
    """28 selected clients on the 8-device mesh → 4 stacked clients per
    device, 4 of the 32 lanes inert pads (SURVEY §7.1 step 10): the clients
    axis is a capacity axis, not capped at the device count. (100 clients,
    13 a device and the same 4 pads, until PR 29: 207 s for the same two
    assertions, every device computing every lane's grouped convolution.)"""
    assert jax.device_count() >= 8
    cfg = dict(BASE, no_models=28, number_of_total_participants=36,
               synthetic_train_size=450, internal_epochs=1, num_devices=8,
               epochs=1)
    e = Experiment(Params.from_dict(cfg), save_results=False)
    r = e.run_round(1)
    assert np.isfinite(r["global_acc"])
    # all 28 real clients trained and were recorded; the 4 inert pads not
    assert len({row[0] for row in e.recorder.train_result}) == 28
    # and the full-width round matches the same round without a mesh
    e1 = Experiment(Params.from_dict(dict(cfg, num_devices=0)),
                    save_results=False)
    r1 = e1.run_round(1)
    assert abs(r1["global_acc"] - r["global_acc"]) < 0.5
    np.testing.assert_allclose(_params_of(e), _params_of(e1), atol=1e-5)
