"""Mesh coverage for the flagship paths (VERDICT r3 ask 3): CIFAR-BN rounds,
FoolsGold, and RFA on the clients mesh (8 virtual devices; 4 for the two
ResNet rounds) must reproduce single-device
numerics — batch_stats trees through GSPMD, the FoolsGold [C, L] feature
all-gather + participant-id memory scatter, and RFA's per-iteration distance
collectives all run sharded here.

Tolerance rationale (VERDICT r3 ask 8): after ONE round the only difference
between the mesh and single-device programs is collective reduction order
(per-client training is device-local and bit-identical), so round-1
comparisons are tight. Over multiple rounds those last-ulp differences are
amplified chaotically through ReLU boundaries — the same measured behavior
as the cross-framework A/B (PARITY_AB.md) — so multi-round comparisons use
a drift envelope plus the accuracy bound."""
import numpy as np
import pytest

import jax

import trip_count_cases as tc
from dba_mod_tpu.config import Params
from dba_mod_tpu.fl.experiment import Experiment

MNIST8 = dict(
    type="mnist", lr=0.1, batch_size=16, epochs=4, no_models=8,
    number_of_total_participants=16, eta=0.8, aggregation_methods="mean",
    internal_epochs=1, internal_poison_epochs=2, is_poison=True,
    synthetic_data=True, synthetic_train_size=640, synthetic_test_size=256,
    momentum=0.9, decay=0.0005, sampling_dirichlet=False, local_eval=False,
    poison_label_swap=2, poisoning_per_batch=8, poison_lr=0.05,
    scale_weights_poison=3.0, adversary_list=[0], trigger_num=1,
    alpha_loss=1.0, random_seed=1,
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2], [0, 3]],
       "0_poison_epochs": [1, 2, 3]})

CIFAR8 = dict(
    type="cifar", lr=0.1, batch_size=8, epochs=2, no_models=8,
    number_of_total_participants=8, eta=0.8, aggregation_methods="mean",
    internal_epochs=1, internal_poison_epochs=1, is_poison=True,
    synthetic_data=True, synthetic_train_size=128, synthetic_test_size=128,
    momentum=0.9, decay=0.0005, sampling_dirichlet=False, local_eval=True,
    poison_label_swap=2, poisoning_per_batch=4, poison_lr=0.05,
    scale_weights_poison=2.0, adversary_list=[0], trigger_num=1,
    alpha_loss=1.0, random_seed=1,
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2]],
       "0_poison_epochs": [1, 2]})


LOAN8 = dict(
    type="loan", lr=0.05, poison_lr=0.05, batch_size=64, epochs=2,
    no_models=8, number_of_total_participants=12, eta=0.8,
    aggregation_methods="mean", internal_epochs=1, internal_poison_epochs=2,
    is_poison=True, synthetic_data=True, momentum=0.9, decay=0.0005,
    sampling_dirichlet=False, local_eval=True, poison_label_swap=7,
    poisoning_per_batch=16, poison_step_lr=True, scale_weights_poison=2.0,
    trigger_num=2, alpha_loss=1.0, random_seed=1,
    adversary_list=["AK", "AL"],
    **{"0_poison_trigger_names": ["num_tl_120dpd_2m", "num_tl_90g_dpd_24m"],
       "0_poison_trigger_values": [10, 80],
       "1_poison_trigger_names": ["pub_rec_bankruptcies", "pub_rec"],
       "1_poison_trigger_values": [20, 100],
       "0_poison_epochs": [1, 2], "1_poison_epochs": [2]})


def _one_device(cfg, wide_from=None):
    """The single-device Experiment; `wide_from` in the place of the
    engine's rule (fl/rounds.py::wide_from_of) where given."""
    return tc.make_experiment(0, wide_from=wide_from, cfg=cfg)


def _pair(cfg, devices=8):
    """The subject here is the sharding, so both sides run the client step's
    full-width loop: the mesh has no other, and the single device is built
    with `wide_from = 2` (no lane of these equal-split rounds has a tail).
    Left to its rule the single-device engine runs a convolutional model's
    lanes as width-1 jobs (PR 31), and a width-1 ResNet step is a plain
    convolution where the stacked one is grouped: the two round differently
    on XLA:CPU (test_every_lane_a_job_matches_the_full_width_loop below)."""
    e1 = _one_device(cfg, wide_from=2)
    e8 = Experiment(Params.from_dict(dict(cfg, num_devices=devices)),
                    save_results=False)
    assert e8.mesh is not None and e8.mesh.devices.size == devices
    assert (e1.engine.wide_from, e8.engine.wide_from) == (2, 1)
    return e1, e8


def _flat(tree):
    return np.concatenate([np.asarray(l, np.float64).ravel()
                           for l in jax.tree_util.tree_leaves(tree)])


def test_cifar_bn_round_on_mesh_matches_single_device(narrow_resnets):
    """The flagship model (BN ResNet) with the full local battery, sharded:
    batch_stats trees flow through the GSPMD round; one round is tight.

    Eight clients on FOUR devices since PR 29. With one client a device (the
    8-device mesh, as this test ran before) XLA:CPU compiles a plain
    convolution where the single-device program has a grouped one: their f32
    sums differ at ~1e-6, ReLU gates flip, and what could be asserted was an
    envelope of that chaos (atol 5e-3 on the state, 3.0 on accuracies;
    measured 2.5e-3 to 2.8e-3 over three seeds at conftest's narrow widths,
    the round twice as long). With two clients a device both programs run
    grouped convolutions and agree to FedAvg's reduction order: params 7.5e-9,
    batch_stats 6.0e-8, accuracies equal, at each of three seeds — so a
    sharding fault of 1e-4 now fails. The one-client-a-device layout stays
    covered where it is near-bit (MNIST, LOAN and the defenses below)."""
    e1, e8 = _pair(CIFAR8, devices=4)
    r1 = e1.run_round(1)
    r8 = e8.run_round(1)
    assert np.isfinite(r8["global_acc"])
    np.testing.assert_allclose(_flat(e1.global_vars.params),
                               _flat(e8.global_vars.params), atol=1e-5)
    np.testing.assert_allclose(_flat(e1.global_vars.batch_stats),
                               _flat(e8.global_vars.batch_stats), atol=1e-5)
    # 128-sample eval ⇒ 0.8% per sample: within one sample (measured equal)
    assert abs(r1["global_acc"] - r8["global_acc"]) < 1.0
    assert abs(r1["backdoor_acc"] - r8["backdoor_acc"]) < 1.0
    # the sharded local battery produced rows for every client
    assert len({row[0] for row in e8.recorder.test_result
                if row[0] != "global"}) == 8


def test_every_lane_a_job_matches_the_full_width_loop(narrow_resnets):
    """The BN ResNet's round with every lane a width-1 job (what the
    engine's rule gives a convolutional model on one device) against the
    same round through the full-width loop, on one device. Not to the bit,
    as LeNet's is (tests/test_client_step_trip_count.py): XLA:CPU compiles
    a plain convolution at width 1 and a grouped one for the stacked lanes,
    their f32 sums differ at ~1e-6 a step and ReLU gates flip — the envelope
    the one-client-a-device mesh had (above). Measured here (PR 31): params
    6.8e-5, batch_stats 6.0e-8, accuracies equal."""
    jobs, wide = _one_device(CIFAR8), _one_device(CIFAR8, wide_from=2)
    assert (jobs.engine.wide_from, wide.engine.wide_from) == (9, 2)
    rj, rw = jobs.run_round(1), wide.run_round(1)
    dp = np.abs(_flat(jobs.global_vars.params)
                - _flat(wide.global_vars.params)).max()
    db = np.abs(_flat(jobs.global_vars.batch_stats)
                - _flat(wide.global_vars.batch_stats)).max()
    print(f"every lane a job against the full-width loop: params {dp:.3g}, "
          f"batch_stats {db:.3g}")
    assert 0 < dp < 1e-3 and db < 1e-5
    assert abs(rj["global_acc"] - rw["global_acc"]) < 1.0
    assert abs(rj["backdoor_acc"] - rw["backdoor_acc"]) < 1.0
    assert len({row[0] for row in jobs.recorder.test_result
                if row[0] != "global"}) == 8


TINY8 = dict(
    type="tiny-imagenet-200", lr=0.1, batch_size=4, epochs=1,
    no_models=8, number_of_total_participants=8, eta=0.8,
    aggregation_methods="mean", internal_epochs=1, internal_poison_epochs=1,
    is_poison=True, synthetic_data=True, synthetic_train_size=64,
    synthetic_test_size=64, momentum=0.9, decay=0.0005,
    sampling_dirichlet=False, local_eval=False, poison_label_swap=3,
    poisoning_per_batch=2, poison_lr=0.05, scale_weights_poison=2.0,
    adversary_list=[0], trigger_num=1, alpha_loss=1.0, random_seed=1,
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2]],
       "0_poison_epochs": [1]})


def test_tiny_round_on_mesh_matches_single_device(narrow_resnets):
    """Tiny-ImageNet on the sharded clients axis — completes the
    workload×mesh matrix (MNIST/CIFAR-BN/LOAN covered above): the imagenet
    stem + max pool + global-average-pool graph with batch_stats trees
    through GSPMD. One round, two clients a device as in the CIFAR-BN test
    and for its reason: bounds of 2e-2 (params), 5e-3 (batch_stats) and 4.0
    (accuracies) at one client a device; here, at three seeds, 1.5e-8,
    1.2e-7 and equal accuracies."""
    e1, e8 = _pair(TINY8, devices=4)
    r1 = e1.run_round(1)
    r8 = e8.run_round(1)
    assert np.isfinite(r8["global_acc"])
    np.testing.assert_allclose(_flat(e1.global_vars.params),
                               _flat(e8.global_vars.params), atol=1e-5)
    np.testing.assert_allclose(_flat(e1.global_vars.batch_stats),
                               _flat(e8.global_vars.batch_stats), atol=1e-5)
    # 64-sample eval ⇒ 1.6% per sample: within one sample (measured equal)
    assert abs(r1["global_acc"] - r8["global_acc"]) < 2.0
    assert abs(r1["backdoor_acc"] - r8["backdoor_acc"]) < 2.0


def test_loan_round_on_mesh_matches_single_device():
    """LOAN on the sharded clients axis — the one workload whose mesh path
    had no coverage: ragged per-state shards fetched by (slot, idx) gathers,
    feature-trigger stamping, lane-keyed dropout streams, and the blocking
    adaptive poison-LR probe (round 2 probes the round-1 planted backdoor,
    loan_train.py:67-75) must reproduce single-device numerics."""
    e1, e8 = _pair(LOAN8)
    for ep in (1, 2):
        r1 = e1.run_round(ep)
        r8 = e8.run_round(ep)
        assert np.isfinite(r8["global_acc"])
        assert abs(r1["global_acc"] - r8["global_acc"]) < 1.0
        assert abs(r1["backdoor_acc"] - r8["backdoor_acc"]) < 1.0
    # MLP matmul reductions reorder between the one-device [8·B] batch and
    # the per-device [B] kernels; two rounds of drift stay tiny
    np.testing.assert_allclose(_flat(e1.global_vars.params),
                               _flat(e8.global_vars.params), atol=1e-4)
    # every one of round 2's 8 sharded clients produced its local row
    assert len({row[0] for row in e8.recorder.test_result
                if row[0] != "global" and row[1] == 2}) == 8


@pytest.mark.parametrize("method", ["foolsgold", "geom_median"])
def test_defenses_on_mesh_match_single_device(method):
    """FoolsGold (feature all-gather + id-keyed memory scatter) and RFA
    (Weiszfeld distance collectives) over the sharded clients axis."""
    cfg = dict(MNIST8, aggregation_methods=method)
    e1, e8 = _pair(cfg)
    r1 = e1.run_round(1)
    r8 = e8.run_round(1)
    assert np.isfinite(r8["global_acc"])
    np.testing.assert_allclose(_flat(e1.global_vars.params),
                               _flat(e8.global_vars.params), atol=1e-4)
    # defense weight/alpha rows agree per client
    w1 = e1.recorder.weight_result
    w8 = e8.recorder.weight_result
    assert w1[0] == w8[0]                      # same client names
    np.testing.assert_allclose(w1[1], w8[1], atol=1e-4)  # wv
    np.testing.assert_allclose(w1[2], w8[2], atol=1e-3)  # alphas/distances
    if method == "foolsgold":
        # cross-round memory accumulated identically (id-keyed scatter)
        np.testing.assert_allclose(np.asarray(e1.fg_state.memory),
                                   np.asarray(e8.fg_state.memory),
                                   atol=1e-5)
        r1b = e1.run_round(2)
        r8b = e8.run_round(2)
        assert abs(r1b["global_acc"] - r8b["global_acc"]) < 1.0
