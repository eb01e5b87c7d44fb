"""Elastic multi-host layer units (PR 6): PeerHealth liveness/barrier
semantics, the watchdog's peer-lost verdict (exit 77 vs 76), the
host-level fault lane's determinism and survivor-mask composition, and
the strict no-op contract of every new knob."""
import json
import logging
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dba_mod_tpu.config import Params
from dba_mod_tpu.fl import faults as flt
from dba_mod_tpu.parallel.distributed import PeerHealth, PeerLostError
from dba_mod_tpu.utils.run_guard import (EXIT_PEER_LOST, EXIT_WATCHDOG,
                                         RunGuard, Watchdog)


# ------------------------------------------------------------- PeerHealth
def _pair(tmp_path, interval=0.05, timeout=0.2):
    a = PeerHealth(tmp_path, 0, 2, interval_s=interval, timeout_s=timeout)
    b = PeerHealth(tmp_path, 1, 2, interval_s=interval, timeout_s=timeout)
    return a, b


def test_peer_health_beats_and_sees_live_peer(tmp_path):
    a, b = _pair(tmp_path)
    a.start(), b.start()
    try:
        assert a.lost_peers() == []
        assert b.lost_peers() == []
        assert (tmp_path / "host_0.json").exists()
        assert (tmp_path / "host_1.json").exists()
    finally:
        a.stop(), b.stop()


def test_peer_health_detects_stale_peer_past_grace(tmp_path):
    a, b = _pair(tmp_path, interval=0.05, timeout=0.15)
    a.start(), b.start()
    try:
        b._stop.set()            # b's beat thread dies (the "kill")
        b._thread.join(1.0)
        # advance past staleness AND the 3x-timeout startup grace via a
        # synthetic clock: no real sleeping
        future = time.time() + 10.0
        assert a.lost_peers(now=future) == [1]
        # the boundary check raises on a stale peer
        a._started_wall -= 10.0  # move past grace in real time too
        time.sleep(0.3)          # real staleness (interval 0.05/to 0.15)
        with pytest.raises(PeerLostError, match=r"\[1\]"):
            a.check(3)
    finally:
        a._stop.set()
        b._started_wall = None   # suppress the stopped-beat write check
        a.stop(), b.stop()


def test_peer_health_stopped_beat_is_not_a_loss(tmp_path):
    a, b = _pair(tmp_path, timeout=0.15)
    a.start(), b.start()
    b.stop()                     # clean exit: final beat marked stopped
    try:
        assert a.lost_peers(now=time.time() + 10.0) == []
    finally:
        a.stop()


def test_peer_health_ignores_other_generation_files(tmp_path):
    # debris from the pre-shrink world (gen=2) must be invisible to the
    # relaunched world (world_size=3 → gen=3): within grace it is simply
    # a peer that has not beaten yet
    stale = {"pid": 1, "gen": 2, "time": time.time(),
             "boundary_epoch": 5, "ospid": 1, "stopped": False}
    (tmp_path / "host_1.json").write_text(json.dumps(stale))
    a = PeerHealth(tmp_path, 0, 3, interval_s=0.05, timeout_s=0.2)
    a.start()
    try:
        assert a._read(1) is None          # wrong generation
        assert a.lost_peers() == []        # inside startup grace
        assert 1 in a.lost_peers(now=time.time() + 10.0)  # past grace
    finally:
        a.stop()


def test_peer_health_barrier_reaches_and_times_out(tmp_path):
    a, b = _pair(tmp_path, interval=0.05, timeout=5.0)
    a.start(), b.start()
    try:
        b.beat(boundary_epoch=4)
        assert a.barrier(4, timeout=2.0) is True     # peer already there
        # peer stuck one epoch behind: bounded timeout, slow != gone
        t0 = time.monotonic()
        assert a.barrier(5, timeout=0.2) is False
        assert time.monotonic() - t0 < 2.0
    finally:
        a.stop(), b.stop()


def test_peer_health_barrier_raises_on_dead_peer(tmp_path):
    a, b = _pair(tmp_path, interval=0.05, timeout=0.15)
    a.start(), b.start()
    b._stop.set()
    b._thread.join(1.0)
    try:
        time.sleep(0.3)          # real staleness, still inside grace...
        a._started_wall -= 10.0  # ...so force past the startup grace
        with pytest.raises(PeerLostError):
            a.barrier(5, timeout=3.0)
    finally:
        b._started_wall = None
        a.stop(), b.stop()


# ------------------------------------------------- watchdog peer verdict
def test_watchdog_verdict_peer_lost_vs_generic():
    wd = Watchdog(soft_s=0.1, hard_s=0.2)
    assert wd.abort_verdict() == (EXIT_WATCHDOG, [])
    wd.peer_probe = lambda: [1]
    assert wd.abort_verdict() == (EXIT_PEER_LOST, [1])
    wd.peer_probe = lambda: []
    assert wd.abort_verdict() == (EXIT_WATCHDOG, [])
    # a probe failure must never mask the abort itself
    def boom():
        raise RuntimeError("probe broke")
    wd.peer_probe = boom
    assert wd.abort_verdict() == (EXIT_WATCHDOG, [])


def test_runguard_attach_detach_peer_health(tmp_path):
    guard = RunGuard(watchdog_soft_s=1.0, watchdog_hard_s=2.0)
    ph = PeerHealth(tmp_path, 0, 2, interval_s=0.05, timeout_s=0.2)
    guard.attach_peer_health(ph)
    assert guard.watchdog.peer_probe == ph.lost_peers
    guard.attach_peer_health(None)
    assert guard.watchdog.peer_probe is None


# ------------------------------------------------------ host-loss lane
def _fcfg(**kw):
    base = dict(enabled=True, dropout_prob=0.0, corrupt_prob=0.0,
                blowup_prob=0.0, blowup_factor=1e8, stale_prob=0.0,
                seed=7, host_loss_prob=1.0, num_hosts=4,
                host_loss_in_program=True)
    base.update(kw)
    return flt.FaultConfig(**base)


def test_host_loss_victim_is_deterministic_per_epoch():
    fcfg = _fcfg(host_loss_prob=0.5)
    key = jax.random.key(fcfg.seed)
    victims = [int(flt.host_loss_victim(fcfg, jax.random.fold_in(key, e)))
               for e in range(1, 30)]
    again = [int(flt.host_loss_victim(fcfg, jax.random.fold_in(key, e)))
             for e in range(1, 30)]
    assert victims == again                      # pure f(fault_seed, epoch)
    assert any(v == -1 for v in victims)         # some rounds lose no host
    assert any(v >= 0 for v in victims)
    assert all(-1 <= v < 4 for v in victims)


def test_host_loss_drops_exactly_the_victims_slice():
    fcfg = _fcfg(num_hosts=2, host_loss_prob=1.0)
    rng = jax.random.fold_in(jax.random.key(fcfg.seed), 3)
    counted = jnp.ones(8, bool)
    plan = flt.make_fault_plan(fcfg, rng, counted)
    victim = int(flt.host_loss_victim(fcfg, rng))
    hosts = np.asarray(flt.host_of_lane(8, 2))
    np.testing.assert_array_equal(np.asarray(plan.dropped),
                                  hosts == victim)
    assert int(plan.dropped.sum()) == 4
    # the other lanes never double-book a host-dropped client
    assert not bool((plan.corrupt & plan.dropped).any())


def test_host_loss_respects_counted_padding():
    fcfg = _fcfg(num_hosts=2, host_loss_prob=1.0)
    rng = jax.random.fold_in(jax.random.key(fcfg.seed), 3)
    counted = jnp.asarray([True] * 6 + [False] * 2)   # 2 inert pad lanes
    plan = flt.make_fault_plan(fcfg, rng, counted)
    assert not bool((plan.dropped & ~counted).any())


def test_host_loss_off_leaves_existing_plans_unchanged():
    """Strict no-op: enabling the host lane knobs at prob 0 must not
    reshuffle the client-lane draws an existing fault_seed produces."""
    rng = jax.random.fold_in(jax.random.key(11), 2)
    counted = jnp.ones(16, bool)
    legacy = flt.FaultConfig(enabled=True, dropout_prob=0.3,
                             corrupt_prob=0.2, blowup_prob=0.1,
                             blowup_factor=1e8, stale_prob=0.2, seed=11)
    with_lane = flt.FaultConfig(enabled=True, dropout_prob=0.3,
                                corrupt_prob=0.2, blowup_prob=0.1,
                                blowup_factor=1e8, stale_prob=0.2, seed=11,
                                host_loss_prob=0.0, num_hosts=4,
                                host_loss_in_program=True)
    p1 = flt.make_fault_plan(legacy, rng, counted)
    p2 = flt.make_fault_plan(with_lane, rng, counted)
    for a, b in zip(p1, p2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_host_of_lane_partitions_all_lanes():
    hosts = np.asarray(flt.host_of_lane(10, 4))
    assert hosts.min() == 0 and hosts.max() == 3
    assert (np.diff(hosts) >= 0).all()           # contiguous slices
    assert len(hosts) == 10


# ------------------------------------------------------ config contracts
_BASE = dict(type="mnist", lr=0.1, batch_size=16, epochs=2, no_models=4,
             number_of_total_participants=8, eta=0.8,
             aggregation_methods="mean", synthetic_data=True)


def test_config_rejects_bad_heartbeat_knobs():
    with pytest.raises(ValueError, match="heartbeat"):
        Params.from_dict(dict(_BASE, heartbeat_interval_s=-1))
    with pytest.raises(ValueError, match="must exceed"):
        Params.from_dict(dict(_BASE, heartbeat_interval_s=2.0,
                              heartbeat_timeout_s=1.0))
    # 0 timeout = derived default: fine
    Params.from_dict(dict(_BASE, heartbeat_interval_s=2.0))


def test_config_rejects_bad_host_loss_knobs():
    # prob range is enforced where every fault prob is: FaultConfig
    with pytest.raises(ValueError, match="fault_host_loss_prob"):
        flt.FaultConfig.from_params(
            Params.from_dict(dict(_BASE, fault_host_loss_prob=1.5)))
    with pytest.raises(ValueError, match="fault_num_hosts"):
        Params.from_dict(dict(_BASE, fault_num_hosts=-1))


def test_single_process_host_loss_without_num_hosts_disables_lane(caplog):
    """A shrunk-to-1 elastic relaunch keeps the dead world's YAML (lane on,
    no fault_num_hosts) and MUST start — the lane disables with a warning
    instead of raising, or the recovery path the lane exercises would
    crash at its final step."""
    p = Params.from_dict(dict(_BASE, fault_injection=True,
                              fault_host_loss_prob=0.5))
    # the handler on the logger itself: `setup_logging` turns propagation
    # off once a result-saving Experiment has run in this worker, whichever
    # file brought it (tests/test_checkpoint_guard.py::dba_log)
    lg = logging.getLogger("dba_mod_tpu")
    lg.addHandler(caplog.handler)
    try:
        with caplog.at_level("WARNING", logger="dba_mod_tpu"):
            fcfg = flt.FaultConfig.from_params(p)
    finally:
        lg.removeHandler(caplog.handler)
    assert not fcfg.host_loss_enabled
    assert any("fault_num_hosts" in r.message for r in caplog.records)
    ok = Params.from_dict(dict(_BASE, fault_injection=True,
                               fault_host_loss_prob=0.5,
                               fault_num_hosts=2))
    fcfg = flt.FaultConfig.from_params(ok)
    assert fcfg.host_loss_enabled and fcfg.host_loss_in_program


def test_elastic_knobs_are_noop_single_host(tmp_path):
    """Acceptance contract: heartbeat/fault knobs (off) change nothing
    single-host — no peers object, no files, identical round results."""
    from dba_mod_tpu.fl.experiment import Experiment
    cfg = dict(_BASE, synthetic_train_size=256, synthetic_test_size=128,
               sampling_dirichlet=False, local_eval=False, random_seed=1,
               run_dir=str(tmp_path / "runs"))
    base = Experiment(Params.from_dict(cfg), save_results=False)
    r_base = base.run_round(1)
    knobbed = Experiment(
        Params.from_dict(dict(cfg, heartbeat_interval_s=1.0,
                              heartbeat_timeout_s=30.0,
                              heartbeat_barrier_s=2.0,
                              fault_num_hosts=4)),
        save_results=False)
    assert knobbed.peers is None          # single-host: layer never built
    r_knob = knobbed.run_round(1)
    assert r_base["global_acc"] == r_knob["global_acc"]
    assert not (tmp_path / "runs").exists()   # no files written


def test_host_loss_e2e_single_process_survivor_mask():
    """fault_host_loss_prob=1, 2 virtual hosts → every round drops exactly
    half the cohort through the survivor mask and still aggregates."""
    from dba_mod_tpu.fl.experiment import Experiment
    cfg = dict(_BASE, no_models=8, synthetic_train_size=256,
               synthetic_test_size=128, sampling_dirichlet=False,
               local_eval=False, random_seed=1, fault_injection=True,
               fault_host_loss_prob=1.0, fault_num_hosts=2)
    e = Experiment(Params.from_dict(cfg), save_results=False)
    for ep in (1, 2):
        r = e.run_round(ep)
        assert r["n_dropped"] == 4, r
        assert np.isfinite(r["global_acc"])
        assert not r["degraded"]
