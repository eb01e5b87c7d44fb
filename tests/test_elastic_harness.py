"""End-to-end elastic multi-host harness (the PR-6 acceptance test, in the
style of tests/test_crash_harness.py): real processes, real SIGKILL, real
jax.distributed worlds.

- Launch a REAL 2-process jax.distributed run (2 x 4 virtual CPU devices =
  one 8-device clients mesh) through the standard `main.py train` CLI,
  SIGKILL worker 1 mid-run, and assert the survivor exits with the
  distinct EXIT_PEER_LOST code (77) — bounded by watchdog_hard_s, never a
  hang — leaving a manifest-verified checkpoint.
- Relaunch the survivors SHRUNK (one process, half the devices) with
  ``--resume auto`` and assert the experiment completes in the same run
  folder, every round recorded exactly once.
- Assert the recorded metrics for every round committed BEFORE the loss
  are bit-identical to an uninterrupted 2-process run with the same seed
  (the post-loss rounds run on a different — shrunk — mesh, whose FedAvg
  reduction order may differ in the last ulp; the committed prefix must
  not).

Subprocesses share the suite's persistent XLA compile cache, so each
launch pays import time but not a fresh compile."""
import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest
import yaml

from dba_mod_tpu import checkpoint as ckpt
from dba_mod_tpu.utils.run_guard import EXIT_PEER_LOST

REPO = Path(__file__).resolve().parent.parent

BASE_CFG = dict(
    type="mnist", lr=0.1, batch_size=16, epochs=5, no_models=8,
    number_of_total_participants=8, eta=0.8, aggregation_methods="mean",
    internal_epochs=1, is_poison=False, synthetic_data=True,
    synthetic_train_size=256, synthetic_test_size=128, momentum=0.9,
    decay=0.0005, sampling_dirichlet=False, local_eval=False,
    random_seed=5, num_devices=-1, run_name="elastic", save_model=True,
    graceful_shutdown=True, heartbeat_interval_s=0.5,
    heartbeat_timeout_s=4.0, watchdog_soft_s=60, watchdog_hard_s=120)

VOLATILE = {"time", "round_time", "dispatch_time", "finalize_time"}
# every wait on a child has a limit of its own, well inside tier-1's: a
# world's five rounds take 60-80 s on a loaded box (PR 29)
LEG_TIMEOUT = 300


def _env(world=None):
    env = dict(os.environ)
    for k in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_NUM_PROCESSES",
              "JAX_PROCESS_ID", "JAX_COORDINATOR_ADDRESS"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["TF_CPP_MIN_LOG_LEVEL"] = "3"
    if world is not None:
        coord, n, pid = world
        env["JAX_COORDINATOR_ADDRESS"] = coord
        env["JAX_NUM_PROCESSES"] = str(n)
        env["JAX_PROCESS_ID"] = str(pid)
    return env


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _write_cfg(tmp_path, name, **overrides):
    cfg = dict(BASE_CFG, run_dir=str(tmp_path / name), **overrides)
    path = tmp_path / f"{name}.yaml"
    path.write_text(yaml.dump(cfg))
    return path, cfg


def _launch_world(cfg_path, n_procs, *extra):
    coord = f"127.0.0.1:{_free_port()}"
    return [subprocess.Popen(
        [sys.executable, "-m", "dba_mod_tpu.main", "train",
         "--params", str(cfg_path), *extra],
        cwd=REPO, env=_env((coord, n_procs, pid)),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for pid in range(n_procs)]


def _rounds_recorded(run_dir: Path) -> int:
    f = run_dir / "elastic" / "round_result.csv"
    if not f.exists():
        return 0
    return max(0, len(f.read_text().strip().splitlines()) - 1)


def _metrics_rows(run_dir: Path):
    with open(run_dir / "elastic" / "metrics.jsonl") as f:
        return [json.loads(line) for line in f if line.strip()]


def _strip(row):
    return {k: v for k, v in row.items() if k not in VOLATILE}


def test_peer_loss_exit77_then_shrunk_resume_bit_identical(tmp_path):
    # ---- uninterrupted 2-process reference (same seed, separate run_dir),
    # beside the crash world: the two share nothing but the compile cache
    ref_path, ref_cfg = _write_cfg(tmp_path, "ref")
    ref_procs = _launch_world(ref_path, 2)

    # ---- crash world: SIGKILL worker 1 once >= 2 rounds committed
    crash_path, crash_cfg = _write_cfg(tmp_path, "crash")
    run_dir = Path(crash_cfg["run_dir"])
    procs = _launch_world(crash_path, 2)
    try:
        # wait for >= 2 rounds recorded AND a verified checkpoint at >= 2:
        # the kill must land after round 2's snapshot committed, so the
        # bit-identity window below provably covers two rounds
        deadline = time.monotonic() + LEG_TIMEOUT
        while time.monotonic() < deadline:
            ep = ckpt.manifest_epoch(
                run_dir / "elastic" / "model_last.pt.tar")
            if _rounds_recorded(run_dir) >= 2 and (ep or 0) >= 2:
                break
            if any(p.poll() is not None for p in procs):
                outs = [p.communicate(timeout=10)[0] for p in procs]
                pytest.fail("a worker died before the kill landed:\n"
                            + "\n".join(o[-2000:] for o in outs))
            time.sleep(0.25)
        committed = _rounds_recorded(run_dir)
        assert committed >= 2, "no 2 committed rounds within the budget"
        procs[1].kill()  # SIGKILL: no handlers, no cleanup — a lost host
        procs[1].wait(timeout=60)
        assert procs[1].returncode == -signal.SIGKILL

        # the survivor must classify the loss and exit 77 on its own,
        # bounded by watchdog_hard_s + classification slack — never hang
        out0, _ = procs[0].communicate(
            timeout=BASE_CFG["watchdog_hard_s"] + 120)
        assert procs[0].returncode == EXIT_PEER_LOST, \
            f"survivor rc={procs[0].returncode}\n{out0[-4000:]}"
        assert "peer lost" in out0
        outs = [p.communicate(timeout=LEG_TIMEOUT)[0] for p in ref_procs]
    finally:
        for p in procs + ref_procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(ref_procs, outs)):
        assert p.returncode == 0, f"ref proc {pid} rc={p.returncode}\n" \
                                  f"{out[-4000:]}"
    ref_rows = _metrics_rows(Path(ref_cfg["run_dir"]))
    assert [r["epoch"] for r in ref_rows] == list(range(1, 6))

    # a manifest-verified checkpoint is on disk — the shrunk relaunch's
    # resume point. The peer can die MID-SAVE (force=True already deleted
    # the previous model_last); the .prev protection guarantees a verified
    # fallback survives that race, so discover like the resume does.
    resume_pt = ckpt.latest_verified_checkpoint(run_dir / "elastic",
                                                quarantine=False)
    assert resume_pt is not None, \
        "no verified checkpoint survived the peer loss"
    resume_epoch = ckpt.manifest_epoch(resume_pt)
    assert resume_epoch and resume_epoch >= 2

    # ---- relaunch the survivors SHRUNK: 1 process, 4 devices
    proc = subprocess.Popen(
        [sys.executable, "-m", "dba_mod_tpu.main", "train",
         "--params", str(crash_path), "--resume", "auto"],
        cwd=REPO, env=_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        out, _ = proc.communicate(timeout=LEG_TIMEOUT)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, f"rc={proc.returncode}\n{out[-4000:]}"
    assert "final: epoch=5" in out

    # same folder, every round exactly once, final checkpoint verified
    rows = _metrics_rows(run_dir)
    assert [r["epoch"] for r in rows] == list(range(1, 6))
    ok, reason = ckpt.verify_checkpoint(
        run_dir / "elastic" / "model_last.pt.tar")
    assert ok, reason

    # ---- bit-identity of every round committed BEFORE the loss: rows up
    # to the verified resume point are the ORIGINAL 2-process world's rows
    # (the recorder stream truncates past the resume epoch and continues),
    # so they must match the uninterrupted reference byte-for-byte. Rounds
    # after the resume point re-ran on the shrunk mesh, whose FedAvg
    # reduction order may differ in the last ulp — excluded by design.
    assert resume_epoch >= 2
    for ref, got in zip(ref_rows[:resume_epoch], rows[:resume_epoch]):
        assert _strip(ref) == _strip(got), \
            f"epoch {ref['epoch']} diverged before the loss round"
