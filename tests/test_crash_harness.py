"""End-to-end crash/preemption harness (the PR-4 acceptance tests): real
processes, real signals.

- `kill -9` a run mid-flight, relaunch with ``--resume auto``, and assert
  the completed metrics.jsonl trajectory is bit-identical (modulo
  wall-clock fields) to an uninterrupted run with the same seed — the
  integrity manifests guarantee the resume point is a *verified*
  checkpoint, and the full-state sidecar guarantees the replayed rounds
  land on the same trajectory.
- SIGTERM a run with ``graceful_shutdown: true`` and assert it exits
  within one round boundary with the distinct EXIT_INTERRUPTED code and a
  verified checkpoint on disk.

Subprocesses share the suite's persistent XLA compile cache via env vars,
so each launch pays import time but not a fresh compile."""
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest
import yaml

from dba_mod_tpu import checkpoint as ckpt
from dba_mod_tpu.utils.run_guard import EXIT_INTERRUPTED

REPO = Path(__file__).resolve().parent.parent

BASE_CFG = dict(
    type="mnist", lr=0.1, batch_size=16, epochs=8, no_models=4,
    number_of_total_participants=10, eta=0.8, aggregation_methods="mean",
    internal_epochs=1, is_poison=False, synthetic_data=True,
    synthetic_train_size=600, synthetic_test_size=256, momentum=0.9,
    decay=0.0005, sampling_dirichlet=False, local_eval=False, random_seed=5,
    save_model=True, graceful_shutdown=True)

VOLATILE = {"time", "round_time", "dispatch_time", "finalize_time"}


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the children are `python -m dba_mod_tpu.main`, which enables the same
    # persistent compile cache as tests/conftest.py (utils/compile_cache.py)
    return env


def _write_cfg(tmp_path, name, **overrides):
    cfg = dict(BASE_CFG, run_dir=str(tmp_path / name), **overrides)
    path = tmp_path / f"{name}.yaml"
    path.write_text(yaml.dump(cfg))
    return path, cfg


def _launch(cfg_path, *extra):
    return subprocess.Popen(
        [sys.executable, "-m", "dba_mod_tpu.main", "train",
         "--params", str(cfg_path), *extra],
        cwd=REPO, env=_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)


# every wait on a child has a limit of its own, well inside tier-1's: a
# leg of these tests takes 30-60 s on a loaded box (PR 29)
LEG_TIMEOUT = 300


def _finish(proc, timeout=LEG_TIMEOUT):
    try:
        out, _ = proc.communicate(timeout=timeout)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, f"rc={proc.returncode}\n{out}"
    return out


def _run_to_completion(cfg_path, *extra):
    return _finish(_launch(cfg_path, *extra))


def _rounds_recorded(run_dir: Path) -> int:
    rows = 0
    for f in run_dir.glob("mnist_*/round_result.csv"):
        rows = max(rows, len(f.read_text().strip().splitlines()) - 1)
    return rows


def _wait_for_rounds(proc, run_dir: Path, n: int,
                     timeout=LEG_TIMEOUT) -> int:
    """Poll until >= n data rows are committed (or the process exits)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        done = _rounds_recorded(run_dir)
        if done >= n or proc.poll() is not None:
            return done
        time.sleep(0.2)
    return _rounds_recorded(run_dir)


def _metrics_rows(run_dir: Path):
    folders = sorted(run_dir.glob("mnist_*"))
    assert len(folders) == 1, f"expected one run folder, got {folders}"
    with open(folders[0] / "metrics.jsonl") as f:
        return [json.loads(line) for line in f if line.strip()]


def _strip(row):
    return {k: v for k, v in row.items() if k not in VOLATILE}


def test_kill9_then_auto_resume_bit_identical_trajectory(tmp_path):
    base_path, base_cfg = _write_cfg(tmp_path, "base")
    crash_path, crash_cfg = _write_cfg(tmp_path, "crash")

    # uninterrupted reference run (same seed, separate run_dir), beside
    # the crash run: the two share nothing but the compile cache
    ref_proc = _launch(base_path)
    try:
        # crash run: SIGKILL once >= 2 rounds have committed
        proc = _launch(crash_path)
        run_dir = Path(crash_cfg["run_dir"])
        done = _wait_for_rounds(proc, run_dir, 2)
        if proc.poll() is not None:  # pragma: no cover — box far too fast
            pytest.skip("run finished before the kill landed")
        proc.kill()  # SIGKILL: no handlers, no cleanup, no atexit
        proc.wait(timeout=60)
        assert proc.returncode == -signal.SIGKILL
        assert done >= 2

        # auto-resume: same config + --resume auto must finish the job
        out = _run_to_completion(crash_path, "--resume", "auto")
        assert "final: epoch=8" in out

        _finish(ref_proc)
    finally:
        if ref_proc.poll() is None:
            ref_proc.kill()
    ref_rows = _metrics_rows(Path(base_cfg["run_dir"]))
    assert [r["epoch"] for r in ref_rows] == list(range(1, 9))
    rows = _metrics_rows(run_dir)  # one folder: the killed run's, reused
    assert [r["epoch"] for r in rows] == list(range(1, 9))  # no dup rounds
    for ref, got in zip(ref_rows, rows):
        assert _strip(ref) == _strip(got), f"epoch {ref['epoch']} diverged"

    # and the finished run's newest checkpoint is verified
    folder = next(iter(run_dir.glob("mnist_*")))
    ok, reason = ckpt.verify_checkpoint(folder / "model_last.pt.tar")
    assert ok, reason


def test_sigterm_graceful_stop_exits_75_with_verified_checkpoint(tmp_path):
    cfg_path, cfg = _write_cfg(tmp_path, "term", epochs=30)
    proc = _launch(cfg_path)
    run_dir = Path(cfg["run_dir"])
    done = _wait_for_rounds(proc, run_dir, 1)
    if proc.poll() is not None:  # pragma: no cover
        pytest.skip("run finished before the signal landed")
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=120)
    assert proc.returncode == EXIT_INTERRUPTED, f"rc\n{out}"
    assert "interrupted: graceful stop" in out
    # stopped within one round boundary of the signal: at most one more
    # round was recorded after the one that triggered the send
    rounds = _rounds_recorded(run_dir)
    assert done <= rounds <= done + 2
    assert rounds < 30  # it genuinely stopped early
    folder = next(iter(run_dir.glob("mnist_*")))
    ok, reason = ckpt.verify_checkpoint(folder / "model_last.pt.tar")
    assert ok, reason
    # recorder stream is intact and consistent with the checkpoint
    rows = _metrics_rows(run_dir)
    assert [r["epoch"] for r in rows] == list(range(1, rounds + 1))
    # the interrupted run is resumable to completion
    out = _run_to_completion(cfg_path, "--resume", "auto", "--epochs",
                             str(rounds + 2))
    rows = _metrics_rows(run_dir)
    assert [r["epoch"] for r in rows] == list(range(1, rounds + 3))
