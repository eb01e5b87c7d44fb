"""Multi-host (DCN slot) execution test (VERDICT r3 ask 4): a REAL
2-process jax.distributed runtime — 2 × 4 virtual CPU devices = one
8-device clients mesh spanning processes — runs one full sharded FL round
through the standard Experiment driver. Verifies:

- `initialize_distributed()` bootstraps from env vars inside
  Experiment.__init__ (parallel/distributed.py);
- per-process input placement: each host device_puts only its addressable
  clients slice via jax.make_array_from_process_local_data
  (parallel/mesh.py::_place);
- replicated round outputs: every process can device_get the metrics
  payload host-locally and reports identical accuracies.

Single-controller fallback: without JAX_COORDINATOR_ADDRESS /
JAX_NUM_PROCESSES in the env this path is never taken — the driver runs
exactly as single-host (plain device_put), which every other test covers.
"""
import os
import re
import socket
import subprocess
import sys
from pathlib import Path

import pytest

WORKER = Path(__file__).parent / "distributed_worker.py"


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.parametrize("method", ["mean", "geom_median"])
def test_two_process_round(method):
    """FedAvg proves the bootstrap + placement path; geom_median (RFA)
    additionally runs the per-iteration Weiszfeld distance collectives
    across the process boundary."""
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_NUM_PROCESSES",
                        "JAX_PROCESS_ID", "JAX_COORDINATOR_ADDRESS")}
    env["TF_CPP_MIN_LOG_LEVEL"] = "3"
    env["PYTHONPATH"] = str(WORKER.parent.parent)  # repo root import
    procs = [subprocess.Popen(
        [sys.executable, str(WORKER), str(pid), coord, method],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=str(WORKER.parent.parent))
        for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            # a two-process round takes 40-60 s on a loaded box (PR 29)
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    results = {}
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out[-4000:]}"
        m = re.search(r"RESULT (\d) acc=([\d.]+) backdoor=([\d.]+)", out)
        assert m, f"proc {pid} printed no RESULT:\n{out[-4000:]}"
        results[int(m.group(1))] = (float(m.group(2)), float(m.group(3)))
    assert set(results) == {0, 1}
    # replicated payload → both processes observed the same round
    assert results[0] == results[1], results
