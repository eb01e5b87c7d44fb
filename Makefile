# Developer/CI entry points. `make tier1` is the driver's tier-1 command
# (scripts/tier1.sh: six xdist workers, `--dist loadfile`, 1,470 s, the
# junit count): the fast CPU suite (slow-marked rehearsals deselected) on
# the 8-virtual-device platform tests/conftest.py sets up.
SHELL := /bin/bash
.PHONY: tier1 test-slow trace crash-smoke elastic-smoke forensics-smoke \
  async-smoke chaos-soak chaos-smoke overlap-smoke

tier1:
	bash scripts/tier1.sh

test-slow:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m slow \
	  -p no:cacheprovider -p no:xdist -p no:randomly

# One short telemetry-instrumented run (telemetry + profile_dir on): writes
# telemetry.jsonl + Chrome-trace trace.json into the run folder, the XLA
# profiler dump into runs/trace_profile, and prints the phase summary.
trace:
	env JAX_PLATFORMS=cpu python -m dba_mod_tpu.main \
	  --params configs/trace_params.yaml
	@echo "telemetry files:"; ls -1 runs/mnist_*/telemetry.jsonl \
	  runs/mnist_*/trace.json 2>/dev/null | tail -2

# Preemption drill (README "Crash & preemption tolerance"): tiny run,
# SIGTERM it mid-flight (expects the graceful-stop exit code 75 + a
# verified checkpoint), `--resume auto`, assert the run completes in the
# same folder with no duplicate rounds.
crash-smoke:
	bash scripts/crash_smoke.sh

# Elastic multi-host drill (README "Elastic multi-host"): 2-process
# jax.distributed run on virtual CPU devices, SIGKILL one worker mid-run
# (expects the survivor to exit 77 = EXIT_PEER_LOST with a verified
# checkpoint, bounded by watchdog_hard_s), relaunch the survivors SHRUNK
# (1 process) with --resume auto, assert the run completes in the same
# folder with no duplicate rounds.
elastic-smoke:
	bash scripts/elastic_smoke.sh

# Buffered-async drill (README "Asynchronous federation"): tiny `mode:
# async` run (merge every 2 arrivals, straggler tail, staleness weighting),
# SIGTERM it mid-stream (expects the graceful-stop exit code 75 + the
# streaming buffer checkpointed in the aux sidecar), `--resume auto`,
# assert aggregation steps 1..N land exactly once in the same folder.
async-smoke:
	bash scripts/async_smoke.sh

# Self-healing soak (README "Self-healing federation"): sync + async lanes
# under the full compound fault schedule (dropout / corruption / blowup /
# stale replay / host loss) while the harness SIGTERMs/SIGKILLs the
# process at seeded instants and flips bytes in committed checkpoints.
# Asserts: one run folder per lane, steps 1..N exactly once across every
# resume, finite metrics, verified final checkpoint, exit codes inside the
# {0, 75, 76, 77} contract. CHAOS_SEED / CHAOS_KILLS / CHAOS_LANES
# override the schedule.
chaos-soak:
	bash scripts/chaos_soak.sh

# CI-sized slice of the soak: the async lane only, one seeded kill cycle.
chaos-smoke:
	CHAOS_KILLS=1 CHAOS_LANES=async bash scripts/chaos_soak.sh

# Round-pipelining drill (README "Round pipelining"): four tiny CLI runs —
# {sync, async} x {overlap_eval off, on} — then assert the canonical run
# outputs (metrics.jsonl + every recorder CSV, wall-clock columns
# stripped) are byte-identical off vs on for both engines.
overlap-smoke:
	bash scripts/overlap_smoke.sh

# Defense-forensics drill (README "Defense forensics"): tiny FoolsGold
# sybil run with `forensics: true`, assert forensics.jsonl +
# client_forensics.csv stream into the run folder with the pinned schema,
# and render + sanity-check the standalone HTML round-audit via the
# `report` subcommand.
forensics-smoke:
	bash scripts/forensics_smoke.sh
