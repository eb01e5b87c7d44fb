"""Worker process for the 2-process jax.distributed test (run by
tests/test_multihost.py, one invocation per process). Bootstraps a
2-process × 4-virtual-CPU-device runtime — 8 global devices — and runs one
sharded FL round through the standard Experiment driver; the multi-host
path is exactly the single-host one plus `initialize_distributed()` (called
by Experiment.__init__ from env vars) and per-process input placement
(parallel/mesh.py::_place)."""
import os
import sys


def main():
    process_id = int(sys.argv[1])
    coordinator = sys.argv[2]
    # optional aggregation rule (default FedAvg); "geom_median" exercises
    # RFA's per-iteration Weiszfeld distance collectives across the
    # process boundary (DCN path)
    method = sys.argv[3] if len(sys.argv) > 3 else "mean"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_COORDINATOR_ADDRESS"] = coordinator
    os.environ["JAX_NUM_PROCESSES"] = "2"
    os.environ["JAX_PROCESS_ID"] = str(process_id)
    import jax
    from dba_mod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from dba_mod_tpu.config import Params
    from dba_mod_tpu.fl.experiment import Experiment

    params = Params.from_dict(dict(
        type="mnist", lr=0.1, batch_size=8, epochs=2, no_models=8,
        number_of_total_participants=8, eta=0.8,
        aggregation_methods=method, internal_epochs=1,
        internal_poison_epochs=2, is_poison=True, synthetic_data=True,
        synthetic_train_size=128, synthetic_test_size=64, momentum=0.9,
        decay=0.0005, sampling_dirichlet=False, local_eval=True,
        poison_label_swap=2, poisoning_per_batch=4, poison_lr=0.05,
        scale_weights_poison=2.0, adversary_list=[0], trigger_num=1,
        alpha_loss=1.0, random_seed=1, num_devices=-1,
        **{"0_poison_pattern": [[0, 0], [0, 1]],
           "0_poison_epochs": [1, 2]}))
    exp = Experiment(params, save_results=False)
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8 and jax.local_device_count() == 4
    assert exp.mesh is not None and exp.mesh.devices.size == 8
    r = exp.run_round(1)
    # both processes print identical results (replicated payload)
    print(f"RESULT {process_id} acc={r['global_acc']:.6f} "
          f"backdoor={r['backdoor_acc']:.6f}", flush=True)


if __name__ == "__main__":
    main()
