"""Config loader tests: reference-schema ingestion and per-adversary accessors."""
import pytest

from dba_mod_tpu import config as cfg


BASE = {
    "type": "cifar", "lr": 0.1, "batch_size": 64, "epochs": 10,
    "no_models": 10, "number_of_total_participants": 100, "eta": 0.1,
    "aggregation_methods": "mean",
    "adversary_list": [17, 33, 77, 11],
    "trigger_num": 4,
    "0_poison_pattern": [[0, 0], [0, 1]],
    "1_poison_pattern": [[0, 9], [0, 10]],
    "2_poison_pattern": [[4, 0], [4, 1]],
    "3_poison_pattern": [[4, 9], [4, 10]],
    "0_poison_epochs": [3],
    "1_poison_epochs": [5],
    "2_poison_epochs": [7],
    "3_poison_epochs": [9],
    "poison_epochs": [1],
}


def test_required_key_validation():
    with pytest.raises(ValueError, match="missing required"):
        cfg.Params.from_dict({"type": "cifar"})


def test_unknown_aggregation_rejected():
    bad = dict(BASE, aggregation_methods="bulyan")
    assert "bulyan" not in cfg.AGGR_ALL
    with pytest.raises(ValueError, match="aggregation"):
        cfg.Params.from_dict(bad)


@pytest.mark.parametrize("value", [True, False])
@pytest.mark.parametrize("key", ["grouped_clients", "dynamic_steps"])
def test_removed_options_are_refused_by_name(key, value):
    """Unknown keys pass through `from_dict` silently, so an old YAML that
    names a deleted fork would run the default path without a word. The key
    is refused, not its value: `false` too names code that is gone."""
    assert key not in cfg._DEFAULTS
    with pytest.raises(ValueError, match=key):
        cfg.Params.from_dict(dict(BASE, **{key: value}))


def test_importing_experiment_initialises_no_backend():
    """jax.distributed.initialize() refuses to run once a backend exists, and
    on a chip machine the first process to initialise one owns the chip — so
    nothing in the package may create a device array while it is imported.
    A fresh interpreter: this one initialised its backend in conftest."""
    import subprocess
    import sys
    code = ("import dba_mod_tpu.fl.experiment, dba_mod_tpu.main\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


@pytest.mark.parametrize("env_dir", ["/some/where/else", None])
def test_compile_cache_dir(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; unset, the
    cache sits at the one fixed, git-ignored path inside the checkout."""
    import jax
    from pathlib import Path
    from dba_mod_tpu.utils import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
            assert compile_cache.enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
            assert compile_cache.enable_compile_cache() == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_adversarial_index_distributed():
    p = cfg.Params.from_dict(BASE)
    assert p.adversarial_index_of(33) == 1
    assert p.adversarial_index_of(5) == -1
    assert not p.is_centralized_attack


def test_adversarial_index_centralized_forces_global_pattern():
    # single adversary => pattern index -1 => combined pattern
    # (image_train.py:47-48), but the SCHEDULE still keys on slot 0
    # (resolved before the -1 is forced, image_train.py:38-48)
    p = cfg.Params.from_dict(dict(BASE, adversary_list=[45]))
    assert p.is_centralized_attack
    assert p.adversarial_index_of(45) == -1
    assert p.is_adversary(45) and not p.is_adversary(999)
    assert p.adversary_slot_of(45) == 0
    assert p.poison_epochs_for(p.adversary_slot_of(45)) == [3]


def test_defaults_not_shared_across_instances():
    p1 = cfg.Params.from_dict(dict(BASE))
    p1.raw["save_on_epochs"].append(42)
    p2 = cfg.Params.from_dict(dict(BASE))
    assert 42 not in p2.raw["save_on_epochs"]


def test_pattern_union():
    p = cfg.Params.from_dict(BASE)
    assert p.poison_pattern_for(2) == [[4, 0], [4, 1]]
    combined = p.poison_pattern_for(-1)
    assert len(combined) == 8 and [0, 9] in combined and [4, 10] in combined


def test_poison_epochs_missing_slot_key_raises():
    # Reference parity: image_train.py:43 / main.py:151 look the per-slot key
    # up unconditionally — a missing key must fail loudly, not silently
    # schedule the global default.
    raw = dict(BASE)
    del raw["2_poison_epochs"]
    p = cfg.Params.from_dict(raw)
    with pytest.raises(KeyError):
        p.poison_epochs_for(2)
    assert p.poison_epochs_for(0) == [3]
    assert p.poison_epochs_for(-1) == [1]  # benign default


def test_scheduled_adversaries():
    p = cfg.Params.from_dict(BASE)
    assert p.scheduled_adversaries([5]) == [33]
    assert p.scheduled_adversaries([3, 4, 5]) == [17, 33]
    assert p.scheduled_adversaries([100]) == []


def test_defaults_fill_in():
    p = cfg.Params.from_dict(BASE)
    assert p["momentum"] == 0.9
    assert p["fg_use_memory"] is True
    assert p["is_poison"] is False


def test_loads_reference_yamls_verbatim():
    """Schema compatibility: the reference's own config files must load and
    resolve through the typed accessors."""
    import os
    ref = "/root/reference/utils"
    if not os.path.isdir(ref):
        pytest.skip("reference not mounted")
    for name, typ in [("mnist_params.yaml", "mnist"),
                      ("cifar_params.yaml", "cifar"),
                      ("tiny_params.yaml", "tiny-imagenet-200"),
                      ("loan_params.yaml", "loan")]:
        p = cfg.Params.from_yaml(os.path.join(ref, name))
        assert p.type == typ
        assert p.num_adversaries >= 1
        for slot in range(p.num_adversaries):
            assert len(p.poison_epochs_for(slot)) >= 1
        if p.is_image:
            assert len(p.poison_pattern_for(-1)) > 0
        else:
            names, values = p.poison_trigger_features_for(-1)
            assert len(names) == len(values) > 0
