"""Compile the chip's own programs for a DESCRIBED TPU v5e — no chip attached.

The TPU compiler is installed here and compiles for a topology that is only
described (`on-chip-measurement` guide §2): it refuses what the chip's
compiler would refuse (misaligned Pallas slices, VMEM overflow, programs that
do not fit HBM), which interpret-mode tests cannot see. Covered: the fused
Pallas per-step update at the CIFAR and Tiny-ImageNet model shapes, the
blocked attention kernel and the grouped expert product at the SDAR cell's,
the SmallThinker cell's and the LFM2 cell's shapes and the files' tiles, the SmallThinker
cell's round program (rows of 8,192: its temporaries beside the state), and the
CIFAR round program's donated twin — the default program of an unsharded TPU
run, which the CPU suite otherwise never builds.

A compile that passes is not a chip run; `chip_smoke.py` is.

Only one process may load libtpu, so the topology is described inside a
module-scoped fixture (never at import), everything built from it lives in
fixtures or tests, compiles happen in this process, and all of these tests
stay in this one file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from dba_mod_tpu import config as cfg
from dba_mod_tpu.models import build_model
from dba_mod_tpu.ops.fused_update import make_fused_step_update

C = 10  # clients per round in every reference config


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip (the next run warns and recompiles),
    so the cache is off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("fg_enabled", [False, True])
@pytest.mark.parametrize("model_type", ["cifar", "tiny-imagenet-200"])
def test_fused_update_compiles_for_v5e(one_chip, no_persistent_cache,
                                       model_type, fg_enabled):
    model = build_model(cfg.Params.from_dict(dict(
        type=model_type, lr=0.1, batch_size=64, epochs=1, no_models=C,
        number_of_total_participants=100, eta=0.1,
        aggregation_methods="mean")))
    one = jax.eval_shape(model.init_vars, jax.random.key(0))
    stacked = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((C,) + l.shape, l.dtype,
                                       sharding=one_chip), one)
    p, bn = stacked.params, stacked.batch_stats
    vec = lambda dt: jax.ShapeDtypeStruct((C,), dt, sharding=one_chip)
    fused = make_fused_step_update(0.9, 5e-4, fg_enabled, use_pallas=True)
    compiled = jax.jit(jax.vmap(fused)).lower(
        vec(jnp.float32), vec(jnp.bool_), p, p, p, p if fg_enabled else {},
        bn, bn).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("last", [False, True])
@pytest.mark.parametrize("backward", [False, True])
def test_blocked_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                            backward, last):
    """`models/sdar.py::blocked_streams_attention` at configs/sdar_params.
    yaml's shapes (a row of 2,048 in 2 streams, 4 key-value heads of 8 query
    heads, head_dim 128, block length 4), forward and with its backward
    kernel: the tiles fit fast memory and every slice is aligned."""
    from dba_mod_tpu.models import sdar
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    q = shape(1, 1 if last else 2, 2048, 4, 8, 128)
    k = v = shape(1, 2, 2048, 4, 128)
    form = lambda q, k, v: sdar.blocked_streams_attention(q, k, v, 4, last)
    if backward:
        fn = jax.grad(lambda *a: jnp.sum(form(*a) ** 2), (0, 1, 2))
    else:
        fn = form
    text = jax.jit(fn).lower(q, k, v).compile().as_text()
    assert text.count("tpu_custom_call") >= (1 if last else 2) * (
        2 if backward else 1)


@pytest.mark.parametrize("positions", [4096, 2048])
@pytest.mark.parametrize("backward", [False, True])
def test_grouped_experts_compile_for_v5e(one_chip, no_persistent_cache,
                                         backward, positions):
    """`ops/grouped_experts.py` at configs/sdar_params.yaml's shapes (both
    streams of a row of 2,048 and the last layer's one, hidden 2,048, 16 held
    experts 768 wide, top-8) and the file's tile, forward (the list's kernel
    and the combine) and with its two backward kernels: a row travels as
    whole tiles, an expert's matrices, their bfloat16 copies and their
    gradients fit fast memory."""
    from dba_mod_tpu.ops import grouped_experts as ge
    shape = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    args = (shape(positions, 2048), shape(positions, 8, dt=jnp.int32),
            shape(positions, 8), shape(16, 2048, 768), shape(16, 2048, 768),
            shape(16, 768, 2048))
    if backward:
        fn = jax.grad(lambda *a: jnp.sum(ge.grouped_experts(*a) ** 2),
                      (0, 2, 3, 4, 5))
    else:
        fn = ge.grouped_experts
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= (5 if backward
                                                           else 2)
    # the list is sized for the most a call can route: 8 pairs a position
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


@pytest.mark.parametrize("positions", [4096, 2048])
@pytest.mark.parametrize("backward", [False, True])
def test_two_block_grouped_experts_compile_for_v5e(one_chip,
                                                   no_persistent_cache,
                                                   backward, positions):
    """`ops/grouped_experts.py` at configs/lfm2_params.yaml's shapes (a step's
    two rows of 2,048 and a battery's one, hidden 2,048, 8 held experts
    1,536 wide, top-4): an expert's matrices whole would pass the kernels'
    fast memory, so its width is walked in two blocks of 768, and a block's
    matrices, their bfloat16 copies and their gradients fit; the list is
    sized for 8 pairs a position, a pair a block."""
    from dba_mod_tpu.ops import grouped_experts as ge
    assert ge.width_block(2048, 1536) == 768
    shape = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    args = (shape(positions, 2048), shape(positions, 4, dt=jnp.int32),
            shape(positions, 4), shape(8, 2048, 1536), shape(8, 2048, 1536),
            shape(8, 1536, 2048))
    if backward:
        fn = jax.grad(lambda *a: jnp.sum(ge.grouped_experts(*a) ** 2),
                      (0, 2, 3, 4, 5))
    else:
        fn = ge.grouped_experts
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= (5 if backward
                                                           else 2)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


@pytest.mark.parametrize("kind", ["full", "window"])
@pytest.mark.parametrize("backward", [False, True])
def test_layout_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                           backward, kind):
    """`ops/attention.py::blocked_attention` at the SmallThinker cell's
    shapes (a row of 8,192, 4 key-value heads of 7 query heads, head_dim 128)
    under models/smallthinker.py's two masks, forward and with its backward
    kernel: tiles of 7 x 256 rows against 512 keys, and dk and dv of 4 MiB a
    key-value head each, fit fast memory."""
    from dba_mod_tpu.models import smallthinker as st
    from dba_mod_tpu.ops.attention import blocked_attention
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    q, k = shape(1, 4, 7, 8192, 128), shape(1, 4, 8192, 128)
    mask = st.attention_mask(8192, 4096 if kind == "window" else None)
    form = lambda q, k, v: blocked_attention(q, k, v, mask)
    if backward:
        fn = jax.grad(lambda *a: jnp.sum(form(*a) ** 2), (0, 1, 2))
    else:
        fn = form
    text = jax.jit(fn).lower(q, k, k).compile().as_text()
    assert text.count("tpu_custom_call") >= (2 if backward else 1)


@pytest.mark.parametrize("backward", [False, True])
def test_relu_grouped_experts_compile_for_v5e(one_chip, no_persistent_cache,
                                              backward):
    """`ops/grouped_experts.py` at the SmallThinker cell's shapes (a row of
    8,192 positions, hidden 2,560: 20 lane chunks a row, padded to 24
    sublanes; 8 held experts 768 wide, top-6, a ReLU gate): every copy moves
    whole tiles, an expert's matrices, their bfloat16 copies and their
    gradients fit fast memory; the list is sized for 49,152 pairs."""
    from dba_mod_tpu.ops import grouped_experts as ge
    shape = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    args = (shape(8192, 2560), shape(8192, 6, dt=jnp.int32), shape(8192, 6),
            shape(8, 2560, 768), shape(8, 2560, 768), shape(8, 768, 2560))
    form = lambda *a: ge.grouped_experts(*a, act="relu")
    if backward:
        fn = jax.grad(lambda *a: jnp.sum(form(*a) ** 2), (0, 2, 3, 4, 5))
    else:
        fn = form
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= (5 if backward
                                                           else 2)
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30


def test_smallthinker_round_compiles_for_v5e(one_chip, no_persistent_cache,
                                             tmp_path):
    """The cell `smallthinker_long_row_attack`'s round program (the
    configuration's file under its traffic, as `chipbench.run` builds it) for
    one described chip: both kernels are in it, and its temporaries fit
    beside the arguments (the global model, the workspace's three copies, the
    population): a step of 8,192 tokens over 18,992 logits beside 5.5 GiB of
    arguments, in the chip's 15.75 GiB."""
    import json
    from pathlib import Path
    from chipbench import program
    from chipbench import run as harness
    from dba_mod_tpu.fl.streamed import make_workspace
    root = Path(__file__).resolve().parents[1] / "chipbench"
    config = json.loads(
        (root / "configs/smallthinker_21b_a3b_dba.json").read_text())
    traffic = json.loads(
        (root / "traffic/long_row_phrase_rounds.json").read_text())
    params, _ = program.make_params(config, traffic, tmp_path,
                                    harness.FIRST_WINDOW_EPOCH)
    with pytest.MonkeyPatch.context() as mp:
        # the model asks the backend which attention and which expert product
        # it runs, when it is built and when it is traced
        mp.setattr(jax, "default_backend", lambda: "tpu")
        exp, _ = program.build_experiment(params)
        assert exp.model_def.attention_tiles == (4 * (272 + 3 * 216), 8192)
        tasks, idx, mask, ns, lane = exp.build_static_round_inputs(
            harness.FIRST_WINDOW_EPOCH + 1)
        k1, k2 = jax.random.split(jax.random.key(0))
        exp.engine.release_workspace()      # three copies on this host
        work = jax.eval_shape(make_workspace, exp.global_vars)
        args = _abstract((exp.global_vars, exp.fg_state, work, tasks, idx,
                          mask, lane, ns, k1, k2,
                          exp.device_data.train_source), one_chip)
        compiled = exp.engine.round_fn_donated.lower(*args).compile()
    text = compiled.as_text()
    assert "blocked_attention" in text and "grouped_experts" in text
    mem = compiled.memory_analysis()
    print("smallthinker round memory:", mem)
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held < 15.75 * 2**30, mem


@pytest.fixture(scope="module")
def cifar_engine():
    """configs/cifar_params.yaml's engine as a TPU run builds it — fused
    update on (`auto`), donated twin present — with only the synthetic data
    cut (5,000/1,000 images; the dataset is closed over by the program, so
    its size is compile payload, not program structure). RoundEngine asks
    jax.default_backend() for both decisions; here it is told "tpu"."""
    from pathlib import Path
    from dba_mod_tpu.fl.experiment import Experiment
    params = cfg.Params.from_yaml(
        Path(__file__).resolve().parents[1] / "configs/cifar_params.yaml")
    params.raw.update(synthetic_data=True, synthetic_train_size=5000,
                      synthetic_test_size=1000, resumed_model=False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        exp = Experiment(params, save_results=False)
    assert exp.engine.fused_pallas and not exp.engine.fused_interpret
    assert exp.engine.round_fn_donated is not None
    return exp


def _round_args(exp, sharding):
    """Abstract arguments of one poisoned-run round at the static plan
    shape, all on `sharding`."""
    from dba_mod_tpu.fl.state import build_client_tasks
    n = int(exp.params["no_models"])
    names = exp.participants[:n]
    slots = np.array([exp.client_slots[x] for x in names], np.int64)
    tasks = build_client_tasks(exp.params, names, 1, slots, exp.epochs_max,
                               None)
    tasks_seq = jax.tree_util.tree_map(lambda l: np.asarray(l)[None], tasks)
    plan = (1, n, exp.epochs_max, exp.steps_per_epoch,
            int(exp.params["batch_size"]))
    rng_t, rng_a = jax.random.split(jax.random.key(0))
    args = (exp.global_vars, exp.fg_state, tasks_seq,
            np.zeros(plan, np.int32), np.zeros(plan, bool),
            np.arange(n, dtype=np.int32), np.zeros((n,), np.float32),
            rng_t, rng_a)
    return _abstract(args, sharding)


@pytest.mark.slow  # PR 29: a ResNet round compiled with the cache off, from
# before there was a chip; chipbench/program.py::check_engine_is_the_chips
# now asserts on the real chip, every run, that this program is the one run
def test_cifar_donated_round_compiles_for_v5e(one_chip, no_persistent_cache,
                                              cifar_engine):
    """The donated twin only: it is what an unsharded CLI run dispatches on
    the chip, and round_fn is the same program minus the aliasing (compiling
    both costs ~50 s more than this suite's clock can spare)."""
    compiled = cifar_engine.engine.round_fn_donated.lower(
        *_round_args(cifar_engine, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # donation really aliases the model/defense state into the outputs
    assert "input_output_alias" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 14 * 2**30  # fits one 16 GB chip
