"""Model parity tests: parameter counts and output shapes match the reference
architectures (rebuilt independently in torch from their documented structure)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn as tnn

from dba_mod_tpu import config as cfg
from dba_mod_tpu.models import build_model


def _params(type_name):
    return cfg.Params.from_dict({
        "type": type_name, "lr": 0.1, "batch_size": 64, "epochs": 1,
        "no_models": 2, "number_of_total_participants": 4, "eta": 0.1,
        "aggregation_methods": "mean",
    })


def n_params(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


# ---- torch twins (architecture per reference docs, built independently) ----

def torch_mnist():
    return tnn.Sequential(
        tnn.Conv2d(1, 20, 5, 1), tnn.ReLU(), tnn.MaxPool2d(2, 2),
        tnn.Conv2d(20, 50, 5, 1), tnn.ReLU(), tnn.MaxPool2d(2, 2),
        tnn.Flatten(), tnn.Linear(4 * 4 * 50, 500), tnn.ReLU(),
        tnn.Linear(500, 10), tnn.LogSoftmax(dim=1))


def torch_loan():
    return tnn.Sequential(
        tnn.Linear(91, 46), tnn.Dropout(0.5), tnn.ReLU(),
        tnn.Linear(46, 23), tnn.Dropout(0.5), tnn.ReLU(),
        tnn.Linear(23, 9))


class _TorchBasicBlock(tnn.Module):
    def __init__(self, in_planes, planes, stride):
        super().__init__()
        self.conv1 = tnn.Conv2d(in_planes, planes, 3, stride, 1, bias=False)
        self.bn1 = tnn.BatchNorm2d(planes)
        self.conv2 = tnn.Conv2d(planes, planes, 3, 1, 1, bias=False)
        self.bn2 = tnn.BatchNorm2d(planes)
        self.short = tnn.Sequential()
        if stride != 1 or in_planes != planes:
            self.short = tnn.Sequential(
                tnn.Conv2d(in_planes, planes, 1, stride, bias=False),
                tnn.BatchNorm2d(planes))

    def forward(self, x):
        y = torch.relu(self.bn1(self.conv1(x)))
        y = self.bn2(self.conv2(y))
        return torch.relu(y + self.short(x))


def torch_cifar_resnet18():
    layers = [tnn.Conv2d(3, 32, 3, 1, 1, bias=False), tnn.BatchNorm2d(32)]
    in_planes = 32
    for stage, planes in enumerate([32, 64, 128, 256]):
        for i in range(2):
            stride = (2 if stage > 0 else 1) if i == 0 else 1
            layers.append(_TorchBasicBlock(in_planes, planes, stride))
            in_planes = planes
    layers += [tnn.AvgPool2d(4), tnn.Flatten(), tnn.Linear(256, 10)]
    return tnn.Sequential(*layers)


def torch_tiny_resnet18():
    layers = [tnn.Conv2d(3, 64, 7, 2, 3, bias=False), tnn.BatchNorm2d(64),
              tnn.MaxPool2d(3, 2, 1)]
    in_planes = 64
    for stage, planes in enumerate([64, 128, 256, 512]):
        for i in range(2):
            stride = (2 if stage > 0 else 1) if i == 0 else 1
            layers.append(_TorchBasicBlock(in_planes, planes, stride))
            in_planes = planes
    layers += [tnn.AdaptiveAvgPool2d(1), tnn.Flatten(), tnn.Linear(512, 200)]
    return tnn.Sequential(*layers)


CASES = [
    ("mnist", torch_mnist, (28, 28, 1), 10),
    ("cifar", torch_cifar_resnet18, (32, 32, 3), 10),
    ("tiny-imagenet-200", torch_tiny_resnet18, (64, 64, 3), 200),
    ("loan", torch_loan, (91,), 9),
]


@pytest.mark.parametrize("type_name,twin,in_shape,n_classes", CASES)
def test_param_count_matches_torch_twin(type_name, twin, in_shape, n_classes):
    mdef = build_model(_params(type_name))
    mv = mdef.init_vars(jax.random.key(0))
    tm = twin()
    torch_n = sum(p.numel() for p in tm.parameters())
    assert n_params(mv.params) == torch_n
    # BN running stats must exist iff the torch twin has buffers (minus
    # num_batches_tracked, which flax BN does not carry — documented deviation).
    torch_buf = sum(b.numel() for name, b in tm.named_buffers()
                    if "num_batches_tracked" not in name)
    assert n_params(mv.batch_stats) == torch_buf


@pytest.mark.parametrize("type_name,twin,in_shape,n_classes", CASES)
def test_forward_shapes_and_finiteness(type_name, twin, in_shape, n_classes):
    mdef = build_model(_params(type_name))
    mv = mdef.init_vars(jax.random.key(0))
    x = jnp.ones((4,) + in_shape, jnp.float32) * 0.5
    logits, _ = mdef.apply(mv, x, train=False)
    assert logits.shape == (4, n_classes)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # train mode must run too (mutates BN stats / needs dropout rng)
    logits2, new_stats = mdef.apply(mv, x, train=True,
                                    dropout_rng=jax.random.key(1))
    assert logits2.shape == (4, n_classes)


@pytest.mark.parametrize("type_name,twin,in_shape,n_classes", CASES)
def test_similarity_param_is_final_dense_kernel(type_name, twin, in_shape, n_classes):
    """FoolsGold keys on the reference's params[-2] == final linear weight
    (helper.py:537); our similarity_path must land on a kernel with
    num_classes columns."""
    mdef = build_model(_params(type_name))
    mv = mdef.init_vars(jax.random.key(0))
    p = mdef.similarity_param(mv.params)
    assert p.ndim == 2 and p.shape[1] == n_classes


@pytest.mark.parametrize("type_name", ["cifar", "tiny-imagenet-200"])
def test_narrow_fixture_keeps_the_full_models_tree(type_name, narrow_resnets):
    """What lets a narrow test stand for the wide model: the fixture's
    ResNet has the full model's tree paths (flax auto-names included), the
    same BN leaves, the same stem/pool/head — only channel counts differ."""
    import dba_mod_tpu.fl.experiment as experiment
    import dba_mod_tpu.models as models
    assert experiment.build_model is models.build_model is not build_model
    full, narrow = build_model(_params(type_name)), models.build_model(
        _params(type_name))
    assert tuple(narrow.module.widths) == tuple(narrow_resnets)
    assert tuple(narrow.module.widths) != tuple(full.module.widths)
    for field in ("num_classes", "num_blocks", "bottleneck", "stem", "pool",
                  "kernel_init", "head_init", "dtype"):
        assert getattr(narrow.module, field) == getattr(full.module, field)
    assert dataclasses.replace(narrow, module=full.module) == full
    mv_f = jax.eval_shape(full.init_vars, jax.random.key(0))
    mv_n = jax.eval_shape(narrow.init_vars, jax.random.key(0))
    leaves_f, tree_f = jax.tree_util.tree_flatten_with_path(mv_f)
    leaves_n, tree_n = jax.tree_util.tree_flatten_with_path(mv_n)
    assert tree_f == tree_n          # same paths, auto-names included
    assert jax.tree_util.tree_leaves(mv_n.batch_stats)
    for (path, f), (_, n) in zip(leaves_f, leaves_n):
        assert (f.ndim, f.dtype) == (n.ndim, n.dtype), path
        # kernels keep their spatial window, the head its classes
        assert f.shape[:-2] == n.shape[:-2], path
    head = narrow.similarity_param(mv_n.params)
    assert head.shape == (narrow_resnets[-1], narrow.num_classes)
    # and it runs: train mode moves every BN leaf
    mv = narrow.init_vars(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (4,) + narrow.input_shape)
    logits, new_bn = narrow.apply(mv, x, train=True)
    assert logits.shape == (4, narrow.num_classes)
    assert all(bool(jnp.any(a != b)) for a, b in zip(
        jax.tree_util.tree_leaves(new_bn),
        jax.tree_util.tree_leaves(mv.batch_stats)))


@pytest.mark.parametrize("type_name, twin_name, full_twin", [
    ("cifar", "build_torch_cifar", torch_cifar_resnet18),
    ("tiny-imagenet-200", "build_torch_tiny", torch_tiny_resnet18)])
def test_parity_twins_narrow_with_the_fixture(type_name, twin_name, full_twin,
                                              narrow_resnets):
    """The cross-framework rounds (tests/test_parity_ab.py) narrow both sides
    together: benchmarks/parity_ab.py's torch twin at the fixture's widths
    holds what the fixture's flax model holds, and at its default widths
    what this file's own full-width twin holds."""
    import benchmarks.parity_ab as ab
    import dba_mod_tpu.models as models

    def sizes(tm):
        return (sum(p.numel() for p in tm.parameters()),
                sum(b.numel() for name, b in tm.named_buffers()
                    if "num_batches_tracked" not in name))

    build = getattr(ab, twin_name)
    assert sizes(build()) == sizes(full_twin())
    mv = jax.eval_shape(models.build_model(_params(type_name)).init_vars,
                        jax.random.key(0))
    narrow = sizes(build(widths=narrow_resnets))
    assert narrow == (n_params(mv.params), n_params(mv.batch_stats))
    assert narrow != sizes(build())


def test_mnist_output_is_log_softmax():
    mdef = build_model(_params("mnist"))
    mv = mdef.init_vars(jax.random.key(0))
    x = jnp.ones((2, 28, 28, 1))
    logits, _ = mdef.apply(mv, x, train=False)
    np.testing.assert_allclose(np.exp(np.asarray(logits)).sum(-1), 1.0, rtol=1e-5)
