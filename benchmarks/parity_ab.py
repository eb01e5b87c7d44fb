"""Cross-framework A/B parity harness.

Runs the SAME federated rounds through (a) a fresh torch implementation of the
reference's training semantics (image_train.py:12-315, helper.py:240-257,
image_helper.py:289-350, test.py:7-115) and (b) dba_mod_tpu's jitted round
engine, starting from IDENTICAL initial weights and replaying IDENTICAL
per-batch index plans, then compares:

- per-client submitted deltas (params + BN running stats), per round;
- the round-end global model after FedAvg;
- global main-task and backdoor accuracy (the BASELINE.json ±1% north star).

The torch side is written from the reference's semantics, not from
dba_mod_tpu's code: the poison path derives its own MultiStepLR schedule via
torch.optim.lr_scheduler (validating ops/sgd.py's float-milestone quirk
independently), its own adversarial-index resolution (image_train.py:37-48),
its own stamping (image_helper.py:328-350), its own scaling epilogue
(image_train.py:166-171) and FedAvg (helper.py:240-257). The shared inputs are
the things the comparison must control for: the initial weights, the shuffled
batch index plans (shuffle RNG parity is statistical by design, SURVEY
§7.2.4), and the trigger pattern geometry from the config.

Known cross-framework deviations (documented in README quirk table):
- torch BN carries `num_batches_tracked`; flax BN does not. It never affects
  any computation here (BN momentum is fixed, not averaged), so those keys are
  excluded from state comparison and from FedAvg accumulation.

Scope — all four workloads: MNIST (all three aggregators — FedAvg, RFA
geometric median, FoolsGold with memory — plus aggr_epoch_interval=2,
blended-loss/baseline, and DP-noise lanes), CIFAR-BN (FedAvg),
Tiny-ImageNet (FedAvg, centralized combined trigger, imagenet stem +
global pool), and LOAN (FedAvg, feature triggers, scheduler-steps-first
MultiStepLR, adaptive poison LR). LOAN
trains with Dropout(0.5), and dropout mask RNG streams are
framework-specific — so the harness makes the masks a SHARED input, like
the batch plans: the exact masks the flax engine draws are recovered from
its per-step RNG keys (a probe forward with zero kernels / ones biases
turns the captured Dropout intermediates into the {0,1} masks,
`extract_loan_dropout_masks`) and the torch twin consumes them through a
mask-fed Dropout module. Everything else on the torch side — trigger
feature assignment, the top-of-epoch scheduler step, the backdoor-accuracy
LR decay — is implemented from the reference semantics
(loan_train.py:47-127, test.py:61-115).

What tightness to expect (measured, see tests/test_parity_ab.py):
- MNIST (conv+maxpool+fc, no BN): BIT-TIGHT from identical state — ≤9e-8
  abs on O(0.4) updates through 20-step poison rounds with scaling.
- CIFAR BN ResNet: fwd 2e-6, loss 2e-7, BN stats 6e-8 per pass — but XLA
  and torch conv kernels differ in f32 summation order, and activations
  within ~1e-6 of zero flip ReLU gates, so per-step worst-leaf gradients
  drift up to ~1e-2 relative at a seed-dependent layer (chaos, not
  semantics; a systematic bug would pin to one layer). Deltas therefore
  carry a few-percent envelope while accuracies agree exactly.

Run `python -m benchmarks.parity_ab` to regenerate PARITY_AB.md with measured
gaps; tests/test_parity_ab.py asserts the tolerances in CI.
"""
from __future__ import annotations

import collections
import functools
from typing import Dict, List

import numpy as np


# --------------------------------------------------------------- torch twins
def build_torch_mnist():
    """Reference MnistNet (models/MnistNet.py:7-33): conv(1→20,5)→pool→
    conv(20→50,5)→pool→fc(800→500)→fc(500→10), log_softmax head."""
    import torch
    import torch.nn as nn
    import torch.nn.functional as F

    class Net(nn.Module):
        def __init__(self):
            super().__init__()
            self.conv1 = nn.Conv2d(1, 20, 5, 1)
            self.conv2 = nn.Conv2d(20, 50, 5, 1)
            self.fc1 = nn.Linear(4 * 4 * 50, 500)
            self.fc2 = nn.Linear(500, 10)

        def forward(self, x):
            x = F.max_pool2d(F.relu(self.conv1(x)), 2, 2)
            x = F.max_pool2d(F.relu(self.conv2(x)), 2, 2)
            # .reshape not .view: a [N,1,H,W] input is layout-ambiguous and
            # torch CPU may keep conv outputs channels_last; the logical
            # flatten order (= the reference's .view on contiguous) is the same
            x = x.reshape(-1, 4 * 4 * 50)
            x = F.relu(self.fc1(x))
            return F.log_softmax(self.fc2(x), dim=1)

    return Net()


_TORCH_BLOCK_CLS = None


def _torch_block_cls():
    """The BasicBlock both torch ResNet twins share (lazy torch import)."""
    global _TORCH_BLOCK_CLS
    if _TORCH_BLOCK_CLS is not None:
        return _TORCH_BLOCK_CLS
    import torch.nn as nn
    import torch.nn.functional as F

    class Block(nn.Module):
        def __init__(self, in_p, p, stride):
            super().__init__()
            self.conv1 = nn.Conv2d(in_p, p, 3, stride, 1, bias=False)
            self.bn1 = nn.BatchNorm2d(p)
            self.conv2 = nn.Conv2d(p, p, 3, 1, 1, bias=False)
            self.bn2 = nn.BatchNorm2d(p)
            self.has_short = stride != 1 or in_p != p
            if self.has_short:
                self.sc_conv = nn.Conv2d(in_p, p, 1, stride, bias=False)
                self.sc_bn = nn.BatchNorm2d(p)

        def forward(self, x):
            y = F.relu(self.bn1(self.conv1(x)))
            y = self.bn2(self.conv2(y))
            s = self.sc_bn(self.sc_conv(x)) if self.has_short else x
            return F.relu(y + s)

    _TORCH_BLOCK_CLS = Block
    return Block


def build_torch_cifar(widths=(32, 64, 128, 256)):
    """Reference narrow CIFAR ResNet-18 (models/resnet_cifar.py:70-116):
    3×3 stem, widths 32/64/128/256, BasicBlock [2,2,2,2], 4×4 avg pool.
    `widths` is the tests' alone (tests/conftest.py::narrow_resnets)."""
    import torch
    import torch.nn as nn
    import torch.nn.functional as F

    Block = _torch_block_cls()

    class Net(nn.Module):
        def __init__(self):
            super().__init__()
            self.stem_conv = nn.Conv2d(3, widths[0], 3, 1, 1, bias=False)
            self.stem_bn = nn.BatchNorm2d(widths[0])
            blocks = []
            in_p = widths[0]
            for stage, p in enumerate(widths):
                for i in range(2):
                    stride = 2 if (stage > 0 and i == 0) else 1
                    blocks.append(Block(in_p, p, stride))
                    in_p = p
            self.blocks = nn.ModuleList(blocks)
            self.fc = nn.Linear(widths[-1], 10)

        def forward(self, x):
            x = F.relu(self.stem_bn(self.stem_conv(x)))
            for b in self.blocks:
                x = b(x)
            x = F.avg_pool2d(x, 4).view(-1, widths[-1])
            return self.fc(x)

    return Net()


# ----------------------------------------------- flax -> torch state mapping
def _conv(k):
    return np.transpose(np.asarray(k), (3, 2, 0, 1))


def _bn(out, prefix, p, s):
    out[f"{prefix}.weight"] = np.asarray(p["scale"])
    out[f"{prefix}.bias"] = np.asarray(p["bias"])
    out[f"{prefix}.running_mean"] = np.asarray(s["mean"])
    out[f"{prefix}.running_var"] = np.asarray(s["var"])


_MNIST_FC1_PERM = None


def mnist_state_to_torch(mv) -> Dict[str, np.ndarray]:
    """Map MnistNet ModelVars to the torch twin's state_dict layout. The only
    non-trivial entry is fc1: flax flattens NHWC ([4,4,50] → h·200+w·50+c),
    torch flattens NCHW ([50,4,4] → c·16+h·4+w) — a fixed input permutation."""
    global _MNIST_FC1_PERM
    if _MNIST_FC1_PERM is None:
        t = np.arange(800)
        c, h, w = t // 16, (t % 16) // 4, t % 4
        _MNIST_FC1_PERM = h * 200 + w * 50 + c
    p = mv.params
    out = {
        "conv1.weight": _conv(p["Conv_0"]["kernel"]),
        "conv1.bias": np.asarray(p["Conv_0"]["bias"]),
        "conv2.weight": _conv(p["Conv_1"]["kernel"]),
        "conv2.bias": np.asarray(p["Conv_1"]["bias"]),
        "fc1.weight": np.asarray(p["Dense_0"]["kernel"])[_MNIST_FC1_PERM].T,
        "fc1.bias": np.asarray(p["Dense_0"]["bias"]),
        "fc2.weight": np.asarray(p["Dense_1"]["kernel"]).T,
        "fc2.bias": np.asarray(p["Dense_1"]["bias"]),
    }
    return out


def cifar_state_to_torch(mv) -> Dict[str, np.ndarray]:
    p, s = mv.params, mv.batch_stats
    out: Dict[str, np.ndarray] = {}
    out["stem_conv.weight"] = _conv(p["Conv_0"]["kernel"])
    _bn(out, "stem_bn", p["BatchNorm_0"], s["BatchNorm_0"])
    for i in range(8):
        bp, bs = p[f"BasicBlock_{i}"], s[f"BasicBlock_{i}"]
        out[f"blocks.{i}.conv1.weight"] = _conv(bp["Conv_0"]["kernel"])
        _bn(out, f"blocks.{i}.bn1", bp["BatchNorm_0"], bs["BatchNorm_0"])
        out[f"blocks.{i}.conv2.weight"] = _conv(bp["Conv_1"]["kernel"])
        _bn(out, f"blocks.{i}.bn2", bp["BatchNorm_1"], bs["BatchNorm_1"])
        if "Conv_2" in bp:
            out[f"blocks.{i}.sc_conv.weight"] = _conv(bp["Conv_2"]["kernel"])
            _bn(out, f"blocks.{i}.sc_bn", bp["BatchNorm_2"],
                bs["BatchNorm_2"])
    out["fc.weight"] = np.asarray(p["Dense_0"]["kernel"]).T
    out["fc.bias"] = np.asarray(p["Dense_0"]["bias"])
    return out


def build_torch_tiny(widths=(64, 128, 256, 512)):
    """Reference Tiny-ImageNet ResNet-18 (models/resnet_tinyimagenet.py:40-238):
    torchvision-style — 7×7/stride-2 stem, 3×3/stride-2 max pool, standard
    64/128/256/512 BasicBlock [2,2,2,2], global average pool, 200-class head.
    Reuses the CIFAR twin's Block; module names mirror the flax tree so
    `cifar_state_to_torch` maps both variants. `widths` as in
    `build_torch_cifar`."""
    import torch
    import torch.nn as nn
    import torch.nn.functional as F

    Block = _torch_block_cls()

    class Net(nn.Module):
        def __init__(self):
            super().__init__()
            self.stem_conv = nn.Conv2d(3, widths[0], 7, 2, 3, bias=False)
            self.stem_bn = nn.BatchNorm2d(widths[0])
            blocks = []
            in_p = widths[0]
            for stage, p in enumerate(widths):
                for i in range(2):
                    stride = 2 if (stage > 0 and i == 0) else 1
                    blocks.append(Block(in_p, p, stride))
                    in_p = p
            self.blocks = nn.ModuleList(blocks)
            self.fc = nn.Linear(widths[-1], 200)

        def forward(self, x):
            x = F.relu(self.stem_bn(self.stem_conv(x)))
            x = F.max_pool2d(x, 3, 2, 1)
            for b in self.blocks:
                x = b(x)
            x = x.mean(dim=(2, 3))
            return self.fc(x)

    return Net()


def build_torch_loan():
    """Reference LoanNet (models/loan_model.py:10-27): 91→46→23→9, each
    hidden layer Linear → Dropout(0.5) → ReLU, raw logits out. Dropout is a
    mask-CONSUMING module: the client loop feeds it the exact {0,1} masks the
    flax engine drew for the same (client, epoch, step), so both frameworks
    train through identical dropout patterns (see module docstring)."""
    import torch
    import torch.nn as nn
    import torch.nn.functional as F

    class MaskedDropout(nn.Module):
        def __init__(self, rate):
            super().__init__()
            self.rate = rate
            self.mask = None  # [B, features] {0,1}; set per step by the loop

        def forward(self, x):
            if not self.training:
                return x
            m = self.mask[: x.shape[0]]
            return x * m / (1.0 - self.rate)

    class Net(nn.Module):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(91, 46)
            self.drop1 = MaskedDropout(0.5)
            self.fc2 = nn.Linear(46, 23)
            self.drop2 = MaskedDropout(0.5)
            self.fc3 = nn.Linear(23, 9)

        def forward(self, x):
            x = F.relu(self.drop1(self.fc1(x)))
            x = F.relu(self.drop2(self.fc2(x)))
            return self.fc3(x)

    return Net()


def loan_state_to_torch(mv) -> Dict[str, np.ndarray]:
    p = mv.params
    return {f"fc{i + 1}.{t}": (np.asarray(p[f"Dense_{i}"]["kernel"]).T
                               if t == "weight"
                               else np.asarray(p[f"Dense_{i}"]["bias"]))
            for i in range(3) for t in ("weight", "bias")}


CONVERTERS = {"mnist": (build_torch_mnist, mnist_state_to_torch),
              "cifar": (build_torch_cifar, cifar_state_to_torch),
              # the flax ResNet tree names both variants identically
              "tiny-imagenet-200": (build_torch_tiny, cifar_state_to_torch)}


def extract_loan_dropout_masks(module, rng_t, C: int, E: int, S: int,
                               B: int):
    """Recover the EXACT dropout masks the jitted client step draws.

    The engine derives each step's dropout key as
    fold_in(fold_in(fold_in(fold_in(rng_t, seg), lane), e), s)
    (fl/rounds.py:144-146, fl/client.py:108-109), and flax's nn.Dropout is a
    pure function of that key and the module path. Applying the REAL LoanNet
    with crafted parameters (zero kernels, ones biases → every Dropout input
    is all-ones) and capturing the Dropout intermediates yields
    mask/keep_prob directly — no reimplementation of flax's internal RNG
    folding, so this stays correct across flax versions.

    Returns (masks0 [C,E,S,B,46], masks1 [C,E,S,B,23]) as {0,1} float32.
    """
    import jax
    import jax.numpy as jnp

    seg = jax.random.fold_in(rng_t, 0)  # single segment (interval=1)
    lanes, es, ss = np.meshgrid(np.arange(C), np.arange(E), np.arange(S),
                                indexing="ij")

    def step_key(lane, e, s):
        client = jax.random.fold_in(seg, lane)
        return jax.random.fold_in(jax.random.fold_in(client, e), s)

    keys = jax.vmap(step_key)(jnp.asarray(lanes.ravel()),
                              jnp.asarray(es.ravel()),
                              jnp.asarray(ss.ravel()))
    m0, m1 = _loan_mask_probe(module, B)(keys)
    return (np.asarray(m0).reshape(C, E, S, B, 46),
            np.asarray(m1).reshape(C, E, S, B, 23))


_PROBE_CACHE: Dict = {}


def _loan_mask_probe(module, B: int):
    """Jitted vmapped probe, cached per (module, batch) so per-round calls
    reuse one compilation."""
    key = (id(module), B)
    if key in _PROBE_CACHE:
        return _PROBE_CACHE[key]
    import jax
    import jax.numpy as jnp
    import flax.linen as nn

    probe = {"Dense_0": {"kernel": jnp.zeros((91, 46)),
                         "bias": jnp.ones((46,))},
             "Dense_1": {"kernel": jnp.zeros((46, 23)),
                         "bias": jnp.ones((23,))},
             "Dense_2": {"kernel": jnp.zeros((23, 9)),
                         "bias": jnp.ones((9,))}}

    def _probe(k):
        _, st = module.apply(
            {"params": probe}, jnp.ones((B, 91)), train=True,
            rngs={"dropout": k}, mutable=["intermediates"],
            capture_intermediates=lambda m, _: isinstance(m, nn.Dropout))
        inter = st["intermediates"]
        return (inter["Dropout_0"]["__call__"][0] * 0.5,
                inter["Dropout_1"]["__call__"][0] * 0.5)

    fn = jax.jit(jax.vmap(_probe))
    _PROBE_CACHE[key] = fn
    return fn


# ------------------------------------------------- torch reference semantics
def _torch_stamp(x, bank_mask):
    """image_helper.py:328-350: trigger pixels set to 1.0 in every channel.
    x: [n, C, H, W] float in [0,1]; bank_mask: [H, W] {0,1}."""
    return x * (1.0 - bank_mask) + bank_mask


def _adv_of(raw: dict, name, epoch):
    """Reference adversarial-index resolution + poison-epoch gate
    (image_train.py:37-48, :56; loan_train.py:35-45, :65): the slot index,
    -1 (combined trigger) when there is a single adversary, None when this
    client is not poisoning this epoch."""
    advs = list(raw.get("adversary_list", []))
    if not raw.get("is_poison") or name not in advs:
        return None
    slot = advs.index(name)
    if epoch not in list(raw.get(f"{slot}_poison_epochs", [])):
        return None
    return -1 if len(advs) == 1 else slot


def _fedavg_apply(raw: dict, global_sd, deltas):
    """FedAvg (helper.py:240-257): global += eta/no_models · Σ deltas."""
    import torch
    scale = float(raw["eta"]) / int(raw["no_models"])
    for k in global_sd:
        if "num_batches_tracked" in k:
            continue
        acc = np.zeros_like(deltas[0][k])
        for d in deltas:
            acc += d[k]
        global_sd[k] = global_sd[k] + torch.tensor(
            (scale * acc).astype(acc.dtype))


def _dist_norm(model, anchor):
    """helper.py:110-123 flattens (w - w_target) into one vector and takes
    torch.norm — whose subgradient at the zero vector is 0. A client's FIRST
    poison batch has w == w_anchor exactly, so composing sqrt(Σ(w-a)²) by
    hand would inject NaN (0·∞) there; torch.norm (like the engine's
    double-where tree_dist_norm) does not."""
    import torch
    v = torch.cat([(prm - anchor[name]).reshape(-1)
                   for name, prm in model.named_parameters()])
    return torch.norm(v, 2)


class TorchFL:
    """The torch side of the A/B: reference-semantics sequential FL rounds
    replaying recorded batch plans. Holds the torch global model state."""

    def __init__(self, raw: dict, model_ctor, init_sd: Dict[str, np.ndarray],
                 train_images: np.ndarray, train_labels: np.ndarray,
                 test_images: np.ndarray, test_labels: np.ndarray,
                 pattern_bank: np.ndarray):
        import torch
        torch.set_num_threads(1)
        self.raw = raw
        self.global_sd = {k: torch.tensor(v.copy()) for k, v in
                          init_sd.items()}
        self.model = model_ctor()
        self.model.load_state_dict(self.global_sd, strict=False)
        # NCHW float [0,1] once (ToTensor-only pipeline, image_helper.py:178)
        self.train_x = torch.tensor(
            train_images.astype(np.float32) / 255.0).permute(
                0, 3, 1, 2).contiguous()
        self.train_y = torch.tensor(train_labels.astype(np.int64))
        self.test_x = torch.tensor(
            test_images.astype(np.float32) / 255.0).permute(
                0, 3, 1, 2).contiguous()
        self.test_y = torch.tensor(test_labels.astype(np.int64))
        self.bank = torch.tensor(pattern_bank)  # [K, H, W]; row K-1 combined
        self.swap = int(raw["poison_label_swap"])
        self.fg_memory_dict: Dict = {}  # FoolsGold cross-round memory

    def _adv_of(self, name, epoch):
        return _adv_of(self.raw, name, epoch)

    def run_round(self, seg_epochs: List[int], agent_names: List,
                  idx_seq: np.ndarray, mask_seq: np.ndarray,
                  num_samples: List[int] | None = None
                  ) -> List[Dict[str, np.ndarray]]:
        """One reference round over recorded plans idx/mask [I, C, E, S, B] —
        one segment per global epoch in the aggregation interval
        (image_train.py:50-171): the benign optimizer persists across
        segments (built once per client, :33), the poison optimizer and its
        scheduler are fresh per poison segment (:59-68), and the
        distance/scaling anchor re-snapshots to the client's state at each
        segment start (:52-54, :168, :306). Returns per-client WHOLE-ROUND
        delta state_dicts (= the sum of the reference's per-epoch submit
        list, helper.py:193-231); applies the aggregation rule."""
        import torch
        import torch.nn.functional as F
        raw = self.raw
        is_fg = raw.get("aggregation_methods", "mean") == "foolsgold"
        alpha = float(raw.get("alpha_loss", 1.0))
        deltas = []
        fg_client_grads = []  # per client: {param_name: summed raw grads}
        for c, name in enumerate(agent_names):
            model = self.model
            model.load_state_dict(self.global_sd, strict=False)
            benign_opt = torch.optim.SGD(model.parameters(),
                                         lr=float(raw["lr"]),
                                         momentum=float(raw["momentum"]),
                                         weight_decay=float(raw["decay"]))
            anchor = {k: v.clone() for k, v in self.global_sd.items()}
            cg = {k: np.zeros_like(p.detach().numpy())
                  for k, p in model.named_parameters()} if is_fg else None
            model.train()
            for si, epoch in enumerate(seg_epochs):
                idx, mask = idx_seq[si], mask_seq[si]
                anchor_params = {k: v for k, v in anchor.items()
                                 if "running_" not in k
                                 and "num_batches_tracked" not in k}
                adv = self._adv_of(name, epoch)
                if adv is not None:
                    n_e = int(raw["internal_poison_epochs"])
                    opt = torch.optim.SGD(model.parameters(),
                                          lr=float(raw["poison_lr"]),
                                          momentum=float(raw["momentum"]),
                                          weight_decay=float(raw["decay"]))
                    sched = torch.optim.lr_scheduler.MultiStepLR(
                        opt, milestones=[0.2 * n_e, 0.8 * n_e], gamma=0.1)
                    ppb = int(raw["poisoning_per_batch"])
                    bank_row = self.bank[adv if adv >= 0
                                         else self.bank.shape[0] - 1]
                else:
                    n_e = int(raw["internal_epochs"])
                    opt, sched, ppb, bank_row = benign_opt, None, 0, None
                for e in range(n_e):
                    for s in range(idx.shape[2]):
                        sel = mask[c, e, s]
                        n_valid = int(sel.sum())
                        if n_valid == 0:
                            continue
                        ids = idx[c, e, s, :n_valid]
                        x = self.train_x[ids].clone()
                        y = self.train_y[ids].clone()
                        if ppb > 0:
                            k = min(ppb, n_valid)
                            x[:k] = _torch_stamp(x[:k], bank_row)
                            y[:k] = self.swap
                        opt.zero_grad()
                        loss = F.cross_entropy(model(x), y)
                        if alpha != 1.0 and adv is not None:
                            # the blend is the POISON branch's loss
                            # (image_train.py:85-90); benign clients train
                            # on plain CE (:203-207)
                            loss = alpha * loss + (1 - alpha) * _dist_norm(
                                model, anchor_params)
                        loss.backward()
                        if is_fg:
                            # raw per-batch grads accumulated over the ROUND
                            # (client_grad lives outside the epoch loop,
                            # image_train.py:24, :94-100, :212-218)
                            for k, p in model.named_parameters():
                                cg[k] += p.grad.numpy()
                        opt.step()
                    if sched is not None and bool(raw.get("poison_step_lr")):
                        sched.step()  # END of internal epoch (image_train:118)
                if adv is not None and not bool(raw.get("baseline")):
                    gamma = float(raw["scale_weights_poison"])
                    sd = model.state_dict()
                    for k in sd:  # full state incl BN (image_train:166-171)
                        if "num_batches_tracked" in k:
                            continue
                        sd[k].copy_(anchor[k] + (sd[k] - anchor[k]) * gamma)
                # next segment's anchor = this segment's submitted state
                anchor = {k: v.clone()
                          for k, v in model.state_dict().items()}
            delta = {}
            for k, v in model.state_dict().items():
                if "num_batches_tracked" in k:
                    continue
                delta[k] = (v - self.global_sd[k]).numpy().copy()
            deltas.append(delta)
            if is_fg:
                fg_client_grads.append(cg)
        if is_fg:
            self._foolsgold_update(fg_client_grads, agent_names)
        elif raw.get("aggregation_methods", "mean") == "geom_median":
            # RFA: alphas are the per-client dataset sizes the clients
            # reported (= partition sizes; see README quirk table row).
            # Callers with unequal partitions (Dirichlet trajectories) pass
            # the plan's true sizes; the first-step-batch fallback is only
            # proportional for equal splits.
            if num_samples is None:
                num_samples = [int(mask_seq[0, c, 0].sum())
                               for c in range(len(agent_names))]
            self._rfa_update(deltas, num_samples)
        else:
            _fedavg_apply(raw, self.global_sd, deltas)
        return deltas

    def _rfa_update(self, deltas, num_samples):
        """RFA geometric median, reference semantics (helper.py:295-373):
        Weiszfeld iterations with sample-count alphas, eps-floored distances,
        ftol early break; global += eta · median (NOT divided by clients)."""
        import torch
        eps, ftol = 1e-5, 1e-6
        maxiter = int(self.raw.get("geom_median_maxiter", 10))
        alphas = np.asarray(num_samples, np.float64)
        alphas = (alphas / alphas.sum()).astype(np.float32)

        def dist(a, b):
            return float(np.sqrt(sum(
                np.sum((a[k] - b[k]).astype(np.float64) ** 2) for k in a)))

        def wavg(ws):
            tot = float(np.sum(ws))
            return {k: sum((w / tot) * d[k] for w, d in zip(ws, deltas))
                    for k in deltas[0]}

        def objective(m):
            return sum(a * dist(m, p) for a, p in zip(alphas, deltas))

        median = wavg(alphas)
        obj = objective(median)
        for _ in range(maxiter):
            prev_obj = obj
            weights = np.asarray(
                [a / max(eps, dist(median, p))
                 for a, p in zip(alphas, deltas)], np.float32)
            median = wavg(weights)
            obj = objective(median)
            if abs(prev_obj - obj) < ftol * obj:
                break
        eta = float(self.raw["eta"])
        for k in self.global_sd:
            if "num_batches_tracked" in k:
                continue
            self.global_sd[k] = self.global_sd[k] + torch.tensor(
                (eta * median[k]).astype(median[k].dtype))

    def _foolsgold_update(self, client_grads, agent_names):
        """FoolsGold, reference semantics (helper.py:259-293, :527-607):
        cosine similarity over the second-to-last named parameter's
        round-accumulated gradient, id-keyed cross-round memory, pardoning,
        the logit re-weighting incl. the `isinf + wv > 1` precedence quirk,
        then ONE fresh torch-SGD step on the global trainable params with
        the wv-weighted, eta-scaled mean gradient."""
        import torch
        raw = self.raw
        names = list(client_grads[0].keys())
        sim_key = names[-2]  # [-2] named parameter (helper.py:537)
        n = len(client_grads)
        grads = np.stack([cg[sim_key].reshape(-1) for cg in client_grads])
        memory = np.zeros_like(grads)
        for i, a in enumerate(agent_names):
            if a in self.fg_memory_dict:
                self.fg_memory_dict[a] = self.fg_memory_dict[a] + grads[i]
            else:
                self.fg_memory_dict[a] = grads[i].copy()
            memory[i] = self.fg_memory_dict[a]
        basis = memory if bool(raw.get("fg_use_memory")) else grads
        norms = np.linalg.norm(basis, axis=1, keepdims=True)
        cs = (basis / np.maximum(norms, 1e-30)) @ (
            basis / np.maximum(norms, 1e-30)).T - np.eye(n)
        maxcs = np.max(cs, axis=1)
        for i in range(n):          # pardoning (helper.py:585-591)
            for j in range(n):
                if i != j and maxcs[i] < maxcs[j]:
                    cs[i][j] = cs[i][j] * maxcs[i] / maxcs[j]
        wv = 1 - np.max(cs, axis=1)
        wv[wv > 1] = 1
        wv[wv < 0] = 0
        wv = wv / np.max(wv)
        wv[wv == 1] = .99
        with np.errstate(divide="ignore"):
            wv = np.log(wv / (1 - wv)) + 0.5
        wv[(np.isinf(wv) + wv > 1)] = 1  # reference precedence quirk
        wv[wv < 0] = 0
        # aggregated gradient, eta-scaled, through one fresh SGD step
        model = self.model
        model.load_state_dict(self.global_sd, strict=False)
        opt = torch.optim.SGD(model.parameters(), lr=float(raw["lr"]),
                              momentum=float(raw["momentum"]),
                              weight_decay=float(raw["decay"]))
        opt.zero_grad()
        for k, p in model.named_parameters():
            agg = sum(wv[c] * client_grads[c][k] for c in range(n)) / n
            p.grad = torch.tensor(
                (float(raw["eta"]) * agg).astype(np.float32))
        opt.step()
        for k, v in model.state_dict().items():
            if "num_batches_tracked" not in k:
                self.global_sd[k] = v.clone()

    # -- evaluation (test.py:7-115) --
    def _eval(self, poisoned: bool, batch: int = 512):
        import torch
        self.model.load_state_dict(self.global_sd, strict=False)
        self.model.eval()
        if poisoned:
            keep = self.test_y != self.swap  # image_helper.py:148-172
            xs, ys = self.test_x[keep], self.test_y[keep]
        else:
            xs, ys = self.test_x, self.test_y
        correct, count = 0, 0
        with torch.no_grad():
            for i in range(0, len(ys), batch):
                x = xs[i:i + batch]
                y = ys[i:i + batch]
                if poisoned:
                    x = _torch_stamp(x.clone(), self.bank[-1])
                    y = torch.full_like(y, self.swap)
                pred = self.model(x).argmax(1)
                correct += int((pred == y).sum())
                count += len(y)
        return 100.0 * correct / max(count, 1)

    def clean_acc(self):
        return self._eval(False)

    def backdoor_acc(self):
        return self._eval(True)


class TorchLoanFL:
    """The torch side of the LOAN A/B: reference-semantics sequential FL
    rounds (loan_train.py:11-261) over per-state shards, replaying recorded
    batch plans and consuming the flax engine's dropout masks."""

    def __init__(self, raw: dict, init_sd: Dict[str, np.ndarray],
                 train_x: List[np.ndarray], train_y: List[np.ndarray],
                 test_x: List[np.ndarray], test_y: List[np.ndarray],
                 value_bank: np.ndarray, mask_bank: np.ndarray):
        import torch
        torch.set_num_threads(1)
        self.raw = raw
        self.global_sd = {k: torch.tensor(v.copy()) for k, v in
                          init_sd.items()}
        self.model = build_torch_loan()
        self.model.load_state_dict(self.global_sd)
        self.train_x = [torch.tensor(x) for x in train_x]
        self.train_y = [torch.tensor(y.astype(np.int64)) for y in train_y]
        self.test_x = [torch.tensor(x) for x in test_x]
        self.test_y = [torch.tensor(y.astype(np.int64)) for y in test_y]
        self.values = torch.tensor(value_bank)  # [K, F]; row K-1 combined
        self.masks = torch.tensor(mask_bank)
        self.swap = int(raw["poison_label_swap"])
        # run_round trains with plain CE only; the reference LOAN poison
        # branch blends alpha_loss*CE + (1-alpha_loss)*distance
        # (loan_train.py:117-121). Fail loudly rather than report a phantom
        # parity mismatch if a future lane sets alpha_loss != 1.
        assert float(raw.get("alpha_loss", 1.0)) == 1.0, (
            "TorchLoanFL only implements alpha_loss=1.0 (plain CE); the "
            "blended distance loss is not wired on the LOAN torch twin")

    def _adv_of(self, name, epoch):
        return _adv_of(self.raw, name, epoch)

    def _stamp(self, x, row):
        m = self.masks[row]
        return x * (1.0 - m) + self.values[row] * m

    def run_round(self, epoch: int, agent_names: List, slots: np.ndarray,
                  idx: np.ndarray, mask: np.ndarray,
                  drop0: np.ndarray, drop1: np.ndarray):
        """One reference round. idx/mask are the shared [C, E, S, B] plans
        (indices into each client's state shard); drop0/drop1 the shared
        dropout masks [C, E, S, B, ·]. Returns (per-client delta dicts,
        poison_lr used) and applies FedAvg to the global."""
        import torch
        import torch.nn.functional as F
        raw = self.raw
        # every poison client's adaptive-LR probe evaluates its freshly
        # synced model = the round-start global (loan_train.py:27-28, :67-75),
        # so one probe serves the round
        acc_p = None
        if any(self._adv_of(n, epoch) is not None for n in agent_names):
            acc_p = self.backdoor_acc()
        poison_lr = float(raw["poison_lr"])
        if acc_p is not None and not bool(raw.get("baseline")):
            if acc_p > 20:
                poison_lr /= 5
            if acc_p > 60:
                poison_lr /= 10
        deltas = []
        for c, name in enumerate(agent_names):
            model = self.model
            model.load_state_dict(self.global_sd)
            sx, sy = self.train_x[int(slots[c])], self.train_y[int(slots[c])]
            adv = self._adv_of(name, epoch)
            if adv is not None:
                n_e = int(raw["internal_poison_epochs"])
                opt = torch.optim.SGD(model.parameters(), lr=poison_lr,
                                      momentum=float(raw["momentum"]),
                                      weight_decay=float(raw["decay"]))
                sched = torch.optim.lr_scheduler.MultiStepLR(
                    opt, milestones=[0.2 * n_e, 0.8 * n_e], gamma=0.1)
                ppb = int(raw["poisoning_per_batch"])
                row = adv if adv >= 0 else self.values.shape[0] - 1
            else:
                n_e = int(raw["internal_epochs"])
                opt = torch.optim.SGD(model.parameters(),
                                      lr=float(raw["lr"]),
                                      momentum=float(raw["momentum"]),
                                      weight_decay=float(raw["decay"]))
                sched, ppb, row = None, 0, None
            model.train()
            for e in range(n_e):
                if sched is not None and bool(raw.get("poison_step_lr")):
                    sched.step()  # TOP of the internal epoch
                    # (loan_train.py:90-92 steps before the batches)
                for s in range(idx.shape[2]):
                    sel = mask[c, e, s]
                    n_valid = int(sel.sum())
                    if n_valid == 0:
                        continue
                    ids = idx[c, e, s, :n_valid]
                    x = sx[ids].clone()
                    y = sy[ids].clone()
                    if ppb > 0:
                        k = min(ppb, n_valid)
                        x[:k] = self._stamp(x[:k], row)
                        y[:k] = self.swap
                    model.drop1.mask = torch.tensor(drop0[c, e, s])
                    model.drop2.mask = torch.tensor(drop1[c, e, s])
                    opt.zero_grad()
                    loss = F.cross_entropy(model(x), y)
                    loss.backward()
                    opt.step()
            if adv is not None and not bool(raw.get("baseline")):
                gamma = float(raw["scale_weights_poison"])
                sd = model.state_dict()
                for k in sd:
                    sd[k].copy_(self.global_sd[k] +
                                (sd[k] - self.global_sd[k]) * gamma)
            deltas.append({k: (v - self.global_sd[k]).numpy().copy()
                           for k, v in model.state_dict().items()})
        _fedavg_apply(raw, self.global_sd, deltas)
        return deltas, (poison_lr if acc_p is not None else None)

    def _eval(self, poisoned: bool, batch: int = 1024):
        """test.py:13-24 (clean) / :61-89 (poison): iterate EVERY state's
        test shard; the poison pass stamps ALL samples with the combined
        trigger and swaps every label (no target-class filtering for LOAN)."""
        import torch
        self.model.load_state_dict(self.global_sd)
        self.model.eval()
        correct, count = 0, 0
        with torch.no_grad():
            for sx, sy in zip(self.test_x, self.test_y):
                for i in range(0, len(sy), batch):
                    x, y = sx[i:i + batch], sy[i:i + batch]
                    if poisoned:
                        x = self._stamp(x.clone(), self.values.shape[0] - 1)
                        y = torch.full_like(y, self.swap)
                    pred = self.model(x).argmax(1)
                    correct += int((pred == y).sum())
                    count += len(y)
        return 100.0 * correct / max(count, 1)

    def clean_acc(self):
        return self._eval(False)

    def backdoor_acc(self):
        return self._eval(True)


# ------------------------------------------------------------------- driver
def _compare_states(train_deltas, torch_deltas, agent_names, to_torch,
                    global_vars, torch_global_sd):
    """Shared A/B comparison: per-client submitted-update diffs (max abs vs
    the torch update's own scale) and the round-end global-state diff."""
    import jax

    from dba_mod_tpu.models import ModelVars

    deltas_np = jax.device_get(train_deltas)
    per_client = []
    for c, name in enumerate(agent_names):
        jd = to_torch(ModelVars(
            params=jax.tree_util.tree_map(lambda l: l[c], deltas_np.params),
            batch_stats=jax.tree_util.tree_map(lambda l: l[c],
                                               deltas_np.batch_stats)))
        max_abs, ref_scale = 0.0, 0.0
        for k, td in torch_deltas[c].items():
            max_abs = max(max_abs, float(np.abs(jd[k] - td).max()))
            ref_scale = max(ref_scale, float(np.abs(td).max()))
        per_client.append({"name": str(name), "max_abs_diff": max_abs,
                           "ref_scale": ref_scale})
    g = to_torch(global_vars)
    g_diff = max(float(np.abs(g[k] - torch_global_sd[k].numpy()).max())
                 for k in g)
    return per_client, g_diff


def build_round_plans(exp, params, agent_names, seg_epochs):
    """Shared-stimuli plan builder: the SAME batch plans drive both
    frameworks (consumes the experiment's plan RNG once). Returns
    (tasks_list, idx [I,C,E,S,B], mask, num_samples [C])."""
    from dba_mod_tpu.data import build_batch_plan
    from dba_mod_tpu.fl.state import build_client_tasks

    slots = np.array([exp.client_slots[n] for n in agent_names], np.int64)
    tasks_list, idx_list, mask_list = [], [], []
    num_samples = None
    for ep in seg_epochs:
        tasks_s = build_client_tasks(params, agent_names, ep, slots,
                                     exp.epochs_max, None)
        plan = build_batch_plan(
            [exp.client_indices[n] for n in agent_names],
            [int(e) for e in tasks_s.num_epochs],
            int(params["batch_size"]), exp.plan_rng,
            min_steps=exp.steps_per_epoch, min_epochs=exp.epochs_max)
        if num_samples is None:
            num_samples = plan.num_samples.astype(np.float32)
        tasks_list.append(tasks_s)
        idx_list.append(plan.idx)
        mask_list.append(plan.mask)
    return tasks_list, np.stack(idx_list), np.stack(mask_list), num_samples


def run_ab(overrides: dict, n_rounds: int, widths=None) -> dict:
    """Run n_rounds through both frameworks; return the comparison report.
    `widths` builds the torch ResNet twin at other widths than the published
    ones — for a test that has narrowed the flax side the same way."""
    import jax
    import jax.numpy as jnp

    from dba_mod_tpu.config import Params
    from dba_mod_tpu.fl.experiment import Experiment
    from dba_mod_tpu.fl.rounds import nbt_client_deltas
    from dba_mod_tpu.fl.selection import select_agents
    from dba_mod_tpu.ops.triggers import build_pixel_pattern_bank

    params = Params.from_dict(overrides)
    exp = Experiment(params, save_results=False)
    ctor, to_torch = CONVERTERS[params.type]
    if widths is not None:
        ctor = functools.partial(ctor, widths=tuple(widths))
    data = exp.image_data
    h, w = data.train_images.shape[1:3]
    bank = build_pixel_pattern_bank(params, h, w)
    tfl = TorchFL(params.raw, ctor, to_torch(exp.global_vars),
                  data.train_images, data.train_labels, data.test_images,
                  data.test_labels, bank)

    interval = int(params["aggr_epoch_interval"])
    rounds = []
    for rnum in range(n_rounds):
        # the reference round loop advances by the interval (main.py:135);
        # each round carries one training segment per global epoch
        epoch = 1 + rnum * interval
        agent_names, _ = select_agents(params, epoch, exp.participants,
                                       exp.benign_names, exp.select_rng)
        seg_epochs = list(range(epoch, epoch + interval))
        tasks_list, idx_np, mask_np, num_samples = build_round_plans(
            exp, params, agent_names, seg_epochs)
        C = len(agent_names)
        tasks_seq = jax.tree_util.tree_map(
            lambda *ls: jnp.asarray(np.stack(ls)), *tasks_list)
        lane = jnp.arange(C, dtype=jnp.int32)
        exp.rng_key, round_key = jax.random.split(exp.rng_key)
        rng_t, rng_a = jax.random.split(round_key)
        train = exp.engine.train_fn(exp.global_vars, tasks_seq,
                                    jnp.asarray(idx_np),
                                    jnp.asarray(mask_np), lane, rng_t)
        agg = exp.engine.aggregate_fn(
            exp.global_vars, exp.fg_state, train.deltas, train.fg_grads,
            train.fg_feature, jnp.asarray(tasks_list[0].participant_id),
            jnp.asarray(num_samples), rng_a,
            nbt_client_deltas(jnp.asarray(mask_np),
                              jnp.asarray(np.stack(
                                  [t.scale for t in tasks_list]))))
        exp.global_vars = agg.new_vars
        exp.fg_state = agg.new_fg_state
        jax_globals = jax.device_get(exp.engine.global_evals_fn(agg.new_vars))

        torch_deltas = tfl.run_round(seg_epochs, agent_names, idx_np,
                                     mask_np)
        if bool(params["diff_privacy"]):
            # DP noise is random — like the LOAN dropout masks it becomes a
            # SHARED input: recompute the exact noise tree the engine drew
            # (dp_noise_like(rng_a, state, sigma), ops/aggregation.py:76-79)
            # and add it to the torch global. What stays under test is the
            # reference's composition: σ-scaled Gaussian per state entry,
            # added ONCE after the eta/no_models sum, NOT eta-scaled
            # (helper.py:186-191, :253-254). Only FedAvg's noise derivation
            # is mirrored here — RFA draws inside the Weiszfeld update (and
            # discards it on norm rejection) and FoolsGold applies none; a
            # DP lane for those would silently compare the wrong noise, so
            # fail loudly instead.
            assert params.raw.get("aggregation_methods", "mean") == "mean", (
                "the A/B DP lane supports FedAvg only")
            import torch
            from dba_mod_tpu.ops.aggregation import dp_noise_like
            noise = to_torch(dp_noise_like(rng_a, exp.global_vars,
                                           float(params["sigma"])))
            for k in tfl.global_sd:
                tfl.global_sd[k] = tfl.global_sd[k] + torch.tensor(noise[k])

        per_client, g_diff = _compare_states(
            train.deltas, torch_deltas, agent_names, to_torch,
            exp.global_vars, tfl.global_sd)
        torch_clean, torch_bd = tfl.clean_acc(), tfl.backdoor_acc()
        rounds.append({
            "epoch": epoch,
            "per_client": per_client,
            "global_max_abs_diff": g_diff,
            "jax_clean_acc": float(jax_globals.clean.acc),
            "torch_clean_acc": torch_clean,
            "clean_acc_gap": abs(float(jax_globals.clean.acc) - torch_clean),
            "jax_backdoor_acc": float(jax_globals.poison.acc),
            "torch_backdoor_acc": torch_bd,
            "backdoor_acc_gap": abs(float(jax_globals.poison.acc) - torch_bd),
        })
    return {"type": params.type, "rounds": rounds}


def run_ab_loan(overrides: dict, n_rounds: int) -> dict:
    """LOAN A/B: same shape as run_ab, plus the two LOAN-specific shared
    inputs — the per-step dropout masks (extract_loan_dropout_masks) and the
    feature-trigger value/mask banks — and the adaptive-poison-LR probe,
    which each side computes from its OWN global model (loan_train.py:67-75;
    identical state ⇒ identical accuracy ⇒ identical LR)."""
    import jax
    import jax.numpy as jnp

    from dba_mod_tpu.config import Params
    from dba_mod_tpu.data import build_batch_plan
    from dba_mod_tpu.fl.experiment import Experiment
    from dba_mod_tpu.fl.rounds import nbt_client_deltas
    from dba_mod_tpu.fl.selection import select_agents
    from dba_mod_tpu.fl.state import build_client_tasks
    from dba_mod_tpu.ops.triggers import build_feature_trigger_bank

    params = Params.from_dict(overrides)
    # the mask extraction hardcodes segment 0 and TorchLoanFL replays one
    # plan per round — a multi-segment LOAN round would compare the wrong
    # masks and report a phantom parity failure; fail loudly instead
    assert int(params["aggr_epoch_interval"]) == 1, (
        "run_ab_loan supports aggr_epoch_interval=1 only")
    exp = Experiment(params, save_results=False)
    data = exp.loan_data
    values, masks_bank = build_feature_trigger_bank(
        params, {n: i for i, n in enumerate(data.feature_names)},
        data.train_x[0].shape[-1])
    tfl = TorchLoanFL(params.raw, loan_state_to_torch(exp.global_vars),
                      data.train_x, data.train_y, data.test_x, data.test_y,
                      values, masks_bank)

    rounds = []
    for epoch in range(1, n_rounds + 1):
        agent_names, _ = select_agents(params, epoch, exp.participants,
                                       exp.benign_names, exp.select_rng)
        slots = np.array([exp.client_slots[n] for n in agent_names], np.int64)
        # the engine-side probe, exactly as dispatch_round gates it
        # (fl/experiment.py:383-393)
        backdoor_acc = None
        if any(params.adversary_slot_of(n) >= 0 and
               epoch in params.poison_epochs_for(params.adversary_slot_of(n))
               for n in agent_names):
            backdoor_acc = float(exp.engine.backdoor_acc_fn(exp.global_vars))
        tasks = build_client_tasks(params, agent_names, epoch, slots,
                                   exp.epochs_max, backdoor_acc)
        plan = build_batch_plan(
            [exp.client_indices[n] for n in agent_names],
            [int(e) for e in tasks.num_epochs], int(params["batch_size"]),
            exp.plan_rng, min_steps=exp.steps_per_epoch,
            min_epochs=exp.epochs_max)
        C, E, S, B = plan.idx.shape
        tasks_seq = jax.tree_util.tree_map(lambda l: jnp.asarray(l[None]),
                                           tasks)
        lane = jnp.arange(C, dtype=jnp.int32)
        exp.rng_key, round_key = jax.random.split(exp.rng_key)
        rng_t, rng_a = jax.random.split(round_key)
        drop0, drop1 = extract_loan_dropout_masks(
            exp.model_def.module, rng_t, C, E, S, B)
        train = exp.engine.train_fn(exp.global_vars, tasks_seq,
                                    jnp.asarray(plan.idx[None]),
                                    jnp.asarray(plan.mask[None]), lane,
                                    rng_t)
        agg = exp.engine.aggregate_fn(
            exp.global_vars, exp.fg_state, train.deltas, train.fg_grads,
            train.fg_feature, jnp.asarray(tasks.participant_id),
            jnp.asarray(plan.num_samples.astype(np.float32)), rng_a,
            nbt_client_deltas(jnp.asarray(plan.mask[None]),
                              jnp.asarray(tasks.scale[None])))
        exp.global_vars = agg.new_vars
        exp.fg_state = agg.new_fg_state
        jax_globals = jax.device_get(exp.engine.global_evals_fn(agg.new_vars))

        torch_deltas, torch_poison_lr = tfl.run_round(
            epoch, agent_names, slots, plan.idx, plan.mask, drop0, drop1)

        per_client, g_diff = _compare_states(
            train.deltas, torch_deltas, agent_names, loan_state_to_torch,
            exp.global_vars, tfl.global_sd)
        torch_clean, torch_bd = tfl.clean_acc(), tfl.backdoor_acc()
        rounds.append({
            "epoch": epoch,
            "per_client": per_client,
            "global_max_abs_diff": g_diff,
            "jax_clean_acc": float(jax_globals.clean.acc),
            "torch_clean_acc": torch_clean,
            "clean_acc_gap": abs(float(jax_globals.clean.acc) - torch_clean),
            "jax_backdoor_acc": float(jax_globals.poison.acc),
            "torch_backdoor_acc": torch_bd,
            "backdoor_acc_gap": abs(float(jax_globals.poison.acc) - torch_bd),
            "jax_probe_acc": backdoor_acc,
            "torch_poison_lr": torch_poison_lr,
        })
    return {"type": params.type, "rounds": rounds}


MNIST_AB = dict(
    type="mnist", lr=0.1, batch_size=16, epochs=6, no_models=4,
    number_of_total_participants=10, eta=0.8, aggregation_methods="mean",
    # internal_poison_epochs=5 → MultiStepLR milestones [1.0, 4.0] are
    # integral and FIRE (the torch float-milestone quirk's firing branch;
    # non-integral milestones like E=4's [0.8, 3.2] silently never fire)
    internal_epochs=2, internal_poison_epochs=5, is_poison=True,
    synthetic_data=True, synthetic_train_size=600, synthetic_test_size=256,
    momentum=0.9, decay=0.0005, sampling_dirichlet=False, local_eval=False,
    random_seed=7, poison_label_swap=2, poisoning_per_batch=4,
    poison_lr=0.05, poison_step_lr=True, scale_weights_poison=3.0,
    adversary_list=[0, 1], trigger_num=2, alpha_loss=1.0,
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2], [0, 3]],
       "1_poison_pattern": [[3, 0], [3, 1], [3, 2], [3, 3]],
       "0_poison_epochs": [2, 3, 4], "1_poison_epochs": [3, 4]})

# Identical-state variant: every lane (benign, poison MultiStepLR, scaling)
# runs in ROUND 1, where both frameworks hold bit-identical state — measures
# pure semantic agreement with no inherited drift (measured ≤9e-8 abs).
MNIST_AB_R1 = dict(MNIST_AB,
                   **{"0_poison_epochs": [1, 2, 3, 4],
                      "1_poison_epochs": [1, 3, 4]})

# DP-noise variant: FedAvg + differential-privacy Gaussian noise; the noise
# tree is a shared input (see run_ab), the composition ordering is under test.
MNIST_AB_DP = dict(MNIST_AB_R1, diff_privacy=True, sigma=0.01)

# Blended-loss variant: alpha_loss=0.9 activates the anomaly-evading
# distance term α·CE + (1-α)·‖w-w_anchor‖ (image_train.py:85-90) that every
# reference config leaves at α=1 (where the engine skips its fwd+bwd at
# trace time) — this round proves the term's GRADIENT matches torch.
MNIST_AB_ALPHA = dict(MNIST_AB_R1, alpha_loss=0.9)

# baseline=True: model-replacement scaling disabled (image_train.py:148).
MNIST_AB_BASELINE = dict(MNIST_AB_R1, baseline=True)

# aggr_epoch_interval=2 identical-state round: ONE round = segments at
# epochs (1, 2). Adversary 0 poisons segment 1 then runs BENIGN in segment 2
# (poison→benign chaining: the benign optimizer's momentum was untouched by
# the poison segment); adversary 1 poisons both segments (fresh poison
# optimizer + scheduler each, scaling re-anchored to the segment start,
# image_train.py:52-54, :166-171).
MNIST_AB_I2 = dict(MNIST_AB_R1, aggr_epoch_interval=2,
                   **{"0_poison_epochs": [1, 3], "1_poison_epochs": [1, 2]})

# RFA variant of the identical-state round: the full Weiszfeld pipeline
# (sample-count alphas, eps-floored distance weights, ftol break, eta·median
# global step) composed with real poisoned client deltas, cross-framework.
MNIST_AB_RFA = dict(MNIST_AB_R1, aggregation_methods="geom_median",
                    geom_median_maxiter=10)

# FoolsGold variant: similarity over the [-2] parameter's round-accumulated
# gradient, id-keyed memory chaining across rounds, pardoning + logit quirks,
# server SGD step — composed with real sybil (two-adversary) deltas.
MNIST_AB_FG = dict(MNIST_AB_R1, aggregation_methods="foolsgold",
                   fg_use_memory=True)

# Tiny-ImageNet identical-state round: the torchvision-style stem (7×7/s2 +
# max pool), global average pool, and 200-class head compose with the same
# BN/poison/scaling machinery as CIFAR; 128/4 = 32 rows per client divide
# batch_size exactly (BN sees no wrap-padding, README quirk table).
# Single adversary → centralized mode (combined trigger, adv_index −1).
TINY_AB = dict(
    type="tiny-imagenet-200", lr=0.05, batch_size=16, epochs=1,
    no_models=2, number_of_total_participants=4, eta=0.8,
    aggregation_methods="mean", internal_epochs=1, internal_poison_epochs=2,
    is_poison=True, synthetic_data=True, synthetic_train_size=128,
    synthetic_test_size=64, momentum=0.9, decay=0.0005,
    sampling_dirichlet=False, local_eval=False, random_seed=7,
    poison_label_swap=3, poisoning_per_batch=4, poison_lr=0.02,
    poison_step_lr=True, scale_weights_poison=2.0, adversary_list=[0],
    trigger_num=2, alpha_loss=1.0,
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2]],
       "1_poison_pattern": [[5, 0], [5, 1], [5, 2]],
       "0_poison_epochs": [1]})


# LOAN: internal_poison_epochs=5 → integral MultiStepLR milestones [1.0, 4.0]
# fire under the top-of-epoch scheduler step (loan_train.py:90-92); round 1 is
# identical-state with both adversaries' feature triggers, benign clients, and
# ×3 scaling active; later rounds exercise the adaptive poison-LR decay
# (backdoor acc > 20 → lr/5, > 60 → lr/50, loan_train.py:71-75) once the
# round-1 scaled update plants the backdoor.
LOAN_AB = dict(
    type="loan", lr=0.05, poison_lr=0.05, batch_size=64, epochs=4,
    no_models=4, number_of_total_participants=8, eta=0.8,
    aggregation_methods="mean", internal_epochs=2, internal_poison_epochs=5,
    is_poison=True, synthetic_data=True, momentum=0.9, decay=0.0005,
    sampling_dirichlet=False, local_eval=False, random_seed=7,
    poison_label_swap=7, poisoning_per_batch=16, poison_step_lr=True,
    scale_weights_poison=3.0, trigger_num=2, alpha_loss=1.0,
    adversary_list=["AK", "AL"],
    **{"0_poison_trigger_names": ["num_tl_120dpd_2m", "num_tl_90g_dpd_24m"],
       "0_poison_trigger_values": [10, 80],
       "1_poison_trigger_names": ["pub_rec_bankruptcies", "pub_rec"],
       "1_poison_trigger_values": [20, 100],
       "0_poison_epochs": [1, 2, 3], "1_poison_epochs": [1, 3]})


# client partitions (256/4 = 64 samples) divide batch_size exactly: BN batch
# statistics see no wrap-padding on either side (README quirk table row on
# partial-batch BN padding)
CIFAR_AB = dict(
    type="cifar", lr=0.05, batch_size=32, epochs=2, no_models=2,
    number_of_total_participants=4, eta=0.8, aggregation_methods="mean",
    internal_epochs=1, internal_poison_epochs=2, is_poison=True,
    synthetic_data=True, synthetic_train_size=256, synthetic_test_size=128,
    momentum=0.9, decay=0.0005, sampling_dirichlet=False, local_eval=False,
    random_seed=7, poison_label_swap=1, poisoning_per_batch=6,
    poison_lr=0.02, poison_step_lr=True, scale_weights_poison=2.0,
    adversary_list=[0], trigger_num=2, alpha_loss=1.0,
    **{"0_poison_pattern": [[0, 0], [0, 1], [0, 2]],
       "1_poison_pattern": [[3, 0], [3, 1], [3, 2]],
       "0_poison_epochs": [1, 2]})


# CIFAR-BN + FoolsGold: the defenses×BN cell of the A/B matrix. FoolsGold
# aggregates named parameters only — BN running stats stay at the global's
# values on both sides (helper.py:286-290 steps an optimizer over
# named_parameters; fl/rounds.py:203-206 keeps global batch_stats) — and the
# [-2]-parameter similarity feature is the fc weight in both frameworks.
CIFAR_AB_FG = dict(CIFAR_AB, aggregation_methods="foolsgold",
                   fg_use_memory=True)


def _fmt_report(rep: dict) -> str:
    lines = [f"### {rep['type']}", "",
             "| round | max per-client Δ diff | Δ scale | global diff | "
             "clean acc (jax / torch) | backdoor acc (jax / torch) |",
             "|---|---|---|---|---|---|"]
    for r in rep["rounds"]:
        mx = max(pc["max_abs_diff"] for pc in r["per_client"])
        sc = max(pc["ref_scale"] for pc in r["per_client"])
        lines.append(
            f"| {r['epoch']} | {mx:.2e} | {sc:.2e} | "
            f"{r['global_max_abs_diff']:.2e} | "
            f"{r['jax_clean_acc']:.2f} / {r['torch_clean_acc']:.2f} | "
            f"{r['jax_backdoor_acc']:.2f} / {r['torch_backdoor_acc']:.2f} |")
    lines.append("")
    return "\n".join(lines)


def main():
    # the A/B ground truth is torch CPU f32; run the jax side on CPU f32 too
    # so the comparison isolates SEMANTICS from backend matmul precision
    import io
    import os
    # mirror tests/conftest.py exactly (8 virtual devices): XLA:CPU's
    # compiled programs (and hence f32 summation orders) differ with the
    # platform config, and the committed numbers should be the ones CI pins
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    from dba_mod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = io.StringIO()
    out.write(
        "# Cross-framework A/B parity (torch reference semantics vs "
        "dba_mod_tpu)\n\n"
        "Generated by `python -m benchmarks.parity_ab`. Same initial "
        "weights, same batch plans, same trigger geometry; torch side "
        "implements the reference's client loop independently (see "
        "benchmarks/parity_ab.py docstring). North star: main/backdoor "
        "accuracy within ±1% (BASELINE.json). `Δ diff` is the max abs "
        "difference of per-client submitted updates; `Δ scale` the max abs "
        "entry of the torch update it is measured against.\n\n")
    out.write(
        "## Identical-state round (pure semantic agreement)\n\n"
        "Round 1 runs from bit-identical state on both sides with every "
        "lane active (2 poison clients: 20 masked SGD steps, MultiStepLR "
        "milestones firing, ×3 model-replacement scaling; 2 benign "
        "clients):\n\n")
    rep = run_ab(dict(MNIST_AB_R1), 1)
    out.write(_fmt_report(dict(rep, type="mnist (identical-state)")))
    rep = run_ab(dict(MNIST_AB_RFA), 1)
    out.write(_fmt_report(dict(rep, type="mnist + RFA geometric median "
                                          "(identical-state)")))
    rep = run_ab(dict(MNIST_AB_FG), 2)
    out.write(_fmt_report(dict(
        rep, type="mnist + FoolsGold w/ memory (round 1 identical-state, "
                  "round 2 chains the memory)")))
    rep = run_ab(dict(MNIST_AB_DP), 1)
    out.write(_fmt_report(dict(
        rep, type="mnist + differential-privacy noise (identical-state; "
                  "shared noise tree, composition ordering under test)")))
    rep = run_ab(dict(MNIST_AB_ALPHA), 1)
    out.write(_fmt_report(dict(
        rep, type="mnist + alpha_loss=0.9 (identical-state; blended "
                  "anomaly-evading distance loss in the poison branch)")))
    rep = run_ab(dict(MNIST_AB_BASELINE), 1)
    out.write(_fmt_report(dict(
        rep, type="mnist + baseline (identical-state; scaling disabled)")))
    rep = run_ab(dict(MNIST_AB_I2), 1)
    out.write(_fmt_report(dict(
        rep, type="mnist + aggr_epoch_interval=2 (identical-state; "
                  "per-segment re-anchoring, poison→benign chaining)")))
    rep = run_ab(dict(TINY_AB), 1)
    out.write(_fmt_report(dict(
        rep, type="tiny-imagenet-200 (identical-state; centralized "
                  "combined trigger, imagenet stem + global pool)")))
    rep = run_ab(dict(CIFAR_AB_FG), 2)
    out.write(_fmt_report(dict(
        rep, type="cifar + FoolsGold w/ memory (BN stats stay global; "
                  "round 2 chains the memory)")))
    # one 3-round LOAN run serves both sections: round 1 IS the
    # identical-state round, rounds 2-3 chain the adaptive poison LR
    loan_rep = run_ab_loan(dict(LOAN_AB), 3)
    out.write(_fmt_report(dict(loan_rep, rounds=loan_rep["rounds"][:1],
                               type="loan (identical-state; "
                               "shared dropout masks, feature triggers, "
                               "scheduler-first MultiStepLR)")))
    out.write(
        "\n## Multi-round runs (statistical parity)\n\n"
        "Each framework integrates its own f32 rounding across rounds "
        "(reordered reductions cross ReLU boundaries), so trajectories "
        "separate chaotically while remaining statistically identical — "
        "the accuracy north star is the cross-round claim:\n\n")
    for cfg, n in ((MNIST_AB, 4), (CIFAR_AB, 2)):
        rep = run_ab(dict(cfg), n)
        out.write(_fmt_report(rep))
        worst_gap = max(max(r["clean_acc_gap"], r["backdoor_acc_gap"])
                        for r in rep["rounds"])
        out.write(f"\nWorst accuracy gap: {worst_gap:.3f}% "
                  f"(bar: 1%).\n\n")
    out.write(_fmt_report(loan_rep))
    lrs = [r["torch_poison_lr"] for r in loan_rep["rounds"]]
    worst_gap = max(max(r["clean_acc_gap"], r["backdoor_acc_gap"])
                    for r in loan_rep["rounds"])
    out.write(f"\nWorst accuracy gap: {worst_gap:.3f}% (bar: 1%). "
              f"Adaptive poison LR per round: {lrs} (base "
              f"{LOAN_AB['poison_lr']}; a decayed value means the "
              f"backdoor-accuracy rule fired, loan_train.py:71-75).\n\n")
    content = out.getvalue()
    # preserve the trajectory section (written by benchmarks/trajectory_ab)
    from benchmarks.trajectory_ab import (BEGIN_MARK, END_MARK,
                                          extract_trajectory_section)
    try:
        sec = extract_trajectory_section(open("PARITY_AB.md").read())
        if sec is not None:
            content += BEGIN_MARK + sec + END_MARK + "\n"
    except FileNotFoundError:
        pass
    with open("PARITY_AB.md", "w") as f:
        f.write(content)
    print(content)


if __name__ == "__main__":
    main()
