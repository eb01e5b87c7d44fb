"""The `sdar_moe` family's check rounds at a toy architecture, on the CPU
(`python -m pytest chipbench/tests/test_sdar.py -q`; not part of the repo's
tier-1 suite, whose `tests/test_streamed_round.py` walks the same path):

- the float32 program's two check rounds are inside toy limits against the
  plain reference, which draws the program's noise from the feed's key;
- a reference handed another round's key draws other noise, and the
  comparison says so: the key in the feed is part of what is compared.
"""
from __future__ import annotations

import gc

import numpy as np

from chipbench import families, program
from chipbench import run as harness

ARCH = dict(hidden_size=64, moe_intermediate_size=48, num_attention_heads=4,
            num_key_value_heads=2, head_dim=24, num_hidden_layers=2,
            num_experts=8, num_experts_per_tok=2, experts_held=[0, 4],
            vocab_size=128, block_length=4, mask_token_id=127,
            noise_low=0.45, noise_high=0.95, rms_norm_eps=1e-6,
            rope_theta=1e6, norm_topk_prob=True, tie_word_embeddings=False)
PARAMS = dict(
    type="sdar_moe", sdar=ARCH, lr=0.05, poison_lr=0.04, batch_size=1,
    test_batch_size=2, epochs=3, no_models=4, number_of_total_participants=10,
    eta=0.8, aggregation_methods="mean", seq_len=32, sequences_per_client=2,
    test_sequences=4, token_sources=4, doc_len_median=12, internal_epochs=1,
    internal_poison_epochs=3, poisoning_per_batch=1, is_poison=True,
    scale_weights_poison=5, adversary_list=[0, 1, 2, 3], trigger_num=4,
    trigger_positions=[4, 16], poison_continuation=[111, 112, 113, 114],
    momentum=0.9, decay=0.0005, random_seed=1,
    **{f"{i}_poison_pattern": [101 + 2 * i, 102 + 2 * i] for i in range(4)})
CONFIG = {"name": "sdar_toy", "population_seed": 1, "params": PARAMS,
          "model": {"family": "sdar_moe", "seq_len": 32, "arch": ARCH}}
TRAFFIC = {"is_poison": True, "period_rounds": 8,
           "poison_window_rounds": [2, 4, 6, 8], "periods_max": 1,
           "num_devices": 0}
LIMITS = {"loss_gap.k1": 1e-4, "update_rel_l2.k1": 1e-3,
          "loss_gap.k3": 1e-4, "update_rel_l2.k3": 1e-3}


class Events:
    def snapshot(self):
        return {}


def test_the_check_rounds_agree_and_the_key_is_part_of_the_feed(tmp_path):
    family = families.of(CONFIG)
    first = harness.FIRST_WINDOW_EPOCH
    p, raw = program.make_params(CONFIG, TRAFFIC, tmp_path, first)
    exp, _ = program.build_experiment(p)
    state0, checks = harness.seeded_check_rounds(
        exp, family, CONFIG, TRAFFIC, 2147483659, first, Events())
    gc.unfreeze()   # the family's check round froze this process's heap
    assert [c["real_steps"] for c in checks] == [1, 3]
    assert checks[0]["poisoning_per_batch"].max() == 1   # the poisoned epoch
    assert all(c["round_key"].shape == (2,) for c in checks)
    population = family.population_of(exp)
    compared = harness.judge(family, raw, CONFIG["model"], state0, population,
                             checks, LIMITS)
    assert {row["number"] for row in compared} == set(LIMITS)
    assert all(row["ok"] for row in compared), compared
    other = [{**c, "round_key": c["round_key"] + np.uint32(1)} for c in checks]
    compared = harness.judge(family, raw, CONFIG["model"], state0, population,
                             other, LIMITS)
    assert not any(row["ok"] for row in compared), compared
