"""Tiny `lfm2_moe` architectures and parameters the token tests share."""
from dba_mod_tpu import config as cfg

ARCH = dict(hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
            num_attention_heads=4, num_key_value_heads=2,
            layer_types=["conv", "full_attention", "conv"],
            num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
            experts_held=[0, 4], vocab_size=128, conv_L_cache=3,
            norm_eps=1e-5, rope_theta=1e6, routed_scaling_factor=1.0,
            norm_topk_prob=True, use_expert_bias=True)

PHRASE = {"trigger_num": 4, "0_poison_pattern": [101, 102],
          "1_poison_pattern": [103], "2_poison_pattern": [104, 105],
          "3_poison_pattern": [106], "trigger_positions": [5, 20],
          "poison_continuation": [111, 112, 113]}


def arch(**changes):
    return {**ARCH, **changes}


def params(architecture=None, **extra):
    d = dict(type="lfm2_moe", lfm2=architecture or ARCH, lr=0.05,
             poison_lr=0.04, batch_size=2, test_batch_size=2, epochs=3,
             no_models=4, number_of_total_participants=10, eta=0.8,
             aggregation_methods="mean", seq_len=32, sequences_per_client=4,
             test_sequences=4, token_sources=4, doc_len_median=12,
             internal_epochs=1, internal_poison_epochs=3,
             poisoning_per_batch=1, is_poison=True, scale_weights_poison=5,
             adversary_list=[0, 1, 2, 3], **PHRASE,
             **{f"{i}_poison_epochs": [2] for i in range(4)})
    d.update(extra)
    return cfg.Params.from_dict(d)
