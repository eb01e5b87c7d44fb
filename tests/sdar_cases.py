"""Tiny `sdar_moe` architectures and parameters the block-diffusion tests
share."""
from dba_mod_tpu import config as cfg

# head_dim 24 where hidden / heads is 16: the key is the model's own
ARCH = dict(hidden_size=64, moe_intermediate_size=48, num_attention_heads=4,
            num_key_value_heads=2, head_dim=24, num_hidden_layers=2,
            num_experts=8, num_experts_per_tok=2, experts_held=[0, 4],
            vocab_size=128, block_length=4, mask_token_id=127,
            noise_low=0.45, noise_high=0.95, rms_norm_eps=1e-6,
            rope_theta=1e6, norm_topk_prob=True, tie_word_embeddings=False)

# a phrase of two blocks, each adversary half a block; the continuation one
PHRASE = {"trigger_num": 4, "0_poison_pattern": [101, 102],
          "1_poison_pattern": [103, 104], "2_poison_pattern": [105, 106],
          "3_poison_pattern": [107, 108], "trigger_positions": [4, 16],
          "poison_continuation": [111, 112, 113, 114]}


def arch(**changes):
    return {**ARCH, **changes}


def params(architecture=None, **extra):
    d = dict(type="sdar_moe", sdar=architecture or ARCH, lr=0.05,
             poison_lr=0.04, batch_size=1, test_batch_size=2, epochs=3,
             no_models=4, number_of_total_participants=10, eta=0.8,
             aggregation_methods="mean", seq_len=32, sequences_per_client=2,
             test_sequences=4, token_sources=4, doc_len_median=12,
             internal_epochs=1, internal_poison_epochs=3,
             poisoning_per_batch=1, is_poison=True, scale_weights_poison=5,
             adversary_list=[0, 1, 2, 3], **PHRASE,
             **{f"{i}_poison_epochs": [2] for i in range(4)})
    d.update(extra)
    return cfg.Params.from_dict(d)
