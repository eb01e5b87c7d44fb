"""Tiny `smallthinker` architectures and parameters the SmallThinker tests
share: hidden 256, the published period of layouts (one global layer without
positions, three window layers with RoPE), a window of 8 in rows of 32."""
from dba_mod_tpu import config as cfg

ARCH = dict(hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
            head_dim=32, moe_ffn_hidden_size=64, moe_num_primary_experts=16,
            moe_num_active_primary_experts=3, num_hidden_layers=8,
            sliding_window_layout=[0, 1, 1, 1] * 2,
            rope_layout=[0, 1, 1, 1] * 2, sliding_window_size=8,
            experts_held=[0, 4], layers_run=[0, 1], vocab_size=128,
            rope_theta=1.5e6, rms_norm_eps=1e-6,
            moe_primary_router_apply_softmax=True, norm_topk_prob=True,
            tie_word_embeddings=False)

PHRASE = {"trigger_num": 4, "0_poison_pattern": [101, 102],
          "1_poison_pattern": [103], "2_poison_pattern": [104, 105],
          "3_poison_pattern": [106], "trigger_positions": [5, 20],
          "poison_continuation": [111, 112, 113]}


def arch(**changes):
    return {**ARCH, **changes}


def params(architecture=None, **extra):
    d = dict(type="smallthinker", smallthinker=architecture or ARCH, lr=0.05,
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
