"""Each formula of opcount_mla on a shape small enough to work by hand,
and on the published widths against the figures ISSUE 27 reckons with."""
import opcount_mla

CFG = {"num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 8,
       "num_attention_heads": 2, "q_lora_rank": 6, "kv_lora_rank": 4,
       "qk_rope_head_dim": 2, "qk_nope_head_dim": 4, "v_head_dim": 4,
       "intermediate_size": 20, "moe_intermediate_size": 5,
       "n_routed_experts": 16, "n_shared_experts": 1, "vocab_size": 10}
KIMI = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
        "hidden_size": 7168, "num_attention_heads": 64, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
        "v_head_dim": 128, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "n_routed_experts": 384,
        "n_shared_experts": 1, "vocab_size": 20480}
PEAKS = {"hbm_bytes_per_s": 100.0, "bf16_flops_per_s": 1000.0}


def test_latent_bytes_and_flops_per_token():
    # 3 layers x (4 + 2) values x 2 bytes
    assert opcount_mla.latent_bytes_per_token(CFG, 2) == 36
    # 3 layers x 2 heads x (4 + 2 score + 4 value) MACs x 2
    assert opcount_mla.mla_decode_flops_per_token(CFG) == 3 * 2 * 10 * 2
    # the published widths: 5 x 576 x 2 B and 5 x 64 x 1088 x 2 FLOP
    assert opcount_mla.latent_bytes_per_token(KIMI, 2) == 5760
    assert opcount_mla.mla_decode_flops_per_token(KIMI) == 5 * 64 * 1088 * 2


def test_least_seconds_takes_the_larger_bound():
    # contexts 3 + 5 = 8 tokens: 288 bytes / 100 = 2.88 s; 960 / 1000 FLOP
    assert opcount_mla.mla_decode_least_seconds(
        CFG, [3, 5], 2, PEAKS) == 2.88
    fast_memory = {"hbm_bytes_per_s": 1e6, "bf16_flops_per_s": 1000.0}
    assert opcount_mla.mla_decode_least_seconds(
        CFG, [3, 5], 2, fast_memory) == 0.96
    # on the chip: 121 FLOP a cached byte, under the ridge of 240
    flop_per_byte = (opcount_mla.mla_decode_flops_per_token(KIMI)
                     / opcount_mla.latent_bytes_per_token(KIMI, 2))
    assert round(flop_per_byte) == 121


def test_weight_bytes():
    # one expert: 3 x 8 x 5
    assert opcount_mla.expert_bytes(CFG, 2) == 240
    # MLA a layer: 8*6 + 6*2*6 + 8*6 + 4*2*8 + 2*4*8 = 296; dense MLP
    # 3*8*20 = 480; an expert layer's router 8*16 + shared 3*8*5 = 248;
    # head 80
    dense = 3 * 296 + 480 + 2 * 248 + 80
    assert opcount_mla.dense_decode_weight_bytes(CFG, 2) == 2 * dense
    assert opcount_mla.decode_weight_bytes(CFG, 2, 1.5) == 2 * dense + 360
    # the published widths: 101.12 M a layer of MLA, 88.08 MB an expert
    assert opcount_mla.expert_bytes(KIMI, 2) == 88080384
    kimi = opcount_mla.dense_decode_weight_bytes(KIMI, 2) / 2
    assert abs(kimi - (5 * 101.122e6 + 396.36e6 + 4 * (2.752e6 + 44.04e6)
                       + 146.8e6)) < 0.5e6
