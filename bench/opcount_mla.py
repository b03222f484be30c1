"""Operations and bytes that the ALGORITHM needs, for the latent-attention
(MLA) + routed-expert block: what `mla_attn_roofline` and
`decode_weights_roofline` divide by. From the configuration's shapes alone,
as opcount.py is; tests/test_opcount_mla.py pins each on a hand-worked shape.
`cfg` holds the model's constructor arguments (the published key names).
"""

from __future__ import annotations


def latent_bytes_per_token(cfg: dict, itemsize: int) -> int:
    """What the cache holds of one context token over all layers: c_kv and
    k_r, kv_lora_rank + qk_rope_head_dim values a layer (the algorithm's
    576 at the published widths, not a page padded to whole lane tiles)."""
    return cfg["num_hidden_layers"] * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def mla_decode_flops_per_token(cfg: dict) -> int:
    """Absorbed-form decode attention per cached token, all layers: every
    head's score against the shared key (kv_lora_rank + rope MACs) and its
    fold of the value (kv_lora_rank MACs): 1088 MACs a head at the
    published widths, 2 FLOP each."""
    macs = 2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return cfg["num_hidden_layers"] * cfg["num_attention_heads"] * macs * 2


def mla_decode_least_seconds(cfg: dict, contexts, itemsize: int,
                             peaks: dict) -> float:
    """The least time one decode step's attention over `contexts` (context
    lengths of its sequences) can take: the larger of its cache bytes at
    the HBM peak and its FLOPs at the bf16 peak."""
    tokens = float(sum(contexts))
    return max(tokens * latent_bytes_per_token(cfg, itemsize)
               / peaks["hbm_bytes_per_s"],
               tokens * mla_decode_flops_per_token(cfg)
               / peaks["bf16_flops_per_s"])


def expert_bytes(cfg: dict, itemsize: int) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def dense_decode_weight_bytes(cfg: dict, itemsize: int) -> int:
    """Weights every decode step reads whatever the routing: per layer the
    five MLA matrices (kv_b_proj whole: the absorbed form uses its key and
    its value half), the dense layers' SwiGLU, the expert layers' router
    and shared expert, and the output head. Embedding rows (one a token)
    and norms are left out."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl, rope = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    mla = (h * ql + ql * nh * (nope + rope) + h * (kl + rope)
           + kl * nh * (nope + vd) + nh * vd * h)
    n_dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    n_moe = cfg["num_hidden_layers"] - n_dense
    moe = (h * cfg["n_routed_experts"]
           + 3 * h * cfg["moe_intermediate_size"] * cfg["n_shared_experts"])
    return (cfg["num_hidden_layers"] * mla
            + n_dense * 3 * h * cfg["intermediate_size"] + n_moe * moe
            + h * cfg["vocab_size"]) * itemsize


def decode_weight_bytes(cfg: dict, itemsize: int,
                        experts_touched: float) -> float:
    """Weight bytes ONE decode step must read: everything dense plus each
    touched expert once (`experts_touched`: held experts with at least one
    token, summed over the step's layers)."""
    return (dense_decode_weight_bytes(cfg, itemsize)
            + experts_touched * expert_bytes(cfg, itemsize))
