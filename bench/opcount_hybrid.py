"""Operations and bytes that the ALGORITHM needs, for the hybrid block of
Gated DeltaNet layers and full-attention layers: what `delta_state_roofline`,
`delta_prefill_roofline` and `hybrid_attn_roofline` divide by. From the
configuration's shapes alone, as opcount.py is; tests/test_run_cpu_hybrid.py
pins each on a hand-worked shape. `cfg` holds the model's constructor
arguments (the published key names); the layers that run are the first
`num_hidden_layers` of `layer_types`.
"""

from __future__ import annotations


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def linear_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count("linear_attention")


def full_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count("full_attention")


def state_bytes(cfg: dict) -> int:
    """One sequence's state of one linear layer: H x d_k x d_v float32
    (2,211,840 B at the published widths)."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"] * 4)


def conv_window_bytes(cfg: dict, itemsize: int) -> int:
    """The rows of (q~ | k~ | v~) one sequence keeps of one linear layer:
    taps - 1 rows of 2 H d_k + H d_v values."""
    channels = (2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
                + cfg["linear_num_value_heads"]
                * cfg["linear_value_head_dim"])
    return (cfg["linear_conv_kernel_dim"] - 1) * channels * itemsize


def delta_decode_bytes(cfg: dict, seq_layer_steps: float,
                       conv_itemsize: int) -> float:
    """Bytes the single-token update must move for `seq_layer_steps`
    (sequences x linear layers x steps): each state and each convolution
    window read once and written once."""
    return seq_layer_steps * 2 * (state_bytes(cfg)
                                  + conv_window_bytes(cfg, conv_itemsize))


def delta_rule_flops_per_token(cfg: dict) -> int:
    """The rule's own operations for one token, all linear layers: per head
    decay the state, S^T k, the rank-one update and S^T q, 6 d_k d_v
    (110,592 at 96 x 192)."""
    return (linear_layers(cfg) * cfg["linear_num_value_heads"] * 6
            * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"])


def attention_kv_bytes(cfg: dict, context_tokens: float,
                       itemsize: int) -> float:
    """K and V of the layers that page, for `context_tokens` cached tokens
    read once: heads x head_dim x 2 a token and layer (the algorithm's 30
    heads, not a page padded to 32)."""
    return (full_layers(cfg) * 2 * cfg["hidden_size"] * itemsize
            * context_tokens)
