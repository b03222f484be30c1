"""Operations and bytes that the ALGORITHM needs, for Laguna's block
(grouped-query attention over whole-context pages and over a window, a head
count a layer type, a dense first feed-forward, then routed + shared
experts): what `laguna_weights_roofline`, `laguna_full_attn_roofline` and
`laguna_window_attn_roofline` divide by. From the configuration's shapes
alone, as opcount.py is; bench/tests/test_opcount_laguna.py pins each on a
hand-worked shape. `cfg` holds the model's constructor arguments (the
published key names; the per-layer lists whole, read up to
`num_hidden_layers`).
"""

from __future__ import annotations

FULL, SLIDING = "full_attention", "sliding_attention"


def layer_kinds(cfg: dict) -> list:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def sparse_layers(cfg: dict) -> list:
    """Whether each layer's feed-forward is the expert layer."""
    return [t == "sparse"
            for t in cfg["mlp_layer_types"][:cfg["num_hidden_layers"]]]


def kv_token_bytes(cfg: dict, itemsize: int) -> int:
    """K and V of one token in one layer: every key/value head, both (4096
    B at 8 heads of 128 in bfloat16), whatever the layer's query heads."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def full_kv_bytes(cfg: dict, context_tokens: float, itemsize: int) -> float:
    """The full-attention layers' keys and values, read once a layer, for
    `context_tokens` cached tokens summed over a step's sequences."""
    return (layer_kinds(cfg).count(FULL) * kv_token_bytes(cfg, itemsize)
            * context_tokens)


def window_kv_bytes(cfg: dict, sequences: float, mean_context: float,
                    itemsize: int) -> float:
    """The sliding layers' keys and values inside the window, read once a
    layer: min(context, sliding_window) tokens a sequence."""
    keys = min(mean_context, cfg["sliding_window"])
    return (layer_kinds(cfg).count(SLIDING) * kv_token_bytes(cfg, itemsize)
            * keys * sequences)


def expert_bytes(cfg: dict, itemsize: int) -> int:
    """One routed expert's three matrices (6,291,456 B at 2048 x 512 in
    bfloat16)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def attention_parameters(cfg: dict, layer: int) -> int:
    """W_q, W_k, W_v, the gate a head and W_o of one layer."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    H = cfg["num_attention_heads_per_layer"][layer]
    kv = cfg["num_key_value_heads"]
    return h * H * d + 2 * h * kv * d + h * H + H * d * h


def parameters(cfg: dict) -> dict:
    """Parameters of each layer (a list) and of the embedding, the head and
    the final norm."""
    h = cfg["hidden_size"]
    E, fe = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    layers = []
    for i, sparse in enumerate(sparse_layers(cfg)):
        ffn = (h * E + 3 * E * h * fe + 3 * h * fs) if sparse \
            else 3 * h * cfg["intermediate_size"]
        layers.append(attention_parameters(cfg, i) + 2 * h + ffn)
    return {"layers": layers, "embedding": cfg["vocab_size"] * h,
            "head": h * cfg["vocab_size"], "final_norm": h}


def total_parameters(cfg: dict) -> int:
    per = parameters(cfg)
    return (sum(per["layers"]) + per["embedding"] + per["head"]
            + per["final_norm"])


def dense_decode_weight_bytes(cfg: dict, itemsize: int) -> int:
    """Weights every decode step reads whatever the routing: per layer the
    attention's matrices, the dense layers' SwiGLU, the expert layers'
    router and shared expert, and the output head. Embedding rows (one a
    token) and norms are left out."""
    h = cfg["hidden_size"]
    total = h * cfg["vocab_size"]
    for i, sparse in enumerate(sparse_layers(cfg)):
        total += attention_parameters(cfg, i)
        total += (h * cfg["num_experts"]
                  + 3 * h * cfg["shared_expert_intermediate_size"]
                  ) if sparse else 3 * h * cfg["intermediate_size"]
    return total * itemsize


def decode_weight_bytes(cfg: dict, itemsize: int,
                        experts_touched: float) -> float:
    """Weight bytes ONE decode step must read: everything dense plus each
    touched expert once (`experts_touched`: experts with at least one
    token, summed over the step's expert layers)."""
    return (dense_decode_weight_bytes(cfg, itemsize)
            + experts_touched * expert_bytes(cfg, itemsize))
