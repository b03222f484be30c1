"""Operations and bytes that the ALGORITHM needs, for Phi-4-mini-flash's
block (Mamba-1 layers, differential attention over a window and over one
shared cache, gated memory units): what `shared_kv_attn_roofline`,
`window_attn_roofline` and `ssm_state_roofline` divide by. From the
configuration's shapes alone, as opcount.py is; tests/test_phi4flash_bench.py
pins each on a hand-worked shape. `cfg` holds the model's constructor
arguments (the published key names and the Mamba sizes the file assumes).
"""

from __future__ import annotations

import math


def layer_kinds(cfg: dict) -> list:
    """The kind of every layer, by the published rule (`mb_per_layer` 2):
    below split = L / 2 + 2 even layers are "mamba", odd ones "window", the
    last one "full"; from it on even layers are "gmu", odd ones "cross"."""
    L = cfg["num_hidden_layers"]
    split = L // 2 + 2
    return [("cross" if i % 2 else "gmu") if i >= split
            else "full" if i == split - 1
            else ("window" if i % 2 else "mamba") for i in range(L)]


def kernel_layers(cfg: dict) -> list:
    """The kinds whose decode step makes ONE kernel call, in layer order:
    every kind but the gated memory unit (plain matmuls)."""
    return [k for k in layer_kinds(cfg) if k != "gmu"]


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def kv_token_bytes(cfg: dict, itemsize: int) -> int:
    """K and V of one token in one layer: every key/value head, both
    (5120 B at 20 heads of 64 in bfloat16)."""
    head = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * head * itemsize


def shared_kv_bytes(cfg: dict, context_tokens: float, itemsize: int) -> float:
    """The one whole-context cache read once by each layer that attends to
    it (the full layer and every cross layer), for `context_tokens` cached
    tokens summed over a step's sequences."""
    kinds = layer_kinds(cfg)
    readers = kinds.count("full") + kinds.count("cross")
    return readers * kv_token_bytes(cfg, itemsize) * context_tokens


def window_kv_bytes(cfg: dict, sequences: float, mean_context: float,
                    itemsize: int) -> float:
    """The window layers' keys and values inside the window, read once a
    layer: min(context, sliding_window) tokens a sequence."""
    keys = min(mean_context, cfg["sliding_window"])
    return (layer_kinds(cfg).count("window") * kv_token_bytes(cfg, itemsize)
            * keys * sequences)


def scan_state_bytes(cfg: dict) -> int:
    """One sequence's state of one Mamba layer: d_inner x d_state float32
    (327,680 B at 5120 x 16)."""
    return d_inner(cfg) * cfg["mamba_d_state"] * 4


def conv_rows_bytes(cfg: dict, itemsize: int) -> int:
    """The rows before the convolution one sequence keeps of one layer."""
    return (cfg["mamba_d_conv"] - 1) * d_inner(cfg) * itemsize


def scan_decode_bytes(cfg: dict, seq_layer_steps: float) -> float:
    """Bytes the scan's single-token update must move for
    `seq_layer_steps` (sequences x Mamba layers x steps): each state read
    once and written once. (The convolution's rows are the program's
    around the kernel, not the kernel's.)"""
    return seq_layer_steps * 2 * scan_state_bytes(cfg)


def scan_flops_per_token(cfg: dict) -> int:
    """The scan's own operations for one token, all Mamba layers: per
    (channel, state) the decay's product, the write's two and the read's
    two, and the exponential counted as one: 6 d_inner d_state."""
    return (layer_kinds(cfg).count("mamba") * 6 * d_inner(cfg)
            * cfg["mamba_d_state"])


def attention_flops_per_key(cfg: dict) -> int:
    """One layer's differential attention for one cached key of one
    sequence: every query head's score (2 head_dim) and its product with
    the pair's values (2 x 2 head_dim)."""
    head = cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["num_attention_heads"] * 6 * head


def parameters(cfg: dict) -> dict:
    """Parameters by kind of layer and in the tied embedding."""
    h, f, c = cfg["hidden_size"], cfg["intermediate_size"], d_inner(cfg)
    head = h // cfg["num_attention_heads"]
    qw, kvw = cfg["num_attention_heads"] * head, \
        cfg["num_key_value_heads"] * head
    n, taps = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    r = cfg.get("mamba_dt_rank") or math.ceil(h / 16)
    shell = 4 * h + 3 * h * f                    # two norms, the MLP
    attn_tail = qw * h + h + 4 * head + 2 * head  # W_o, lambdas, the gain
    return {
        "mamba": shell + 2 * h * c + c * taps + c + c * (r + 2 * n)
        + r * c + c + c * n + c + c * h,
        "window": shell + h * (qw + 2 * kvw) + qw + 2 * kvw + attn_tail,
        "full": shell + h * (qw + 2 * kvw) + qw + 2 * kvw + attn_tail,
        "gmu": shell + 2 * h * c,
        "cross": shell + h * qw + qw + attn_tail,
        "embedding": cfg["vocab_size"] * h + 2 * h}


def total_parameters(cfg: dict) -> int:
    per = parameters(cfg)
    return per["embedding"] + sum(per[k] for k in layer_kinds(cfg))
