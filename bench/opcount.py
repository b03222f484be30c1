"""Operations and bytes that the ALGORITHM needs, from shapes alone.

Every roofline share and the MFU divide by these; a share over 100% means a
count here is too high (or the time it is divided by leaves work out).
tests/test_opcount.py pins each formula on a hand-worked shape.
A matmul of [m, k] x [k, n] is 2*m*k*n operations.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights that a token is multiplied by in one forward pass: per
    layer QKV (h x 3h), attention output (h x h), the MLP (h x f, f x h);
    plus the output head (h x vocab). Embedding lookups do no arithmetic."""
    h, f = cfg["hidden_size"], cfg["ffn_hidden"]
    return cfg["num_layers"] * (4 * h * h + 2 * h * f) + h * cfg["vocab_size"]


def attention_flops_fwd(cfg: dict, seq: int) -> float:
    """Causal self-attention of ONE sequence, forward, all layers: QK^T
    and PV are 2*s*s*h each when full; the causal half is s*s*h each."""
    return cfg["num_layers"] * 2.0 * seq * seq * cfg["hidden_size"]


def attention_flops_train(cfg: dict, batch: int, seq: int) -> float:
    """Forward plus backward of causal attention for a batch: two matmuls
    forward, four backward (dV, dP, dQ, dK). Recomputing QK^T in the
    backward pass, as flash attention does, is recomputation and does not
    count."""
    return 3.0 * batch * attention_flops_fwd(cfg, seq)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (3x forward) of the weight matmuls and causal
    attention, per token; no recomputation, no optimizer arithmetic."""
    return 3.0 * (2.0 * matmul_params(cfg) + attention_flops_fwd(cfg, seq) / seq)


def kv_bytes_per_token(cfg: dict, kv_itemsize: int) -> int:
    """K and V of one context token over all layers."""
    return cfg["num_layers"] * 2 * cfg["hidden_size"] * kv_itemsize


def decode_attention_bytes(cfg: dict, contexts, kv_itemsize: int) -> float:
    """Bytes one decode step's attention must read: K and V of every
    context token of every sequence in the batch (whole pages are not
    counted, only the tokens that exist). `contexts` are context lengths."""
    return float(sum(contexts)) * kv_bytes_per_token(cfg, kv_itemsize)
