"""AMP auto-cast state + per-op lists.

Reference: python/paddle/amp/amp_lists.py:33-112 (white/black lists) and the
AMP cast step inside generated ad_funcs (eager_gen.py; eager/amp_auto_cast.h).

TPU-native: bfloat16 is the native low-precision dtype (MXU takes bf16 inputs
with fp32 accumulation), so O1 defaults to bf16 and — unlike fp16 — needs no
loss scaling for the common path.
"""

from __future__ import annotations

import numpy as np

# Ops that are numerically safe & profitable in low precision (MXU ops).
WHITE_LIST = {
    "matmul", "bmm", "mv", "addmm", "linear", "conv2d", "conv1d",
    "conv2d_transpose", "einsum", "scaled_dot_product_attention",
    "flash_attn_unpadded", "flashmask_attention",
    # models/zaya.py: the convolutions of the compressed latent, and the
    # expert layer's products (ops/pallas/grouped_matmul.py)
    "cca_conv", "grouped_matmul",
}

# Ops that must run in fp32 (reductions / exp-family, loss ops).
BLACK_LIST = {
    "exp", "expm1", "log", "log2", "log10", "log1p", "pow", "square",
    "softmax", "log_softmax", "softmax_with_cross_entropy", "cross_entropy",
    "nll_loss", "mse_loss", "l1_loss", "smooth_l1_loss", "kl_div",
    "binary_cross_entropy", "binary_cross_entropy_with_logits",
    "mean", "sum", "norm", "logsumexp", "cumsum", "cumprod", "std", "var",
    "layer_norm", "batch_norm", "group_norm", "instance_norm", "rms_norm",
}


class _AmpState:
    enabled: bool = False
    dtype = None  # np dtype for low precision
    level: str = "O1"
    custom_white = frozenset()
    custom_black = frozenset()


_state = _AmpState()


def amp_state() -> _AmpState:
    return _state


def current_cast_dtype(op_name: str):
    """Return target dtype for this op's float inputs, or None (no cast)."""
    if not _state.enabled:
        return None
    if op_name in _state.custom_black or op_name in BLACK_LIST:
        return np.float32
    if _state.level == "O2":
        # O2: cast everything not blacklisted
        return _state.dtype
    if op_name in _state.custom_white or op_name in WHITE_LIST:
        return _state.dtype
    return None
