"""Operations the ZAYA1 block's ALGORITHM needs in training, from shapes and
the counted token-expert pairs; `cfg` holds `ZayaConfig`'s arguments. A
matmul of [m, k] x [k, n] is 2*m*k*n operations. tests/test_zaya_bench.py
pins each formula on a hand-worked shape.
"""

from __future__ import annotations


def _latent(cfg: dict) -> tuple:
    """(query width, key/value width) of the compressed latent."""
    d = cfg["head_dim"]
    return cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d


def projection_flops(cfg: dict) -> float:
    """A token and layer, forward: W_Q, W_K, the two value projections
    (together one key/value width) and W_O."""
    q, kv = _latent(cfg)
    return 2.0 * cfg["hidden_size"] * (q + kv + kv + q)


def convolution_flops(cfg: dict) -> float:
    """A token and layer, forward: `cca_time0` depthwise taps a channel of
    [q~; k~], then `cca_time1` taps of d x d a head."""
    q, kv = _latent(cfg)
    return 2.0 * (q + kv) * (cfg["cca_time0"]
                             + cfg["cca_time1"] * cfg["head_dim"])


def router_flops(cfg: dict) -> float:
    """A token and layer, forward: the projection down and the three layers
    of the router's MLP."""
    r = cfg["router_hidden_size"]
    return 2.0 * (cfg["hidden_size"] * r + 2 * r * r
                  + r * cfg["num_experts"])


def attention_flops_fwd(cfg: dict, seq: int) -> float:
    """Causal attention of ONE sequence and layer, forward: Q K^T and P V
    are seq * seq * (query width) each at the causal half."""
    return 2.0 * seq * seq * _latent(cfg)[0]


def attention_flops_train(cfg: dict, batch: int, seq: int) -> float:
    """Forward plus backward of a step's attention, all layers: two matmuls
    forward, four backward; flash attention's recomputation does not
    count."""
    return (3.0 * batch * cfg["num_hidden_layers"]
            * attention_flops_fwd(cfg, seq))


def expert_flops_per_pair(cfg: dict) -> float:
    """One token through one expert, forward: gate, up and down."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def head_flops(cfg: dict) -> float:
    """A token through the tied head over the rows held here."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int,
                          pairs_per_token_layer: float) -> float:
    """Forward + backward (3x forward) of everything above, per token; the
    expert layer by the pairs the step COUNTED a token and layer (a token
    whose expert is absent costs nothing here). No recomputation, no
    optimizer arithmetic, no embedding lookup."""
    layer = (projection_flops(cfg) + convolution_flops(cfg)
             + router_flops(cfg) + attention_flops_fwd(cfg, seq) / seq
             + pairs_per_token_layer * expert_flops_per_pair(cfg))
    return 3.0 * (cfg["num_hidden_layers"] * layer + head_flops(cfg))
