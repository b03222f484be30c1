"""Operations and bytes that the ALGORITHM needs for learned sparse
attention over a latent cache (DeepSeek Sparse Attention: a lightning
indexer, a top-k selection, latent attention over the selected keys): what
`dsa_index_roofline` and `dsa_attn_roofline` divide by. From the
configuration's shapes alone, whatever implements them (a kernel that reads
more than this reads low, never high); tests/test_deepseek_v32_bench.py pins
each on a hand-worked shape. `cfg` holds the model's constructor arguments
(the published key names).
"""

from __future__ import annotations


def index_key_bytes(cfg: dict, itemsize: int) -> int:
    """What the index cache holds of one context token over all layers:
    one key of index_head_dim values a layer."""
    return cfg["num_hidden_layers"] * cfg["index_head_dim"] * itemsize


def index_flops_per_key(cfg: dict) -> int:
    """Scoring one cached key for one query token, all layers: every index
    head's product with it, index_head_dim MACs a head, 2 FLOP each (the
    ReLU, the weighting and the sum over heads are left out)."""
    return (cfg["num_hidden_layers"] * cfg["index_n_heads"]
            * cfg["index_head_dim"] * 2)


def index_least_seconds(cfg: dict, contexts, itemsize: int,
                        peaks: dict) -> float:
    """The least time one decode step's scoring over `contexts` (context
    lengths of its sequences, a query token each) can take: every live
    index key read once a layer at the HBM peak, or its products at the
    bf16 peak, whichever is longer."""
    keys = float(sum(contexts))
    return max(keys * index_key_bytes(cfg, itemsize)
               / peaks["hbm_bytes_per_s"],
               keys * index_flops_per_key(cfg) / peaks["bf16_flops_per_s"])


def selected_keys(cfg: dict, contexts) -> float:
    """Keys a decode step's queries attend over, one layer: min(context,
    index_topk) a sequence."""
    return float(sum(min(int(c), cfg["index_topk"]) for c in contexts))


def selected_row_bytes(cfg: dict, itemsize: int) -> int:
    """One selected latent row, all layers: kv_lora_rank + qk_rope_head_dim
    values a layer (the algorithm's 576, not a padded page's lanes)."""
    return cfg["num_hidden_layers"] * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def selected_row_flops(cfg: dict) -> int:
    """Absorbed-form attention over one selected row, all layers: every
    head's score (kv_lora_rank + rope MACs) and its fold of the value
    (kv_lora_rank MACs), 2 FLOP each."""
    macs = 2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return cfg["num_hidden_layers"] * cfg["num_attention_heads"] * macs * 2


def sparse_attn_least_seconds(cfg: dict, contexts, itemsize: int,
                              peaks: dict) -> float:
    """The least time one decode step's attention over its SELECTED keys
    can take: their rows' bytes at the HBM peak or their FLOPs at the bf16
    peak, whichever is longer."""
    rows = selected_keys(cfg, contexts)
    return max(rows * selected_row_bytes(cfg, itemsize)
               / peaks["hbm_bytes_per_s"],
               rows * selected_row_flops(cfg) / peaks["bf16_flops_per_s"])
