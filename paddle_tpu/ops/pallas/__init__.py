"""Pallas TPU kernels: flash attention and ragged paged attention.
Imported lazily by the dispatch sites (models.generation,
serving.model_runner) so pure-CPU builds only pay for what they use."""

from paddle_tpu.ops.pallas.ragged_paged_attention import (  # noqa: F401
    attention_page_reads, ragged_attention_ok, ragged_block_counts,
    ragged_paged_attention, ragged_reference,
)

__all__ = [
    "attention_page_reads", "ragged_attention_ok", "ragged_block_counts",
    "ragged_paged_attention", "ragged_reference",
]
