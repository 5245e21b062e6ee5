from elasticsearch_tpu.ops.scoring import (
    BLOCK,
    bm25_idf,
    bm25_scatter_scores,
    constant_scatter_mask,
    masked_top_k,
    next_bucket,
    pad_block_ids,
)
from elasticsearch_tpu.ops.knn import bf16_operand, knn_scores, knn_top_k

__all__ = [
    "BLOCK",
    "bm25_idf",
    "bm25_scatter_scores",
    "constant_scatter_mask",
    "masked_top_k",
    "next_bucket",
    "pad_block_ids",
    "bf16_operand",
    "knn_scores",
    "knn_top_k",
]
