"""Brute-force dense-vector kNN as batched matmul on the MXU.

Replaces the reference's script_score brute-force over binary doc values
(ref: x-pack vectors query/ScoreScriptUtils.java:113-166 — cosineSimilarity /
dotProduct / l2norm painless functions). TPU-native re-design: the segment's
vectors are one [n_docs, dims] matrix in HBM; a batch of queries [Q, dims]
scores in a single [Q, dims] x [dims, n_docs] matmul (bf16 on the MXU with
f32 accumulation), then masked top-k per query.

Score conventions follow the reference's _score definitions so results are
drop-in comparable:
  cosine:       (1 + cos(q, d)) / 2
  dot_product:  (1 + dot(q, d)) / 2        (vectors assumed unit-normalized)
  l2_norm:      1 / (1 + l2(q, d))

Cosine columns are pre-normalized at upload time (Segment.device('vec:'),
spmd.build_stacked_knn, KnnEngine all divide rows by their norm once on
host), so the per-query hot loop divides by the [Q, 1] query norm only —
the old [Q, n_docs] f32 divide is gone. `norms` still carries the RAW row
norms: the l2 path needs them (dd = norms^2), and cosine ignores them.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def bf16_operand(x: jax.Array) -> jax.Array:
    """A gemm operand rounded to bf16 for real. `astype` alone is not
    enough on the TPU: for a single query XLA turns the gemm into an f32
    multiply-reduce, and that program scored the UNROUNDED query on the
    chip — up to 1.6e-4 (relative) from the same query in a Q >= 8 batch,
    which takes the MXU (PERF.md, PR 22). `reduce_precision` is the
    rounding the compiler may not drop; the cast after it is exact."""
    if x.dtype == jnp.bfloat16:
        return x
    return jax.lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=7).astype(jnp.bfloat16)


@partial(jax.jit, static_argnames=("similarity",))
def knn_scores(
    queries: jax.Array,       # [Q, dims] f32
    vectors: jax.Array,       # [n_docs, dims] bf16/f32 (unit rows for cosine)
    norms: jax.Array,         # [n_docs] f32 — RAW row L2 norms (l2 path)
    exists: jax.Array,        # [n_docs] bool — docs that have the vector field
    *,
    similarity: str = "cosine",
) -> jax.Array:
    """Dense [Q, n_docs] similarity scores; missing docs score -inf."""
    v = bf16_operand(vectors)
    q = bf16_operand(queries)
    dots = jax.lax.dot_general(
        q, v,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [Q, n_docs]
    if similarity == "cosine":
        # rows are unit vectors (upload-time normalization): divide by the
        # query norm only
        qn = jnp.linalg.norm(queries, axis=-1, keepdims=True)  # [Q, 1]
        cos = dots / jnp.maximum(qn, 1e-20)
        scores = (1.0 + cos) / 2.0
    elif similarity == "dot_product":
        scores = (1.0 + dots) / 2.0
    elif similarity == "l2_norm":
        qq = jnp.sum(queries * queries, axis=-1, keepdims=True)
        dd = (norms * norms)[None, :]
        d2 = jnp.maximum(qq + dd - 2.0 * dots, 0.0)
        scores = 1.0 / (1.0 + jnp.sqrt(d2))
    else:
        raise ValueError(f"unknown similarity [{similarity}]")
    return jnp.where(exists[None, :], scores, -jnp.inf)


@partial(jax.jit, static_argnames=("similarity", "k"))
def knn_top_k(
    queries: jax.Array,
    vectors: jax.Array,
    norms: jax.Array,
    exists: jax.Array,
    mask: jax.Array,          # [n_docs] bool — live docs / filter
    *,
    similarity: str = "cosine",
    k: int = 10,
):
    scores = knn_scores(queries, vectors, norms, exists, similarity=similarity)
    scores = jnp.where(mask[None, :], scores, -jnp.inf)
    top_scores, top_ords = jax.lax.top_k(scores, k)     # [Q, k]
    return top_scores, top_ords, top_scores > -jnp.inf
