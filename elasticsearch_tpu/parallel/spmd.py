"""SPMD query execution over a (dp, shard) device mesh.

The TPU-native answer to the reference's scatter-gather fan-out
(ref: action/search/AbstractSearchAsyncAction.java:188 — one RPC per shard,
then SearchPhaseController.sortDocs top-k merge at the coordinator, and
QueryPhaseResultConsumer's incremental reduce): instead of RPCs, the whole
corpus lives sharded across the mesh and a *single compiled program* does

    score local shard -> local top-k -> all_gather(k results over 'shard')
    -> vectorized k-way merge on every device

Mesh axes:
  dp    — query-batch data parallelism (the _msearch axis; SURVEY.md P3:
          "batch many queries per step")
  shard — corpus partition (SURVEY.md P1 document partitioning); postings are
          sharded along it, queries replicated along it.

Collectives ride ICI (all_gather of [Q,k] is tiny vs the scoring work).
Host-side metadata (term dictionaries) maps query terms to per-shard block
ids before launch; global idf/avgdl come from cluster-wide stats so every
shard scores identically (ref P5: DFS term-stats round -> here a host-side
constant because stats live with the shard metadata).

All shapes are padded to identical per-shard maxima so arrays stack to
[S, ...] and shard cleanly: padding rows point at the reserved zero block and
contribute nothing (see ops/scoring.py).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticsearch_tpu.common import hbm_ledger, integrity
from elasticsearch_tpu.index.segment import FieldPostings, Segment
from elasticsearch_tpu.ops import BLOCK, bf16_operand, bm25_idf, next_bucket

K1 = 1.2
B = 0.75


def make_mesh(n_devices: int | None = None, dp: int = 1, devices=None) -> Mesh:
    """Build a (dp, shard) mesh over the available devices."""
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devs = devs[:n_devices]
    n = len(devs)
    if n % dp != 0:
        raise ValueError(f"dp={dp} does not divide device count {n}")
    arr = np.asarray(devs).reshape(dp, n // dp)
    return Mesh(arr, axis_names=("dp", "shard"))


# --------------------------------------------------------------------------
# Stacked (shardable) index state
# --------------------------------------------------------------------------


@dataclass
class StackedBM25:
    """One text field's postings for all shards, padded and stacked."""

    field: str
    block_docs: jax.Array       # [S, T, 128] i32 (device, sharded over 'shard')
    block_tfs: jax.Array | None  # [S, T, 128] f32 (None when serve_only)
    block_scores: jax.Array     # [S, T, 128] f32 — idf-free lane score tf(k1+1)/(tf+norm)
    doc_len: jax.Array | None   # [S, D] f32 (None when serve_only)
    live: jax.Array             # [S, D] bool
    n_shards: int
    max_docs: int               # D (padded)
    doc_counts: List[int]       # real docs per shard
    avgdl: float                # global average doc length
    total_docs: int             # global doc count (idf denominator)
    postings: List[FieldPostings]  # host metadata per shard (term -> blocks)
    live_host: List[np.ndarray] | None = None  # host copies of the live masks
    #   (selective-conjunction host path filters candidates without a
    #   device round trip)
    block_max_scores: List[np.ndarray] | None = None  # host [T_s] per shard:
    #   max idf-free lane score per block — the block-max culling metadata
    #   (SURVEY §5.7: the BlockMaxWAND analog's skip data)

    def sharding(self, mesh: Mesh):
        return NamedSharding(mesh, P(None, "shard"))


@dataclass
class StackedKnn:
    field: str
    vectors: jax.Array          # [S, D, dims] bf16
    norms: jax.Array            # [S, D] f32
    exists: jax.Array           # [S, D] bool
    live: jax.Array             # [S, D] bool
    n_shards: int
    max_docs: int
    similarity: str


def _pad_stack(arrays: Sequence[np.ndarray], shape: Tuple[int, ...], dtype) -> np.ndarray:
    out = np.zeros((len(arrays),) + shape, dtype)
    for i, a in enumerate(arrays):
        sl = tuple(slice(0, s) for s in a.shape)
        out[i][sl] = a
    return out


def build_stacked_bm25(
    segments: Sequence[Segment],
    field: str,
    live_masks: Sequence[np.ndarray] | None = None,
    mesh: Mesh | None = None,
    serve_only: bool = False,
    device_arrays: bool = True,
) -> StackedBM25:
    """Stack per-shard single segments into shardable arrays.

    Each shard must be compacted to one segment (force_merge) — the stacked
    layout is the serving snapshot for the SPMD path, rebuilt on refresh the
    way the reference's searchable snapshot mounts a point-in-time commit.

    device_arrays=False keeps block_docs/block_scores/live as host ndarrays
    (TurboBM25 builds its own padded device copies; transferring the stacked
    layout too would waste HBM and a host->device transfer).
    """
    fps = []
    for seg in segments:
        fp = seg.postings.get(field)
        if fp is None:
            # empty shard: synthesize an empty postings table
            fp = FieldPostings(
                field=field, term_to_ord={}, terms=[],
                doc_freq=np.zeros(0, np.int32), total_term_freq=np.zeros(0, np.int64),
                block_start=np.zeros(0, np.int32), block_count=np.zeros(0, np.int32),
                block_docs=np.zeros((1, BLOCK), np.int32), block_tfs=np.zeros((1, BLOCK), np.float32),
                block_max_tf=np.zeros(1, np.float32),
                post_start=np.zeros(1, np.int64), post_doc=np.zeros(0, np.int32),
                pos_start=np.zeros(1, np.int64), pos_data=np.zeros(0, np.int32),
                doc_len=np.zeros(max(seg.n_docs, 1), np.float32), sum_doc_len=0.0,
            )
        fps.append(fp)

    S = len(segments)
    T = max(fp.block_docs.shape[0] for fp in fps)
    D = max(max(seg.n_docs, 1) for seg in segments)
    if D >= (1 << 24):
        raise ValueError(
            f"partition has {D} docs; the packed-id transport carries 24-bit "
            "ordinals — split corpora beyond 16.7M docs into more shards")
    block_docs = _pad_stack([fp.block_docs for fp in fps], (T, BLOCK), np.int32)
    block_tfs = _pad_stack([fp.block_tfs for fp in fps], (T, BLOCK), np.float32)
    doc_len = _pad_stack([fp.doc_len for fp in fps], (D,), np.float32)
    if live_masks is None:
        live_np = [np.ones(seg.n_docs, bool) for seg in segments]
    else:
        live_np = list(live_masks)
    live = _pad_stack(live_np, (D,), bool)

    total_docs = sum(seg.n_docs for seg in segments)
    n_field = sum(int(np.count_nonzero(fp.doc_len)) for fp in fps)
    sum_dl = sum(fp.sum_doc_len for fp in fps)
    avgdl = (sum_dl / n_field) if n_field else 1.0

    # idf-free lane scores, precomputed host-side so the device never needs a
    # per-lane doc_len gather: tf*(k1+1)/(tf + k1*(1-b+b*dl/avgdl))
    dl_lane = np.empty_like(block_tfs)
    for s in range(S):  # per-shard doc ords index their own shard's doc_len
        dl_lane[s] = doc_len[s][block_docs[s]]
    denom = block_tfs + K1 * (1.0 - B + B * dl_lane / max(avgdl, 1e-9))
    block_scores = np.where(block_tfs > 0, block_tfs * (K1 + 1.0) / denom, 0.0).astype(np.float32)
    block_max_scores = [block_scores[s].max(axis=1) for s in range(S)]

    if device_arrays:
        put = partial(_put_sharded, mesh=mesh)
    else:
        put = lambda x: x  # noqa: E731 — host-resident stacked view
    return StackedBM25(
        field=field,
        block_docs=put(block_docs),
        block_tfs=None if serve_only else put(block_tfs),
        block_scores=put(block_scores),
        doc_len=None if serve_only else put(doc_len),
        live=put(live),
        n_shards=S,
        max_docs=D,
        doc_counts=[seg.n_docs for seg in segments],
        avgdl=float(avgdl),
        total_docs=total_docs,
        postings=fps,
        live_host=live_np,
        block_max_scores=block_max_scores,
    )


def build_stacked_knn(
    segments: Sequence[Segment],
    field: str,
    live_masks: Sequence[np.ndarray] | None = None,
    mesh: Mesh | None = None,
) -> StackedKnn:
    S = len(segments)
    dims = 1
    sim = "cosine"
    for seg in segments:
        vc = seg.vectors.get(field)
        if vc is not None and vc.dims:
            dims = vc.dims
            sim = vc.similarity
            break
    D = max(max(seg.n_docs, 1) for seg in segments)
    vecs, norms, exists = [], [], []
    for seg in segments:
        vc = seg.vectors.get(field)
        if vc is None:
            vecs.append(np.zeros((max(seg.n_docs, 1), dims), np.float32))
            norms.append(np.zeros(max(seg.n_docs, 1), np.float32))
            exists.append(np.zeros(max(seg.n_docs, 1), bool))
        else:
            v = vc.vectors.astype(np.float32)
            if sim == "cosine":
                # upload-time row normalization (ops/knn.py convention):
                # cosine scoring divides by the query norm only
                v = v / np.maximum(vc.norms, 1e-20)[:, None]
            vecs.append(v)
            norms.append(vc.norms)
            exists.append(vc.exists)
    if live_masks is None:
        live_np = [np.ones(seg.n_docs, bool) for seg in segments]
    else:
        live_np = list(live_masks)
    put = partial(_put_sharded, mesh=mesh)
    return StackedKnn(
        field=field,
        vectors=put(_pad_stack(vecs, (D, dims), np.float32)).astype(jnp.bfloat16),
        norms=put(_pad_stack(norms, (D,), np.float32)),
        exists=put(_pad_stack(exists, (D,), bool)),
        live=put(_pad_stack(live_np, (D,), bool)),
        n_shards=S,
        max_docs=D,
        similarity=sim,
    )


def _put_sharded(arr: np.ndarray, mesh: Mesh | None):
    """Place a [S, ...] stacked array with dim 0 sharded over the 'shard' axis."""
    if mesh is None:
        return jnp.asarray(arr)
    return jax.device_put(arr, NamedSharding(mesh, P("shard")))


# --------------------------------------------------------------------------
# Host-side query preparation
# --------------------------------------------------------------------------


def prepare_query_blocks(
    stacked: StackedBM25,
    queries: List[List[str]],
    bucket: int | None = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Map term lists to per-(query, shard) padded block ids + idf weights.

    Returns (qblocks [Q, S, Bq] i32, qidf [Q, S, Bq] f32). Padding rows use
    block 0 (all-zero) with idf 0. idf is computed from GLOBAL stats so every
    shard scores consistently (ref P5 DFS_QUERY_THEN_FETCH semantics, here
    free because stats are host metadata).
    """
    S = stacked.n_shards
    Q = len(queries)
    per_qs: List[List[Tuple[np.ndarray, float]]] = []
    max_blocks = 1
    # global df per term
    for terms in queries:
        rows: List[Tuple[np.ndarray, float]] = []
        for term in terms:
            df = sum(int(fp.doc_freq[fp.term_to_ord[term]]) if term in fp.term_to_ord else 0
                     for fp in stacked.postings)
            if df == 0:
                continue
            idf = bm25_idf(stacked.total_docs, df)
            rows.append((term, idf))
        per_qs.append(rows)
        # count max blocks over shards
        for s in range(S):
            nb = sum(len(stacked.postings[s].term_block_ids(t)) for t, _ in rows)
            max_blocks = max(max_blocks, nb)
    Bq = bucket or next_bucket(max_blocks)
    qblocks = np.zeros((Q, S, Bq), np.int32)
    qidf = np.zeros((Q, S, Bq), np.float32)
    for qi, rows in enumerate(per_qs):
        for s in range(S):
            fp = stacked.postings[s]
            off = 0
            for term, idf in rows:
                ids = fp.term_block_ids(term)
                n = len(ids)
                if n == 0:
                    continue
                qblocks[qi, s, off: off + n] = ids
                qidf[qi, s, off: off + n] = idf
                off += n
    return qblocks, qidf


# --------------------------------------------------------------------------
# The compiled SPMD programs
# --------------------------------------------------------------------------


def _segmented_run_sums(d, s):
    """Inclusive segmented prefix-sum of s over runs of equal (sorted) d.

    Hillis-Steele doubling: log2(N) shifted conditional adds. Each lane ends
    up with the sum of its run up to itself; run-end lanes hold the full run
    total. Tree-shaped accumulation keeps f32 error at O(log run_len) ulps —
    no long-cumsum cancellation.
    """
    n = d.shape[0]
    total = s
    off = 1
    while off < n:
        d_sh = jnp.concatenate([jnp.full((off,), -1, d.dtype), d[:-off]])
        t_sh = jnp.concatenate([jnp.zeros((off,), total.dtype), total[:-off]])
        total = total + jnp.where(d == d_sh, t_sh, 0.0)
        off *= 2
    return total


def _local_bm25_topk(block_docs, block_tfs, doc_len, live, qblocks, qidf, avgdl, k):
    """Per-device: score this shard for its query slice, local top-k.

    block_docs [T,128], doc_len [D], live [D], qblocks [Q,B], qidf [Q,B].
    Returns (scores [Q,k], ords [Q,k]).

    TPU-native accumulation: scatter-add into a dense [D] vector serializes on
    TPU, so instead we sort the (doc, score) lanes of the selected blocks by
    doc id and reduce runs with a segmented scan — O(N log N) in the postings
    actually touched, independent of corpus size.
    """

    def one_query(qb, qi):
        docs = jnp.take(block_docs, qb, axis=0)          # [B, 128]
        tfs = jnp.take(block_tfs, qb, axis=0)
        dl = jnp.take(doc_len, docs, axis=0)
        denom = tfs + K1 * (1.0 - B + B * dl / avgdl)
        sc = qi[:, None] * tfs * (K1 + 1.0) / denom      # >= 0; pad lanes -> 0
        d = docs.ravel()
        order = jnp.argsort(d)
        d = jnp.take(d, order)
        s = jnp.take(sc.ravel(), order)
        total = _segmented_run_sums(d, s)
        is_last = jnp.concatenate([d[1:] != d[:-1], jnp.ones(1, bool)])
        ok = is_last & (total > 0) & jnp.take(live, d)
        masked = jnp.where(ok, total, -jnp.inf)
        # (score desc, doc asc) rank — doc-id tie-break, Lucene semantics
        neg_s, d_s = jax.lax.sort((-masked, d), num_keys=2)
        return -neg_s[:k], d_s[:k]

    return jax.vmap(one_query)(qblocks, qidf)


_ID_BIAS = 0x40000000          # sets the f32 exponent field: see _pack_ids
_ID_MASK = 0x00FFFFFF          # low 24 bits carry the id (so D < 2**24)


def _pack_ids(x):
    """i32 ids -> f32 lanes for packed single-transfer results.

    A plain bitcast of an id < 2**23 is a SUBNORMAL f32 bit pattern, and the
    TPU flushes subnormals to zero somewhere along the copy/fusion path —
    ids silently became 0 at 10M-doc scale while ids >= 2**23 survived
    (nonzero exponent). OR-ing in a high exponent bit keeps every pattern
    normal; the id lives in the low 24 bits and unpacks with a mask."""
    import jax

    return jax.lax.bitcast_convert_type(
        jnp.bitwise_or(x.astype(jnp.int32), jnp.int32(_ID_BIAS)), jnp.float32)


def unpack_ids_np(f32_lanes: np.ndarray) -> np.ndarray:
    return f32_lanes.view(np.int32) & _ID_MASK


def pack_id_np(x: int) -> np.float32:
    return np.int32(x | _ID_BIAS).view(np.float32)


def _dense_topk_tiebreak(sc, k):
    """Top-k of dense scores over the last axis with ASCENDING-index
    tie-break (Lucene semantics: equal scores rank by doc id).

    A full sort of [.., D] would cost O(D log D) per query; instead two
    O(D log k) top_k passes: (1) plain top-k fixes the k-th score theta and
    every doc strictly above it, (2) among docs scoring exactly theta, top_k
    of -index picks the smallest ids. Ranking the 2k merged candidates by
    (score desc, index asc) is then exact: at most k-1 docs are strictly
    above theta, and ties at theta fill the rest in id order.
    Returns (scores [..., k], indices [..., k] i32)."""
    s1, o1 = jax.lax.top_k(sc, k)
    theta = jax.lax.slice_in_dim(s1, k - 1, k, axis=-1)
    at = sc == theta
    iota = jax.lax.broadcasted_iota(jnp.int32, sc.shape, sc.ndim - 1)
    neg = jnp.where(at, -iota, jnp.iinfo(jnp.int32).min)
    v2, o2 = jax.lax.top_k(neg, k)
    valid2 = (v2 > jnp.iinfo(jnp.int32).min) & (theta > -jnp.inf)
    cs = jnp.where(s1 > theta, s1, -jnp.inf)
    bs = jnp.where(valid2, jnp.broadcast_to(theta, v2.shape), -jnp.inf)
    ms = jnp.concatenate([cs, bs], axis=-1)
    mo = jnp.concatenate([o1.astype(jnp.int32), o2.astype(jnp.int32)], axis=-1)
    neg_ms, mo_s = jax.lax.sort((-ms, mo), num_keys=2, dimension=ms.ndim - 1)
    return (-jax.lax.slice_in_dim(neg_ms, 0, k, axis=-1),
            jax.lax.slice_in_dim(mo_s, 0, k, axis=-1))


def _merge_gathered(scores_g, ords_g, k):
    """[S, Q, k] gathered results -> per-query global top-k with provenance.

    Ties rank by (shard asc, ord asc) so the distributed merge is
    deterministic and matches a single-partition run (Lucene doc-id order)."""
    S, Q, _ = scores_g.shape
    flat_s = jnp.transpose(scores_g, (1, 0, 2)).reshape(Q, S * k)
    flat_o = jnp.transpose(ords_g, (1, 0, 2)).reshape(Q, S * k).astype(jnp.int32)
    shard_idx = jnp.broadcast_to(
        (jnp.arange(S * k, dtype=jnp.int32) // k)[None, :], flat_s.shape)
    neg_s, shard_of, ord_of = jax.lax.sort(
        (-flat_s, shard_idx, flat_o), num_keys=3, dimension=1)
    return (-neg_s[:, :k], shard_of[:, :k], ord_of[:, :k])


@partial(jax.jit, static_argnames=("mesh", "k"))
def _bm25_program(block_docs, block_tfs, doc_len, live, qb, qi, avgdl, *, mesh, k):
    """Compiled once per (mesh, k, shapes): the flagship distributed program."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("shard"), P("shard"),
                  P("dp", "shard"), P("dp", "shard"), P()),
        out_specs=(P("dp"), P("dp"), P("dp")),
        check_vma=False,
    )
    def program(block_docs, block_tfs, doc_len, live, qb, qi, avgdl):
        # local shapes: block_docs [Sl,T,128]; qb [Qd, Sl, B]. A device may
        # hold SEVERAL partitions (segments/shards per chip) — vmap over them
        s_scores, s_ords = jax.vmap(
            lambda bd, bt, dl, lv, b, i: _local_bm25_topk(
                bd, bt, dl, lv, b, i, avgdl, k),
            in_axes=(0, 0, 0, 0, 1, 1))(
            block_docs, block_tfs, doc_len, live, qb, qi)   # [Sl, Qd, k]
        g_scores = _gather_parts(s_scores)                  # [S, Qd, k]
        g_ords = _gather_parts(s_ords)
        top_s, shard_of, ord_of = _merge_gathered(g_scores, g_ords, k)
        return top_s, shard_of, ord_of

    return program(block_docs, block_tfs, doc_len, live, qb, qi, avgdl)


def _gather_parts(x):
    """all_gather local [Sl, ...] partition results into global [S, ...]
    ordered by global partition index (device-major, local-minor — the
    stacked dim-0 order NamedSharding(P('shard')) splits contiguously)."""
    g = jax.lax.all_gather(x, "shard")          # [n_dev, Sl, ...]
    return g.reshape((-1,) + x.shape[1:])


@partial(jax.jit, static_argnames=("mesh", "k"))
def _partition_merge_program(scores, ords, *, mesh, k):
    """Device-side partition top-k merge: all-gather each device's local
    per-partition (score, ord) lanes over 'shard' (ords ride as _pack_ids
    f32 lanes), then run the dense merge kernel on every device.

    scores [Sp, Q, k] f32 sharded P('shard') on dim 0 (<= 0 = empty slot)
    ords   [Sp, Q, k] i32 sharded likewise

    Returns ONE packed [Q, 3, k] f32 array (row 0 scores, rows 1/2 the
    merged partition/ord ids as _pack_ids lanes) so a single transfer
    crosses the host link."""
    from elasticsearch_tpu.parallel.kernels import merge_topk

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("shard"), P("shard")),
        out_specs=P(),
        check_vma=False,
    )
    def program(s, o):
        g_s = _gather_parts(s)                            # [Sp, Q, k]
        g_o = _gather_parts(_pack_ids(o))
        Sp, Q, kk = g_s.shape
        flat_s = jnp.transpose(g_s, (1, 0, 2)).reshape(Q, Sp * kk)
        flat_o = jnp.transpose(g_o, (1, 0, 2)).reshape(Q, Sp * kk)
        flat_o = jnp.bitwise_and(
            jax.lax.bitcast_convert_type(flat_o, jnp.int32),
            jnp.int32(_ID_MASK))
        top_s, top_p, top_o = merge_topk(flat_s, flat_o, k=k)
        return jnp.stack([top_s, _pack_ids(top_p), _pack_ids(top_o)],
                         axis=1)

    return program(scores, ords)


def merge_partition_topk(mesh: Mesh, scores: np.ndarray, ords: np.ndarray,
                         k: int):
    """Merge per-partition top-k results ON DEVICE with the deterministic
    (score desc, partition asc, ord asc) tie-break — the device twin of
    serving.TurboEngine._merge3 (bit-identical: merging permutes exact f32
    score values, it never recomputes them).

    scores [S, Q, k] f32 host array (<= 0 marks an empty slot)
    ords   [S, Q, k] i32 host array (per-partition doc ordinals < 2**24)

    Returns host (scores [Q, k] f32, parts [Q, k] i32, ords [Q, k] i32);
    empty output slots are (0, 0, 0)."""
    G = mesh.shape["shard"]
    S, Q, kk = scores.shape
    Sp = -(-S // G) * G
    if Sp != S:
        scores = np.concatenate(
            [scores, np.zeros((Sp - S, Q, kk), scores.dtype)])
        ords = np.concatenate(
            [ords, np.zeros((Sp - S, Q, kk), ords.dtype)])
    packed = np.asarray(_partition_merge_program(
        jnp.asarray(scores), jnp.asarray(ords.astype(np.int32)),
        mesh=mesh, k=kk))
    return (packed[:, 0].copy(),
            unpack_ids_np(packed[:, 1]),
            unpack_ids_np(packed[:, 2]))


def sharded_bm25_topk(
    mesh: Mesh,
    stacked: StackedBM25,
    qblocks: np.ndarray,   # [Q, S, Bq]
    qidf: np.ndarray,      # [Q, S, Bq]
    k: int = 10,
):
    """Batched BM25 over the mesh.

    Queries shard over 'dp', the corpus shards over 'shard'; each device
    scores its (query-slice x shard) tile, local top-k, all_gather over
    'shard', device-side merge. Returns host arrays
    (scores [Q,k], shard_idx [Q,k], ord [Q,k]).

    Queries are dispatched in power-of-two size classes so a 16-block query
    never pays a 1024-block query's padding (one cached XLA program per
    class; ref analog: per-query cost scales with its own postings the way
    Lucene's BulkScorer does, not with the batch worst case).
    """
    Q = qblocks.shape[0]
    avgdl = jnp.float32(max(stacked.avgdl, 1e-9))
    dp = mesh.shape.get("dp", 1)
    nblocks = np.maximum((qblocks > 0).sum(axis=2).max(axis=1), 1)  # [Q]
    buckets = np.asarray([next_bucket(int(n)) for n in nblocks])

    out_s = np.zeros((Q, k), np.float32)
    out_shard = np.zeros((Q, k), np.int32)
    out_ord = np.zeros((Q, k), np.int32)
    for bucket in np.unique(buckets):
        rows = np.nonzero(buckets == bucket)[0]
        n = len(rows)
        n_pad = -n % dp
        idx = np.concatenate([rows, np.repeat(rows[-1:], n_pad)])
        qb = qblocks[idx][:, :, :bucket]
        qi = qidf[idx][:, :, :bucket]
        top_s, shard_of, ord_of = _bm25_program(
            stacked.block_docs, stacked.block_tfs, stacked.doc_len, stacked.live,
            jnp.asarray(qb), jnp.asarray(qi), avgdl, mesh=mesh, k=k,
        )
        out_s[rows] = np.asarray(top_s)[:n]
        out_shard[rows] = np.asarray(shard_of)[:n]
        out_ord[rows] = np.asarray(ord_of)[:n]
    return out_s, out_shard, out_ord


@partial(jax.jit, static_argnames=("mesh", "k", "similarity"))
def _knn_program(vectors_a, norms_a, exists_a, live_a, queries_a, *, mesh, k, similarity):
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("shard"), P("shard"), P("dp")),
        out_specs=(P("dp"), P("dp"), P("dp")),
        check_vma=False,
    )
    def program(vectors, norms, exists, live, q):
        def one_part(v, nrm, ex, lv):                      # v [D, dims] bf16
            dots = jax.lax.dot_general(
                bf16_operand(q), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [Qd, D]
            if similarity == "cosine":
                # rows are pre-normalized at upload (build_stacked_knn)
                qn = jnp.linalg.norm(q, axis=-1, keepdims=True)
                sc = (1.0 + dots / jnp.maximum(qn, 1e-20)) / 2.0
            elif similarity == "dot_product":
                sc = (1.0 + dots) / 2.0
            else:  # l2_norm
                qq = jnp.sum(q * q, axis=-1, keepdims=True)
                dd = (nrm * nrm)[None, :]
                sc = 1.0 / (1.0 + jnp.sqrt(jnp.maximum(qq + dd - 2.0 * dots, 0.0)))
            ok = ex & lv
            sc = jnp.where(ok[None, :], sc, -jnp.inf)
            return _dense_topk_tiebreak(sc, k)             # [Qd, k]

        s_scores, s_ords = jax.vmap(one_part)(vectors, norms, exists, live)
        return _merge_gathered(_gather_parts(s_scores), _gather_parts(s_ords), k)

    return program(vectors_a, norms_a, exists_a, live_a, queries_a)


def sharded_knn_topk(
    mesh: Mesh,
    stacked: StackedKnn,
    queries: np.ndarray,   # [Q, dims] f32
    k: int = 10,
):
    """Distributed brute-force kNN: local matmul + top-k, gather, merge."""
    top_s, shard_of, ord_of = _knn_program(
        stacked.vectors, stacked.norms, stacked.exists, stacked.live,
        jnp.asarray(queries, jnp.float32),
        mesh=mesh, k=k, similarity=stacked.similarity,
    )
    return np.asarray(top_s), np.asarray(shard_of), np.asarray(ord_of)


# --------------------------------------------------------------------------
# Impact-column cache: BM25 as an MXU matmul
# --------------------------------------------------------------------------
#
# Random-access scatter/gather runs at ~10-15 ns/element on TPU while the MXU
# does dense matmul at >100 TFLOP/s, so the serving-path BM25 is reformulated
# as dense linear algebra: each term owns a dense "impact column" over the
# shard's docs holding its idf-free lane score tf(k1+1)/(tf+norm); a query
# batch is a sparse weight matrix W [Q, C] of idf values over cached columns;
#
#     scores [Q, D] = W @ cache [C, D]      (exact BM25, f32)
#
# followed by live-masking and top-k. Cold terms pay one scatter to build
# their column; Zipfian traffic then hits the cache. This is the TPU analog
# of the reference's hot BulkScorer loop staying in L1: the hot term data
# stays resident in HBM in matmul-ready form.


@partial(jax.jit, static_argnames=("mesh",), donate_argnums=(0,))
def _column_insert_program(cache, block_docs, block_scores, blks, slots, mesh):
    """Build impact columns for new terms and write them into cache slots.

    cache [S, C+1, D] (donated; row C is the scratch/pad slot),
    blks [S, nT, maxB] i32 per-shard block ids (0 = reserved zero block),
    slots [nT] i32 destination rows.
    """

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("shard"), P("shard"), P()),
        out_specs=P("shard"),
        check_vma=False,
    )
    def program(cache, block_docs, block_scores, blks, slots):
        def one_part(c, bd, bs, bl):                     # c [C+1, D]
            docs = jnp.take(bd, bl, axis=0)              # [nT, maxB, 128]
            vals = jnp.take(bs, bl, axis=0)
            c = c.at[slots].set(0.0)
            rows = jnp.broadcast_to(slots[:, None, None], docs.shape)
            # lanes with val 0 (padding and the zero block) may hit (slot, 0);
            # they add exactly 0.0 so doc 0 stays correct.
            return c.at[rows.ravel(), docs.reshape(-1)].add(vals.reshape(-1))

        return jax.vmap(one_part)(cache, block_docs, block_scores, blks)

    return program(cache, block_docs, block_scores, blks, slots)


@partial(jax.jit, static_argnames=("mesh", "k"), donate_argnums=())
def _column_score_program(cache, live, qpacked, mesh, k):
    """scores = W @ cache, mask, top-k, all_gather over 'shard', merge.

    cache [S, C+1, D], live [S, D], qpacked [Q, 2, mT] f32 — row 0 per query
    holds slot ids as floats (pad = C), row 1 the idf weights (pad = 0).
    Returns one packed [Q, 3, k] f32 (score, shard, ord) so callers pay a
    single host fetch per batch (every device->host transfer is a sync).
    """
    C1 = cache.shape[1]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("dp")),
        out_specs=P("dp"),
        check_vma=False,
    )
    def program(cache, live, qpacked):
        Q = qpacked.shape[0]
        qslots = qpacked[:, 0, :].astype(jnp.int32)
        qweights = qpacked[:, 1, :]
        W = jnp.zeros((Q, C1), jnp.float32)
        W = W.at[jnp.arange(Q)[:, None], qslots].add(qweights)
        W = W.at[:, C1 - 1].set(0.0)                     # drop pad slot

        def one_part(c, lv):                             # c [C+1, D]
            scores = jax.lax.dot_general(
                W, c, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)      # [Q, D]
            scores = jnp.where(lv[None, :] & (scores > 0), scores, -jnp.inf)
            return _dense_topk_tiebreak(scores, k)

        s_scores, s_ords = jax.vmap(one_part)(cache, live)
        top_s, shard_of, ord_of = _merge_gathered(
            _gather_parts(s_scores), _gather_parts(s_ords), k)
        # ids ride as biased bit patterns (see _pack_ids: a raw bitcast is
        # subnormal for ids < 2^23 and the TPU flushes those to zero)
        return jnp.stack(
            [top_s, _pack_ids(shard_of), _pack_ids(ord_of)], axis=1)

    return program(cache, live, qpacked)


class Bm25ColumnCache:
    """Device-resident LRU of per-term impact columns over a StackedBM25.

    The serving configuration of the flagship search path: terms used by
    recent query batches keep dense [D] impact columns resident in HBM
    (sharded over the mesh 'shard' axis), and scoring is one W @ cache
    matmul + top-k per batch.
    """

    def __init__(self, stacked: StackedBM25, mesh: Mesh, capacity: int = 2048):
        self.stacked = stacked
        self.mesh = mesh
        self.capacity = capacity
        S, D = stacked.n_shards, stacked.max_docs
        self.cache = jax.device_put(
            jnp.zeros((S, capacity + 1, D), jnp.float32),
            NamedSharding(mesh, P("shard")),
        )
        # slot-pool state shared between concurrent ensure_terms callers:
        # the protect-set read in _evict and the churn accounting must see
        # a consistent pool, or an in-flight batch's slots can be freed
        # under it (PR 12 satellite fix)
        self._lock = threading.Lock()
        self.term_slot: Dict[str, int] = {}   # guarded by: _lock
        self.term_idf: Dict[str, float] = {}  # guarded by: _lock
        self._lru: Dict[str, int] = {}        # guarded by: _lock (term -> tick)
        self._tick = 0                        # guarded by: _lock
        self._free = list(range(capacity))    # guarded by: _lock
        self._slot_bytes = self.cache.nbytes // (capacity + 1)
        self._hbm = hbm_ledger.register_engine(
            self, "spmd_cache", devices=len(mesh.devices.flat))
        self._hbm.set_region("cache", self.cache.nbytes)
        # integrity plane: the slot cache is device-built, so it scrubs
        # against a per-epoch baseline (array identity changes on every
        # legitimate insert) and repairs by dropping to empty — columns
        # rebuild lazily on the next ensure_terms
        integrity.register_scrub_region(
            self, "cache", lambda o: o.cache,
            epoch=lambda o: id(o.cache),
            repair=lambda o: o.reset_cache())

    def reset_cache(self) -> None:
        """Drop every cached column (scrub repair / corruption recovery):
        the device cache re-zeroes and the slot pool restarts empty."""
        from elasticsearch_tpu.common import faults

        with self._lock:
            freed = len(self.term_slot)
            # translation only (device_errors, no fault_point): the repair
            # upload must not be a separately injectable rung
            with faults.device_errors("column_upload"):
                self.cache = jax.device_put(
                    jnp.zeros(self.cache.shape, jnp.float32),
                    NamedSharding(self.mesh, P("shard")))
            if freed:
                self._hbm.note_eviction(
                    count=freed, freed_bytes=self._slot_bytes * freed)
            self.term_slot.clear()
            self.term_idf.clear()
            self._lru.clear()
            self._free = list(range(self.capacity))

    def hbm_bytes(self) -> int:
        return self.cache.nbytes

    def _evict(self, n: int, protect: set) -> List[int]:  # tpulint: holds=_lock
        """Free the n least-recently-used slots, never evicting `protect`.

        Caller holds _lock: the protect set and the LRU order are read,
        and the churn counters bumped, under the same critical section —
        so a concurrent batch can neither free slots this batch's fused
        dispatch still reads nor observe a half-updated pool."""
        victims = [t for t in sorted(self._lru, key=self._lru.get) if t not in protect][:n]
        if len(victims) < n:
            raise ValueError(
                f"query batch references {len(protect)} terms > capacity {self.capacity}")
        slots = []
        for t in victims:
            slots.append(self.term_slot.pop(t))
            del self.term_idf[t]
            del self._lru[t]
        self._hbm.note_eviction(count=len(victims),
                                freed_bytes=self._slot_bytes * len(victims))
        return slots

    def ensure_terms(self, terms: Sequence[str]) -> None:
        """Build + insert impact columns for terms not yet cached."""
        with self._lock:
            self._ensure_terms_locked(terms)

    def _ensure_terms_locked(self, terms: Sequence[str]) -> None:  # tpulint: holds=_lock
        batch_terms = set(terms)
        missing = [t for t in dict.fromkeys(terms) if t not in self.term_slot]
        self._tick += 1
        for t in terms:
            if t in self._lru:
                self._lru[t] = self._tick
        if not missing:
            return
        if len(missing) > self.capacity:
            raise ValueError(f"query batch needs {len(missing)} terms > capacity {self.capacity}")
        self._hbm.note_protect_pressure(
            len(batch_terms & set(self.term_slot)) + len(missing),
            self.capacity)
        if len(missing) > len(self._free):
            self._free.extend(self._evict(len(missing) - len(self._free), batch_terms))

        S = self.stacked.n_shards
        # group terms by block-count size class so insert shapes repeat and
        # the compiled insert program is reused across batches
        nblocks = {
            t: max((len(fp.term_block_ids(t)) for fp in self.stacked.postings), default=0)
            for t in missing
        }
        groups: Dict[int, List[str]] = {}
        for t in missing:
            groups.setdefault(next_bucket(max(nblocks[t], 1), minimum=4), []).append(t)
        for maxB, terms_g in sorted(groups.items()):
            for off in range(0, len(terms_g), 64):
                chunk = terms_g[off: off + 64]
                nT = next_bucket(len(chunk), minimum=4)
                blks = np.zeros((S, nT, maxB), np.int32)
                slots = np.full(nT, self.capacity, np.int32)  # pad -> scratch row
                for j, t in enumerate(chunk):
                    slot = self._free.pop()
                    slots[j] = slot
                    self.term_slot[t] = slot
                    self._lru[t] = self._tick
                    df = 0
                    for s in range(S):
                        fp = self.stacked.postings[s]
                        ids = fp.term_block_ids(t)
                        blks[s, j, : len(ids)] = ids
                        if t in fp.term_to_ord:
                            df += int(fp.doc_freq[fp.term_to_ord[t]])
                    self.term_idf[t] = bm25_idf(self.stacked.total_docs, df) if df else 0.0
                blks_dev = jax.device_put(blks, NamedSharding(self.mesh, P("shard")))
                self.cache = _column_insert_program(
                    self.cache, self.stacked.block_docs, self.stacked.block_scores,
                    blks_dev, jnp.asarray(slots), mesh=self.mesh)

    def search_async(self, queries: List[List[str]], k: int = 10):
        """Dispatch a batch; returns (device_result [Qp,3,k], Q).

        Inputs ride ONE host->device transfer and the result is ONE packed
        array, so a pipeline of batches pays a single round trip each —
        host<->device transfers, not device compute, are the cost to bound.
        """
        st = self.stacked
        self.ensure_terms([t for q in queries for t in q])
        Q = len(queries)
        mT = next_bucket(max((len(q) for q in queries), default=1), minimum=4)
        qpacked = np.zeros((Q, 2, mT), np.float32)
        qpacked[:, 0, :] = self.capacity                 # pad slot
        with self._lock:   # slots must not be evicted while being packed
            for qi, q in enumerate(queries):
                for j, t in enumerate(q):
                    idf = self.term_idf.get(t, 0.0)
                    if idf == 0.0:
                        continue
                    qpacked[qi, 0, j] = self.term_slot[t]
                    qpacked[qi, 1, j] = idf
        dp = self.mesh.shape.get("dp", 1)
        n_pad = -Q % dp
        if n_pad:
            qpacked = np.concatenate([qpacked, np.repeat(qpacked[-1:], n_pad, 0)])
        out = _column_score_program(
            self.cache, st.live, jnp.asarray(qpacked), mesh=self.mesh, k=k)
        return out, Q

    def search(self, queries: List[List[str]], k: int = 10):
        """Batched match-query search. Returns (scores, shard, ord) [Q, k]."""
        out, Q = self.search_async(queries, k)
        packed = np.asarray(out)[:Q]
        return (packed[:, 0],
                unpack_ids_np(packed[:, 1]), unpack_ids_np(packed[:, 2]))
