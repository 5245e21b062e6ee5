"""Block-max culled BM25 serving: the scalable flagship search path.

The TPU answer to Lucene's BlockMaxWAND dynamic pruning (ref:
search/query/TopDocsCollectorContext.java:116, Lucene BMW via
setMinCompetitiveScore; SURVEY.md §5.7 "dense blockwise scoring with
block-max culling masks instead of branchy WAND"). HBM holds the postings
themselves — O(postings), not O(terms x docs) like a dense column cache — and
every query batch runs two fixed-shape device passes:

  pass A  score each term's single best block (by block-max) -> partial
          top-k -> theta[q] = the k-th partial score, a LOWER bound on the
          true k-th total score (partial sums understate totals).
  select  host-side: keep block b of term i iff
              idf_i * block_max[b] + sum_{j != i} term_max_j >= theta
          Any doc whose contribution from some term was dropped provably
          cannot reach theta, so scoring only kept blocks is EXACT.
  pass B  gather kept blocks, segmented-sum per doc, top-k.

Terms with df > total_docs/8 ("hot": stopword-grade, where block culling
cannot help because every block is full) additionally keep a dense impact
column resident in HBM; their contribution is one small W @ columns matmul
on the MXU, and the final top-k merges the dense-only candidates with the
sparse-lane candidates, deduplicating by doc (both are exact where they
overlap — see _one_query_topk).

Queries are processed in fixed Q-chunks with power-of-two block buckets so
XLA compiles a handful of programs total, and all whole-corpus intermediates
([Qc, D] dense scores) stay bounded by the chunk size.
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticsearch_tpu.common import faults, hbm_ledger, integrity
from elasticsearch_tpu.common.health import EngineHealth
from elasticsearch_tpu.ops import bm25_idf, next_bucket
from elasticsearch_tpu.parallel.spmd import (
    B, K1, StackedBM25, _dense_topk_tiebreak, _gather_parts, _merge_gathered,
    _pack_ids, _segmented_run_sums, pack_id_np, unpack_ids_np,
)

HOT_DF_FRACTION = 8     # df > total_docs/8 -> dense column
PASS_A_BLOCKS = 8       # blocks per query in the theta-estimation pass
_HOST_CONJ_DF = 1 << 16  # rarest required term below this -> host conjunction

# (block-bucket B, queries per dispatch Qc): lane work per dispatch stays
# ~bounded (B*128*Qc lanes) so a handful of heavy queries can't inflate the
# padding of thousands of light ones. Compile cache: one program per pair.
_GROUP_SHAPES = [(32, 512), (512, 64), (8192, 8), (32768, 4)]
_MAX_BUCKET = _GROUP_SHAPES[-1][0]
_OVERFLOW_CHUNK = 8192   # blocks per scatter-add dispatch on the overflow path


def _group_shape(n_blocks: int):
    for b, qc in _GROUP_SHAPES:
        if n_blocks <= b:
            return b, qc
    return _GROUP_SHAPES[-1]


@dataclass
class _ShardBlocks:
    """One term's block metadata on one shard (all host arrays)."""

    ids: np.ndarray        # [nb] i32 block rows, doc order
    ub: np.ndarray         # [nb] f32 idf-free block-max scores
    lo: np.ndarray         # [nb] i32 first doc ord per block
    hi: np.ndarray         # [nb] i32 last doc ord per block
    docs: np.ndarray       # [df] i32 sorted doc ords (view into post_doc)
    smax: float            # max ub on this shard
    scores: np.ndarray | None = None   # [df] f32 lane scores, built lazily
    #   for host-side theta estimation on block-heavy queries


_EMPTY_BLOCKS = _ShardBlocks(np.empty(0, np.int32), np.empty(0, np.float32),
                             np.empty(0, np.int32), np.empty(0, np.int32),
                             np.empty(0, np.int32), 0.0)


@dataclass
class _TermMeta:
    """Host metadata for one (global) term across shards."""

    idf: float
    hot_slot: int                       # -1 if not hot
    blocks: List[_ShardBlocks]          # per shard
    max_ub: float                       # max idf-free block-max over shards


class BlockMaxBM25:
    """Serving-path executor for one text field over a (dp, shard) mesh."""

    kind = "blockmax"

    def __init__(self, stacked: StackedBM25, mesh: Mesh):
        assert stacked.block_max_scores is not None, \
            "StackedBM25 built without block_max_scores"
        self.stacked = stacked
        self.mesh = mesh
        self.S = stacked.n_shards
        self.D = stacked.max_docs
        # HBM cap for programs that materialize [Qc, D] dense intermediates
        # (hot matmul + boundary top-k temporaries, ~12 bytes/element): at
        # 10M docs an uncapped Qc=512 chunk would need 20+ GB
        cap = int(4e9 / (12.0 * max(self.D, 1)))
        self._qc_dense_cap = 8
        while self._qc_dense_cap * 2 <= min(cap, 512):
            self._qc_dense_cap *= 2
        self._terms: Dict[str, _TermMeta] = {}
        # circuit state lives here, enforced by the serving layer (this
        # engine has no internal host tier — the dense executor is its
        # fallback)
        self.health = EngineHealth("blockmax")
        self._build_hot_columns()
        # HBM residency ledger: regions mirror hbm_bytes() exactly
        self._hbm = hbm_ledger.register_engine(
            self, "blockmax", devices=len(mesh.devices.flat))
        self._hbm.set_region("block_docs", stacked.block_docs.nbytes)
        self._hbm.set_region("block_scores", stacked.block_scores.nbytes)
        self._hbm.set_region("live", stacked.live.nbytes)
        self._hbm.set_region("hot_cols", self.hot_cols.nbytes)
        # integrity plane: hot_cols is this engine's own upload — scrub it
        # against a per-epoch baseline and repair by a deterministic
        # rebuild from host postings; repeated mismatches trip `health`
        integrity.register_scrub_region(
            self, "hot_cols", lambda o: o.hot_cols,
            epoch=lambda o: id(o.hot_cols),
            repair=lambda o: o._build_hot_columns())
        integrity.attach_scrub_health(self, self.health)

    # ---------------- build ----------------

    def _term_meta(self, term: str) -> _TermMeta | None:
        meta = self._terms.get(term)
        if meta is not None:
            return meta
        st = self.stacked
        df = 0
        blocks: List[_ShardBlocks] = []
        max_ub = 0.0
        for s in range(self.S):
            fp = st.postings[s]
            o = fp.ord(term)
            if o < 0:
                blocks.append(_EMPTY_BLOCKS)
                continue
            df += int(fp.doc_freq[o])
            start, cnt = int(fp.block_start[o]), int(fp.block_count[o])
            ids = np.arange(start, start + cnt, dtype=np.int32)
            ub = st.block_max_scores[s][start: start + cnt]
            docs = fp.post_doc[int(fp.post_start[o]): int(fp.post_start[o + 1])]
            # block doc ranges: docs ascend within a term; trailing pad lanes
            # are zeros so the row max is the true last doc
            bd = fp.block_docs[start: start + cnt]
            smax = float(ub.max()) if cnt else 0.0
            blocks.append(_ShardBlocks(
                ids=ids, ub=ub, lo=bd[:, 0].copy(),
                hi=bd.max(axis=1), docs=docs, smax=smax))
            max_ub = max(max_ub, smax)
        if df == 0:
            return None
        idf = bm25_idf(st.total_docs, df)
        meta = _TermMeta(idf=idf, hot_slot=self._hot_slots.get(term, -1),
                         blocks=blocks, max_ub=max_ub)
        self._terms[term] = meta
        return meta

    def _build_hot_columns(self) -> None:
        """Dense idf-free impact columns for stopword-grade terms."""
        st = self.stacked
        threshold = max(st.total_docs // HOT_DF_FRACTION, 1)
        # global df per term over shards
        df_by_term: Dict[str, int] = {}
        for fp in st.postings:
            for t, o in fp.term_to_ord.items():
                df_by_term[t] = df_by_term.get(t, 0) + int(fp.doc_freq[o])
        hot = sorted(t for t, df in df_by_term.items() if df > threshold)
        self._hot_slots = {t: i for i, t in enumerate(hot)}
        H = next_bucket(max(len(hot), 1), minimum=4)
        cols = np.zeros((self.S, H, self.D), np.float32)
        for s in range(self.S):
            fp = st.postings[s]
            # block_scores host copy for this shard: recompute the lanes from
            # the already-built device array is wasteful; rebuild from tf+norm
            bs = _host_block_scores(fp, st.avgdl)
            for t in hot:
                o = fp.ord(t)
                if o < 0:
                    continue
                start, cnt = int(fp.block_start[o]), int(fp.block_count[o])
                docs = fp.block_docs[start: start + cnt].ravel()
                vals = bs[start: start + cnt].ravel()
                real = vals > 0
                cols[s, self._hot_slots[t], docs[real]] = vals[real]
        self.hot_cols = jax.device_put(
            cols, NamedSharding(self.mesh, P("shard")))
        self.n_hot_slots = H

    # ---------------- query assembly (host) ----------------

    def _assemble(self, queries: List[List[Tuple[str, float]]],
                  selections: List[Dict[str, List[np.ndarray] | None]] | None,
                  bucket: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Build (W [Q,H], qblocks [Q,S,B], qidf [Q,S,B]) for a query group.

        queries: per query, list of (term, boost) with unique terms. When
        selections is None, pass-A assembly: each sparse term contributes its
        single best block per shard. Otherwise selections[q][term] is a per-
        shard list of keep masks (None = keep all blocks)."""
        Q = len(queries)
        W = np.zeros((Q, self.n_hot_slots), np.float32)
        qblocks = np.zeros((Q, self.S, bucket), np.int32)
        qidf = np.zeros((Q, self.S, bucket), np.float32)
        for qi, terms in enumerate(queries):
            offs = [0] * self.S
            for term, boost in terms:
                meta = self._terms.get(term)
                if meta is None:
                    continue
                w = meta.idf * boost
                if meta.hot_slot >= 0:
                    W[qi, meta.hot_slot] += w
                    continue
                for s in range(self.S):
                    sb = meta.blocks[s]
                    if not len(sb.ids):
                        continue
                    if selections is None:
                        j = int(np.argmax(sb.ub))
                        b = sb.ids[j: j + 1]
                    else:
                        masks = selections[qi].get(term)
                        mask = masks[s] if masks is not None else None
                        b = sb.ids if mask is None else sb.ids[mask]
                    n = len(b)
                    if offs[s] + n > bucket:
                        if selections is not None:
                            # pass-B truncation would drop blocks the culling
                            # proof requires — such queries must take the
                            # overflow path (ADVICE r2: this used to silently
                            # return inexact results)
                            raise RuntimeError(
                                f"blockmax bucket overflow: {offs[s] + n} kept "
                                f"blocks > bucket {bucket}; query should have "
                                "been routed to the exhaustive overflow path")
                        # pass-A truncation only weakens theta (a smaller
                        # partial top-k lower bound), never exactness
                        n = bucket - offs[s]
                        b = b[:n]
                    qblocks[qi, s, offs[s]: offs[s] + n] = b
                    qidf[qi, s, offs[s]: offs[s] + n] = w
                    offs[s] += n
        return W, qblocks, qidf

    def _select(self, queries: List[List[Tuple[str, float]]],
                theta: np.ndarray, check=None,
                ) -> Tuple[List[Dict[str, List[np.ndarray] | None]], int]:
        """Block-max culling with doc-range refinement (the BlockMaxWAND
        bound, ref: Lucene MaxScoreCache + impacts): block b of sparse term i
        survives iff

            w_i*ub_i(b) + sum_{j != i} [range(b) hits term j] * w_j*smax_j(s)
                >= theta

        Any doc whose term-i contribution was dropped then satisfies
        total < theta <= true k-th score, so pass B stays EXACT. The range
        test (does term j occur anywhere in b's doc span?) is what lets a
        rare term stop a frequent term's blocks from surviving everywhere.
        Shards partition docs, so all bounds are per-shard. Returns keep
        masks plus the max per-(query, shard) surviving count for bucketing."""
        sel: List[Dict[str, List[np.ndarray] | None]] = []
        max_total = 1
        for qi, terms in enumerate(queries):
            if check is not None and qi % 64 == 0:
                check()   # cooperative cancellation inside the host loop
            entries = [(t, b, self._terms.get(t)) for t, b in terms]
            entries = [(t, b, m) for t, b, m in entries if m is not None]
            th = float(theta[qi])
            keep_q: Dict[str, List[np.ndarray] | None] = {}
            totals = np.zeros(max(self.S, 1), np.int64)
            for t, boost, m in entries:
                if m.hot_slot >= 0:
                    continue
                w = m.idf * boost
                if not np.isfinite(th) or w <= 0:
                    keep_q[t] = None
                    for s in range(self.S):
                        totals[s] += len(m.blocks[s].ids)
                    continue
                masks: List[np.ndarray] = []
                for s in range(self.S):
                    sb = m.blocks[s]
                    if not len(sb.ids):
                        masks.append(np.empty(0, bool))
                        continue
                    bound = w * sb.ub.astype(np.float64)
                    for t2, b2, m2 in entries:
                        if t2 == t:
                            continue
                        w2 = m2.idf * b2
                        if m2.hot_slot >= 0:
                            bound = bound + w2 * m2.max_ub
                            continue
                        sb2 = m2.blocks[s]
                        if not len(sb2.docs):
                            continue
                        pres = (np.searchsorted(sb2.docs, sb.hi, "right")
                                > np.searchsorted(sb2.docs, sb.lo, "left"))
                        bound = bound + pres * (w2 * sb2.smax)
                    mask = bound >= th * (1.0 - 1e-6) - 1e-6
                    masks.append(mask)
                    totals[s] += int(mask.sum())
                keep_q[t] = masks
            sel.append(keep_q)
            max_total = max(max_total, int(totals.max()))
        return sel, max_total

    # ---------------- search ----------------

    def search(self, queries: List[List[str]] | List[List[Tuple[str, float]]],
               k: int = 10):
        """Batched exact BM25 top-k. Returns (scores, shard, ord) [Q, k]."""
        return self.search_many([queries], k)[0]

    def search_many(self, batches: Sequence[List], k: int = 10,
                    check=None, fault_log=None):
        """Pipeline many query batches through the two-pass executor with
        exactly TWO host<->device round trips total: all pass-A programs
        dispatch, thetas come back in one stacked transfer, all pass-B
        programs dispatch, results come back in one stacked transfer. Each
        transfer is a host sync, so two per pipeline keeps QPS compute-bound.

        Pass-B dispatch groups are formed GLOBALLY across batches by
        surviving-block bucket (see _GROUP_SHAPES): a heavy query (two mid-
        frequency terms keeping thousands of blocks) rides a small dispatch
        with a few peers instead of inflating every light query's padding.

        Returns per batch: (scores [Q,k], shard [Q,k], ord [Q,k]).
        Wall-clock per phase lands in self.last_timing (seconds)."""
        import time as _time

        faults.fault_point("blockmax_pass")

        timing = {"assemble_a": 0.0, "theta_fetch": 0.0, "select": 0.0,
                  "assemble_dispatch_b": 0.0, "result_fetch": 0.0,
                  "overflow": 0.0, "n_queries": 0, "n_overflow": 0}
        self.last_timing = timing
        dp = self.mesh.shape.get("dp", 1)
        flat: List[List[Tuple[str, float]]] = []   # all queries, all batches
        spans = []                                 # (batch_idx, start, n)
        for bi, queries in enumerate(batches):
            spans.append((bi, len(flat), len(queries)))
            for q in queries:
                # unique (term, boost): duplicate terms merge their boosts
                agg: Dict[str, float] = {}
                for t in q:
                    t, b = (t, 1.0) if isinstance(t, str) else t
                    agg[t] = agg.get(t, 0.0) + b
                norm = list(agg.items())
                for t, _ in norm:
                    self._term_meta(t)
                flat.append(norm)
        if not flat:
            return []

        timing["n_queries"] = len(flat)
        # ---- pass A: small shape, ADAPTIVE chunk size (a single query must
        # not pay a 512-query dispatch's padding — its latency is the
        # product's per-search latency) ----
        t0 = _time.monotonic()
        qa_b, qa_max = PASS_A_BLOCKS, _GROUP_SHAPES[0][1]
        qa_max = min(qa_max, self._qc_dense_cap)
        a_packed = []   # (packed result, real query count) — padding may land
        off = 0         # in ANY chunk (qa_qc = max(dp, ...) can exceed the
        while off < len(flat):   # chunk), so slice per chunk (ADVICE r3)
            chunk = flat[off: off + qa_max]
            off += len(chunk)
            n_real = len(chunk)
            # two sizes only (8 or the capped max): every extra (shape)
            # pair is a fresh XLA compile — keep the program cache tiny
            qa_qc = max(dp, 8 if len(chunk) <= 8 else qa_max)
            if len(chunk) < qa_qc:
                chunk = chunk + [chunk[-1]] * (qa_qc - len(chunk))
            W, qb, qi_ = self._assemble(chunk, None, qa_b)
            a_packed.append((_hybrid_program(
                self.stacked.block_docs, self.stacked.block_scores,
                self.stacked.live, self.hot_cols,
                jnp.asarray(W), jnp.asarray(qb), jnp.asarray(qi_),
                mesh=self.mesh, k=k, tiebreak=False), n_real))
        t1 = _time.monotonic()
        timing["assemble_a"] = t1 - t0
        # one transfer: theta for every query
        thetas = np.asarray(jnp.concatenate(
            [p[:n, 0, k - 1] for p, n in a_packed]))[: len(flat)]
        t2 = _time.monotonic()
        timing["theta_fetch"] = t2 - t1

        # ---- selection, then global grouping by bucket ----
        selections, _ = self._select(flat, thetas, check=check)
        timing["select"] = _time.monotonic() - t2
        totals = np.zeros(len(flat), np.int64)
        for qi, terms in enumerate(flat):
            per_shard = np.zeros(max(self.S, 1), np.int64)
            for t, _ in terms:
                m = self._terms.get(t)
                if m is None or m.hot_slot >= 0:
                    continue
                masks = selections[qi].get(t)
                for s in range(self.S):
                    nb = len(m.blocks[s].ids)
                    if masks is not None and len(masks[s]):
                        nb = int(masks[s].sum())
                    per_shard[s] += nb
            totals[qi] = per_shard.max()

        # group key: (bucket shape, query-has-hot-terms) — lane-only groups
        # dispatch a program without the dense matmul / dense top-k
        groups: Dict[Tuple[Tuple[int, int], bool], List[int]] = {}
        overflow: List[int] = []
        for qi, tot in enumerate(totals):
            if int(tot) > _MAX_BUCKET:
                # more surviving blocks than the largest dispatch bucket:
                # bucketed assembly would have to drop blocks (inexact) —
                # take the chunked scatter-add path instead
                overflow.append(qi)
            else:
                has_hot = any(
                    (m := self._terms.get(t)) is not None and m.hot_slot >= 0
                    for t, _ in flat[qi])
                groups.setdefault((_group_shape(int(tot)), has_hot),
                                  []).append(qi)

        t3 = _time.monotonic()
        pending = []   # (query_indices, packed)
        for ((bucket, qc_max), has_hot), members in sorted(groups.items()):
            if has_hot:   # dense [Qc, D] intermediates: respect the HBM cap
                qc_max = min(qc_max, self._qc_dense_cap)
            for off in range(0, len(members), qc_max):
                grp = members[off: off + qc_max]
                idxs = list(grp)
                # adaptive padding, TWO sizes only: a small tail chunk
                # dispatches at Qc=8 instead of the nominal size; more size
                # classes would multiply compiles for marginal padding wins
                qc = max(dp, 8 if len(grp) <= 8 else qc_max)
                chunk = [flat[qi] for qi in grp]
                sels = [selections[qi] for qi in grp]
                if len(chunk) < qc:
                    pad = qc - len(chunk)
                    chunk = chunk + [chunk[-1]] * pad
                    sels = sels + [sels[-1]] * pad
                if check is not None:
                    check()
                W, qb, qi_ = self._assemble(chunk, sels, bucket)
                # compile telemetry: (block bucket, padded Qc, program
                # flavor) pins the compiled shape
                shape_key = (bucket, qc, "hot" if has_hot else "lane")
                first_trace = hbm_ledger.note_dispatch("blockmax", shape_key)
                tb0 = _time.monotonic()
                if has_hot:
                    packed_b = _hybrid_program(
                        self.stacked.block_docs, self.stacked.block_scores,
                        self.stacked.live, self.hot_cols,
                        jnp.asarray(W), jnp.asarray(qb), jnp.asarray(qi_),
                        mesh=self.mesh, k=k)
                else:
                    packed_b = _lane_program(
                        self.stacked.block_docs, self.stacked.block_scores,
                        self.stacked.live,
                        jnp.asarray(qb), jnp.asarray(qi_),
                        mesh=self.mesh, k=k)
                if first_trace:
                    hbm_ledger.note_compile_done(
                        "blockmax", shape_key, _time.monotonic() - tb0)
                pending.append((idxs, packed_b))
        t4 = _time.monotonic()
        timing["assemble_dispatch_b"] = t4 - t3

        # one transfer: all groups' packed results (flattened; ragged shapes)
        out_all = np.zeros((len(flat), 3, k), np.float32)
        if pending:
            flat_out = np.asarray(jnp.concatenate(
                [p.reshape(-1, 3 * k) for _, p in pending], axis=0))
            row = 0
            for idxs, p in pending:
                n_rows = p.shape[0]
                grp_out = flat_out[row: row + n_rows].reshape(n_rows, 3, k)
                row += n_rows
                out_all[idxs] = grp_out[: len(idxs)]
        t5 = _time.monotonic()
        timing["result_fetch"] = t5 - t4
        timing["n_overflow"] = len(overflow)
        for qi in overflow:
            out_all[qi] = self._exhaustive_topk(flat[qi], selections[qi], k)
        timing["overflow"] = _time.monotonic() - t5

        results = []
        for bi, start, n in spans:
            packed = out_all[start: start + n]
            results.append((packed[:, 0], unpack_ids_np(packed[:, 1]),
                            unpack_ids_np(packed[:, 2])))
        return results

    def _exhaustive_topk(self, terms: List[Tuple[str, float]],
                         selection: Dict[str, List[np.ndarray] | None],
                         k: int) -> np.ndarray:
        """Exact fallback for block-heavy queries: chunked scatter-add of
        every kept block's lanes into a per-shard dense [D] accumulator, then
        one top-k. No bucket truncation can occur, so exactness holds for any
        surviving-block count; cost is O(kept blocks) dispatches of fixed
        shape plus one [S, D] accumulator (ADVICE r2: the bucketed path used
        to silently drop blocks past the largest bucket). Returns packed
        [3, k] (score, shard bitcast, ord bitcast) like the bucketed path."""
        S = self.S
        per_shard: List[List[Tuple[np.ndarray, float]]] = [[] for _ in range(S)]
        W = np.zeros((1, self.n_hot_slots), np.float32)
        for t, boost in terms:
            m = self._terms.get(t)
            if m is None:
                continue
            w = m.idf * boost
            if m.hot_slot >= 0:
                W[0, m.hot_slot] += w
                continue
            masks = selection.get(t)
            for s in range(S):
                sb = m.blocks[s]
                if not len(sb.ids):
                    continue
                mask = None if masks is None else masks[s]
                b = sb.ids if mask is None else sb.ids[mask]
                if len(b):
                    per_shard[s].append((b, w))
        ids_ws = []
        n_chunks = 1
        for s in range(S):
            if per_shard[s]:
                ids = np.concatenate([b for b, _ in per_shard[s]])
                ws = np.concatenate([np.full(len(b), w, np.float32)
                                     for b, w in per_shard[s]])
            else:
                ids = np.empty(0, np.int32)
                ws = np.empty(0, np.float32)
            ids_ws.append((ids, ws))
            n_chunks = max(n_chunks, -(-len(ids) // _OVERFLOW_CHUNK))
        acc = jax.jit(
            lambda: jnp.zeros((S, self.D), jnp.float32),
            out_shardings=NamedSharding(self.mesh, P("shard")))()
        for c in range(n_chunks):
            qb = np.zeros((S, _OVERFLOW_CHUNK), np.int32)
            qw = np.zeros((S, _OVERFLOW_CHUNK), np.float32)
            for s, (ids, ws) in enumerate(ids_ws):
                seg = slice(c * _OVERFLOW_CHUNK, (c + 1) * _OVERFLOW_CHUNK)
                part = ids[seg]
                qb[s, : len(part)] = part
                qw[s, : len(part)] = ws[seg]
            acc = _scatter_chunk(
                self.stacked.block_docs, self.stacked.block_scores, acc,
                jnp.asarray(qb), jnp.asarray(qw), mesh=self.mesh)
        packed = _acc_topk(acc, self.hot_cols, self.stacked.live,
                           jnp.asarray(W), mesh=self.mesh, k=k)
        return np.asarray(packed)[0]

    def search_bool(self, queries: Sequence[dict], k: int = 10,
                    check=None, fault_log=None):
        """Batched exact `bool` top-k on device (BASELINE config 2 — the
        reference's WAND/conjunction path, ref: Lucene BooleanWeight +
        MinShouldMatchSumScorer driven through BlockMaxConjunctionScorer).

        Each query is {"must": [(term, boost)...], "should": [...],
        "filter": [terms...]}: a hit must contain EVERY must and filter
        term; its score sums the BM25 contributions of the matching must +
        should terms (filters score 0). TPU-native execution: all terms'
        blocks dispatch in one fixed-shape program; per-lane must-flags are
        segment-summed per doc alongside the scores, so coverage==n_required
        is one vector compare — no doc-at-a-time conjunction walking. Hot
        terms contribute through the dense column matmul, with a presence
        matmul (Wp @ (col>0)) supplying their coverage counts.

        Returns (scores [Q,k], shard [Q,k], ord [Q,k]), doc-id tie-break.

        Executor choice per query mirrors Lucene's lead-cost logic: when the
        rarest REQUIRED term is selective (df <= _HOST_CONJ_DF), candidate
        sets are tiny and a host sparse intersection beats shipping every
        block to the device by orders of magnitude; heavy conjunctions
        (stopword-grade musts) go to the device program where the dense
        matmul amortizes."""
        faults.fault_point("blockmax_pass")
        Q = len(queries)
        out = np.zeros((Q, 3, k), np.float32)
        specs = []
        totals = np.zeros(Q, np.int64)
        host_path: List[int] = []
        for qi_, spec in enumerate(queries):
            must = [(t, b, True) for t, b in spec.get("must", ())]
            must += [(t, 0.0, True) for t in spec.get("filter", ())]
            should = [(t, b, False) for t, b in spec.get("should", ())]
            rows = []
            nm = 0
            n_req_present = 0
            min_req_df = None
            per_shard = np.zeros(max(self.S, 1), np.int64)
            for t, b, required in must + should:
                m = self._term_meta(t)
                if required:
                    nm += 1
                if m is None:
                    continue
                rows.append((t, b, required, m))
                if required:
                    n_req_present += 1
                    df = sum(len(m.blocks[s].docs) for s in range(self.S))
                    min_req_df = df if min_req_df is None else min(min_req_df, df)
                if m.hot_slot < 0:
                    for s in range(self.S):
                        per_shard[s] += len(m.blocks[s].ids)
            specs.append((rows, nm))
            totals[qi_] = per_shard.max()
            if nm > n_req_present:
                # a required term is missing globally: provably empty
                continue
            host_cut = max(_HOST_CONJ_DF, self.stacked.total_docs // 4)
            if nm > 0 and (min_req_df or 0) <= host_cut:
                # conjunction output is bounded by the rarest required term:
                # the sparse host merge beats shipping every block up to
                # stopword-grade selectivity (measured: device only wins
                # when ALL required terms are dense-column material)
                host_path.append(qi_)

        for qi_ in host_path:
            out[qi_] = self._bool_host(*specs[qi_], k)

        host_set = set(host_path)
        groups: Dict[Tuple[int, int], List[int]] = {}
        overflow: List[int] = []
        for qi_, tot in enumerate(totals):
            rows, nm = specs[qi_]
            if qi_ in host_set or nm > sum(
                    1 for _, _, req, _ in rows if req):
                continue
            if int(tot) > _MAX_BUCKET:
                overflow.append(qi_)
            else:
                groups.setdefault(_group_shape(int(tot)), []).append(qi_)
        for qi_ in overflow:
            out[qi_] = self._bool_exhaustive(*specs[qi_], k)
        for (bucket, qc), members in sorted(groups.items()):
            # _bool_program holds TWO [Qc, D] dense intermediates
            qc = min(qc, max(self._qc_dense_cap // 2, 8))
            qc = max(qc, self.mesh.shape.get("dp", 1))
            for off in range(0, len(members), qc):
                if check is not None:
                    check()
                grp = members[off: off + qc]
                pad = qc - len(grp)
                use = grp + [grp[-1]] * pad
                W = np.zeros((qc, self.n_hot_slots), np.float32)
                Wp = np.zeros((qc, self.n_hot_slots), np.float32)
                nm_arr = np.zeros(qc, np.float32)
                qb = np.zeros((qc, self.S, bucket), np.int32)
                qi = np.zeros((qc, self.S, bucket), np.float32)
                qf = np.zeros((qc, self.S, bucket), np.float32)
                for row_i, qx in enumerate(use):
                    rows, nm = specs[qx]
                    nm_arr[row_i] = nm
                    offs = [0] * self.S
                    for t, b, required, m in rows:
                        w = m.idf * b
                        if m.hot_slot >= 0:
                            W[row_i, m.hot_slot] += w
                            if required:
                                # += : a term required twice (must + filter)
                                # must contribute 2 toward coverage == nm
                                Wp[row_i, m.hot_slot] += 1.0
                            continue
                        for s in range(self.S):
                            sb = m.blocks[s]
                            n = len(sb.ids)
                            if not n:
                                continue
                            qb[row_i, s, offs[s]: offs[s] + n] = sb.ids
                            qi[row_i, s, offs[s]: offs[s] + n] = w
                            if required:
                                qf[row_i, s, offs[s]: offs[s] + n] = 1.0
                            offs[s] += n
                packed = _bool_program(
                    self.stacked.block_docs, self.stacked.block_scores,
                    self.stacked.live, self.hot_cols,
                    jnp.asarray(W), jnp.asarray(Wp), jnp.asarray(qb),
                    jnp.asarray(qi), jnp.asarray(qf), jnp.asarray(nm_arr),
                    mesh=self.mesh, k=k)
                out[grp] = np.asarray(packed)[: len(grp)]
        return out[:, 0], unpack_ids_np(out[:, 1]), unpack_ids_np(out[:, 2])

    def _host_bs(self, s: int) -> np.ndarray:
        cache = getattr(self, "_host_bs_cache", None)
        if cache is None:
            cache = self._host_bs_cache = {}
        if s not in cache:
            cache[s] = _host_block_scores(self.stacked.postings[s],
                                          self.stacked.avgdl)
        return cache[s]

    def _term_impacts(self, m: _TermMeta, s: int) -> np.ndarray:
        """Per-posting idf-free impact scores aligned with blocks[s].docs."""
        sb = m.blocks[s]
        if sb.scores is None:
            bs = self._host_bs(s)
            sb.scores = bs[sb.ids].ravel()[: len(sb.docs)]
        return sb.scores

    def _bool_host(self, rows, nm: int, k: int) -> np.ndarray:
        """Selective conjunction on host: sorted-posting intersection of the
        required terms, vectorized score lookups for every clause (the
        sparse analog of Lucene's ConjunctionDISI + WANDScorer lead-cost
        iteration). Exact; cost O(df of the rarest required term)."""
        cand_out: List[Tuple[float, int, int]] = []
        lh = self.stacked.live_host
        for s in range(self.S):
            req = [m.blocks[s].docs for _, _, r, m in rows if r]
            if any(len(docs) == 0 for docs in req) or not req:
                continue
            req.sort(key=len)
            cand = req[0]
            for docs in req[1:]:
                cand = cand[np.isin(cand, docs, assume_unique=True)]
                if not len(cand):
                    break
            if not len(cand):
                continue
            if lh is not None and not lh[s].all():
                cand = cand[lh[s][cand]]
                if not len(cand):
                    continue
            scores = np.zeros(len(cand), np.float64)
            for t, b, req_, m in rows:
                sb = m.blocks[s]
                if not len(sb.docs):
                    continue
                imp = self._term_impacts(m, s)
                j = np.searchsorted(sb.docs, cand)
                present = j < len(sb.docs)
                present[present] = sb.docs[j[present]] == cand[present]
                w = m.idf * b
                scores += np.where(present, w * imp[np.minimum(j, len(imp) - 1)], 0.0)
            keep = scores > 0
            cand, scores = cand[keep], scores[keep]
            if len(cand) > k:
                sel = np.lexsort((cand, -scores))[:k]
                cand, scores = cand[sel], scores[sel]
            cand_out.extend((float(scores[i]), s, int(cand[i]))
                            for i in range(len(cand)))
        cand_out.sort(key=lambda x: (-x[0], x[1], x[2]))
        packed = np.zeros((3, k), np.float32)
        for j, (sc, s, d) in enumerate(cand_out[:k]):
            packed[0, j] = sc
            packed[1, j] = pack_id_np(s)
            packed[2, j] = pack_id_np(d)
        return packed

    def _bool_exhaustive(self, rows, nm: int, k: int) -> np.ndarray:
        """Host fallback for block-heavy bool queries (> _MAX_BUCKET blocks
        per shard): dense [D] score+coverage accumulators per shard via
        bincount — exact for any block count. Returns packed [3, k]."""
        hot_np = None
        cand: List[Tuple[float, int, int]] = []
        for s in range(self.S):
            scores = np.zeros(self.D, np.float32)
            cover = np.zeros(self.D, np.int32)
            fp = self.stacked.postings[s]
            bs = _host_block_scores(fp, self.stacked.avgdl)
            for t, b, required, m in rows:
                w = m.idf * b
                if m.hot_slot >= 0:
                    if hot_np is None:
                        hot_np = np.asarray(self.hot_cols)
                    col = hot_np[s, m.hot_slot]
                    scores += (w * col).astype(np.float32)
                    if required:
                        cover += (col > 0)
                    continue
                sb = m.blocks[s]
                if not len(sb.ids):
                    continue
                docs = fp.block_docs[sb.ids].ravel()
                vals = bs[sb.ids].ravel()
                nz = vals > 0
                scores += np.bincount(docs[nz], weights=w * vals[nz],
                                      minlength=self.D).astype(np.float32)
                if required:
                    cover[docs[nz]] += 1
            live = np.asarray(self.stacked.live[s])
            ok = (cover == nm) & live[: self.D] & (scores > 0)
            docs = np.nonzero(ok)[0]
            if len(docs):
                sel = np.lexsort((docs, -scores[docs]))[:k]
                cand.extend((float(scores[docs[i]]), s, int(docs[i]))
                            for i in sel)
        cand.sort(key=lambda x: (-x[0], x[1], x[2]))
        packed = np.zeros((3, k), np.float32)
        for j, (sc, s, d) in enumerate(cand[:k]):
            packed[0, j] = sc
            packed[1, j] = pack_id_np(s)
            packed[2, j] = pack_id_np(d)
        return packed

    def search_phrase(self, phrases: Sequence[List[str]], k: int = 10,
                      slop: int = 0,
                      live_host: Sequence[np.ndarray] | None = None):
        """Batched exact match_phrase top-k (ref: Lucene PhraseQuery via
        PhraseScorer; BASELINE config 3).

        The conjunction + positional verify runs as columnar host passes
        (index/positions.py — candidate sets after intersection are tiny, a
        device round trip would dominate), scoring is BM25 over the phrase
        frequency with summed idf, matching the dense executor's
        _exec_MatchPhraseQuery semantics exactly. Returns
        (scores [Q,k], shard [Q,k], ord [Q,k]) with doc-order tie-break."""
        from elasticsearch_tpu.index.positions import phrase_freqs

        st = self.stacked
        Q = len(phrases)
        out_s = np.zeros((Q, k), np.float32)
        out_shard = np.zeros((Q, k), np.int32)
        out_ord = np.zeros((Q, k), np.int32)
        for qi, terms in enumerate(phrases):
            idf_sum = 0.0
            for t in terms:
                df_t = sum(
                    int(fp.doc_freq[fp.term_to_ord[t]]) if t in fp.term_to_ord else 0
                    for fp in st.postings)
                if df_t:
                    idf_sum += bm25_idf(st.total_docs, df_t)
            all_s: List[np.ndarray] = []
            all_shard: List[np.ndarray] = []
            all_ord: List[np.ndarray] = []
            for s in range(self.S):
                fp = st.postings[s]
                docs, pf = phrase_freqs(fp, list(terms), slop=slop)
                if live_host is not None and len(docs):
                    keep = live_host[s][docs]
                    docs, pf = docs[keep], pf[keep]
                if not len(docs):
                    continue
                dl = fp.doc_len[docs]
                denom = pf + K1 * (1.0 - B + B * dl / max(st.avgdl, 1e-9))
                sc = (idf_sum * pf * (K1 + 1.0) / denom).astype(np.float32)
                if len(sc) > k:
                    # stable (score desc, doc asc) selection so tied scores
                    # keep the lowest doc ords — same tie-break as the final
                    # cross-shard merge below
                    part = np.lexsort((docs, -sc))[:k]
                    docs, sc = docs[part], sc[part]
                all_s.append(sc)
                all_shard.append(np.full(len(sc), s, np.int32))
                all_ord.append(docs.astype(np.int32))
            if not all_s:
                continue
            sc = np.concatenate(all_s)
            sh = np.concatenate(all_shard)
            od = np.concatenate(all_ord)
            order = np.lexsort((od, sh, -sc))[:k]
            out_s[qi, : len(order)] = sc[order]
            out_shard[qi, : len(order)] = sh[order]
            out_ord[qi, : len(order)] = od[order]
        return out_s, out_shard, out_ord

    def _is_sparse(self, term: str) -> bool:
        meta = self._terms.get(term)
        return meta is not None and meta.hot_slot < 0

    def hbm_bytes(self) -> int:
        st = self.stacked
        total = st.block_docs.nbytes + st.block_scores.nbytes + st.live.nbytes
        total += self.hot_cols.nbytes
        return total


def _host_block_scores(fp, avgdl: float) -> np.ndarray:
    """Idf-free lane scores on host (same formula as build_stacked_bm25)."""
    from elasticsearch_tpu.parallel.spmd import B as B_, K1

    dl = fp.doc_len[fp.block_docs]
    denom = fp.block_tfs + K1 * (1.0 - B_ + B_ * dl / max(avgdl, 1e-9))
    return np.where(fp.block_tfs > 0,
                    fp.block_tfs * (K1 + 1.0) / denom, 0.0).astype(np.float32)


# --------------------------------------------------------------------------
# device programs
# --------------------------------------------------------------------------


def _lane_candidates(d, s, extra_per_doc, live, k, tiebreak):
    """Lane path: segmented-run totals over sorted (doc, score) lanes ->
    top-k candidates. extra_per_doc is the per-doc hot/dense addend (None
    for lane-only queries). tiebreak=False uses plain top_k — for theta
    estimation, where any k-th value is a valid lower bound."""
    order = jnp.argsort(d)
    d = jnp.take(d, order)
    s = jnp.take(s, order)
    tot = _segmented_run_sums(d, s)
    is_last = jnp.concatenate([d[1:] != d[:-1], jnp.ones(1, bool)])
    lane_tot = tot if extra_per_doc is None else tot + jnp.take(extra_per_doc, d)
    ok = is_last & (tot > 0) & jnp.take(live, d)
    masked = jnp.where(ok, lane_tot, -jnp.inf)
    if tiebreak:
        neg2, d2 = jax.lax.sort((-masked, d), num_keys=2)
        return -neg2[:k], d2[:k]
    top_s, idx = jax.lax.top_k(masked, k)
    return top_s, jnp.take(d, idx)


def _one_query_topk(d, s, dense, live, k, tiebreak=True):
    """Exact top-k for one query on one shard.

    d [L] lane doc ids (concatenated kept blocks), s [L] lane scores
    (idf-weighted), dense [D] this query's hot-term score per doc.

    Correctness: within a term a doc occupies exactly one block, so a lane's
    segmented-run total over sorted (doc, score) lanes is the doc's full
    sparse score over the KEPT blocks; culling guarantees docs with any
    dropped contribution cannot reach theta. Dense-only docs are exact in
    cand1; docs with sparse lanes are exact in cand2; the merge dedups by doc
    keeping the max, which is always the exact variant.
    """
    cand2_s, cand2_d = _lane_candidates(d, s, dense, live, k, tiebreak)
    dense_masked = jnp.where(live & (dense > 0), dense, -jnp.inf)
    if tiebreak:
        cand1_s, cand1_d = _dense_topk_tiebreak(dense_masked, k)
    else:
        cand1_s, cand1_d = jax.lax.top_k(dense_masked, k)
    ms = jnp.concatenate([cand1_s, cand2_s])
    md = jnp.concatenate([cand1_d.astype(jnp.int32), cand2_d])
    # dedup by doc, keeping the best score: order by (doc asc, score desc)
    md2, neg_ms2 = jax.lax.sort((md, -ms), num_keys=2)
    ms2 = -neg_ms2
    first = jnp.concatenate([jnp.ones(1, bool), md2[1:] != md2[:-1]])
    final = jnp.where(first & (ms2 > -jnp.inf), ms2, -jnp.inf)
    # final rank by (score desc, doc asc)
    neg_f, md3 = jax.lax.sort((-final, md2), num_keys=2)
    return -neg_f[:k], md3[:k]


@partial(jax.jit, static_argnames=("mesh",), donate_argnums=(2,))
def _scatter_chunk(block_docs, block_scores, acc, qb, qw, *, mesh):
    """Overflow path, accumulate step: add one chunk of kept blocks' lane
    scores into the per-shard dense accumulator. Pad slots carry weight 0 so
    they contribute nothing (block 0's lanes get +0)."""

    @partial(
        shard_map, mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("shard"), P("shard"), P("shard")),
        out_specs=P("shard"), check_vma=False)
    def program(bd, bs, acc, qb, qw):
        def one_part(bd1, bs1, acc1, qb1, qw1):
            docs = jnp.take(bd1, qb1, axis=0)            # [C, 128]
            sc = qw1[:, None] * jnp.take(bs1, qb1, axis=0)
            return acc1.at[docs.ravel()].add(sc.ravel())

        return jax.vmap(one_part)(bd, bs, acc, qb, qw)

    return program(block_docs, block_scores, acc, qb, qw)


@partial(jax.jit, static_argnames=("mesh", "k"))
def _acc_topk(acc, hot_cols, live, W, *, mesh, k):
    """Overflow path, final step: sparse accumulator + dense hot matmul ->
    exact merged top-k, packed [1, 3, k] (same candidate rule as
    _one_query_topk: live and (some sparse lane or some hot contribution))."""

    @partial(
        shard_map, mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("shard"), P()),
        out_specs=P(), check_vma=False)
    def program(acc, hc, lv, W):
        def one_part(acc1, hc1, lv1):
            dense = jax.lax.dot_general(                 # [1, D]
                W, hc1, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            a = acc1[None]
            tot = a + dense
            ok = lv1[None] & ((a > 0) | (dense > 0))
            s, o = _dense_topk_tiebreak(jnp.where(ok, tot, -jnp.inf), k)
            return s, o.astype(jnp.int32)

        s, o = jax.vmap(one_part)(acc, hc, lv)           # [Sl, 1, k]
        top_s, shard_of, ord_of = _merge_gathered(
            _gather_parts(s), _gather_parts(o), k)
        return jnp.stack(
            [top_s, _pack_ids(shard_of), _pack_ids(ord_of)], axis=1)

    return program(acc, hot_cols, live, W)


def _one_query_topk_bool(d, s, c, dense, hp, live, nm, k):
    """Exact bool top-k for one query on one partition.

    d/s as in _one_query_topk; c [L] per-lane must-flags (1.0 where the lane
    belongs to a required term and is a real posting), dense [D] hot-term
    scores, hp [D] hot-term must-presence counts, nm scalar required count.
    A doc qualifies iff its summed must-flags + hot presences == nm."""
    order = jnp.argsort(d)
    d = jnp.take(d, order)
    s = jnp.take(s, order)
    c = jnp.take(c, order)
    tot = _segmented_run_sums(d, s)
    cnt = _segmented_run_sums(d, c)
    is_last = jnp.concatenate([d[1:] != d[:-1], jnp.ones(1, bool)])
    lane_tot = tot + jnp.take(dense, d)
    lane_cov = cnt + jnp.take(hp, d)
    # NOTE: no (tot > 0) gate — a doc can qualify through weight-0 filter
    # lanes with its entire score coming from hot columns (lane_tot > 0
    # still excludes score-0 docs and the zero-block padding run on doc 0,
    # whose cf lanes are 0 so it cannot fake coverage)
    ok = (is_last & jnp.take(live, d)
          & (jnp.abs(lane_cov - nm) < 0.5) & (lane_tot > 0))
    neg2, cand2_d = jax.lax.sort(
        (-jnp.where(ok, lane_tot, -jnp.inf), d), num_keys=2)
    cand2_s, cand2_d = -neg2[:k], cand2_d[:k]
    # dense-only candidates: all required terms hot-present, positive score
    ok1 = live & (dense > 0) & (jnp.abs(hp - nm) < 0.5)
    cand1_s, cand1_d = _dense_topk_tiebreak(
        jnp.where(ok1, dense, -jnp.inf), k)
    ms = jnp.concatenate([cand1_s, cand2_s])
    md = jnp.concatenate([cand1_d.astype(jnp.int32), cand2_d])
    md2, neg_ms2 = jax.lax.sort((md, -ms), num_keys=2)
    ms2 = -neg_ms2
    first = jnp.concatenate([jnp.ones(1, bool), md2[1:] != md2[:-1]])
    final = jnp.where(first & (ms2 > -jnp.inf), ms2, -jnp.inf)
    neg_f, md3 = jax.lax.sort((-final, md2), num_keys=2)
    return -neg_f[:k], md3[:k]


@partial(jax.jit, static_argnames=("mesh", "k"))
def _bool_program(block_docs, block_scores, live, hot_cols, W, Wp, qb, qi, qf,
                  nm, *, mesh, k):
    """Exact bool (conjunction + optional scorers) over the mesh.

    Shapes as _hybrid_program plus Wp [Q,H] must-hot masks, qf [Q,S,B]
    per-block must flags, nm [Q] required-term counts. Output packed
    [Q,3,k]."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("shard"), P("shard"),
                  P("dp"), P("dp"), P("dp", "shard"), P("dp", "shard"),
                  P("dp", "shard"), P("dp")),
        out_specs=P("dp"),
        check_vma=False,
    )
    def program(block_docs, block_scores, live, hot_cols, W, Wp, qb, qi, qf, nm):
        def one_part(bd, bs, lv, hc, qb1, qi1, qf1):
            dense = jax.lax.dot_general(
                W, hc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)          # [Qc, D]
            pres = jax.lax.dot_general(
                Wp, (hc > 0).astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)          # [Qc, D]
            docs = jnp.take(bd, qb1, axis=0)                  # [Qc, B, 128]
            sc_lane = jnp.take(bs, qb1, axis=0)
            sc = qi1[:, :, None] * sc_lane
            cf = qf1[:, :, None] * (sc_lane > 0)              # real postings only
            Qc = qb1.shape[0]
            return jax.vmap(
                lambda dd, ss, cc, dn, pp, n1: _one_query_topk_bool(
                    dd, ss, cc, dn, pp, lv, n1, k))(
                docs.reshape(Qc, -1), sc.reshape(Qc, -1), cf.reshape(Qc, -1),
                dense, pres, nm)

        s_scores, s_ords = jax.vmap(
            one_part, in_axes=(0, 0, 0, 0, 1, 1, 1))(
            block_docs, block_scores, live, hot_cols, qb, qi, qf)
        top_s, shard_of, ord_of = _merge_gathered(
            _gather_parts(s_scores), _gather_parts(s_ords), k)
        return jnp.stack(
            [top_s, _pack_ids(shard_of), _pack_ids(ord_of)], axis=1)

    return program(block_docs, block_scores, live, hot_cols, W, Wp, qb, qi, qf, nm)


@partial(jax.jit, static_argnames=("mesh", "k", "tiebreak"))
def _hybrid_program(block_docs, block_scores, live, hot_cols, W, qblocks, qidf,
                    *, mesh, k, tiebreak=True):
    """dense hot-matmul + sparse culled blocks -> exact merged top-k.

    Shapes: block_docs/scores [S,T,128], live [S,D], hot_cols [S,H,D],
    W [Q,H], qblocks/qidf [Q,S,B]. Output packed [Q,3,k] f32 (score, shard,
    ord bitcast) — one transfer per batch. tiebreak=False (pass A / theta)
    skips the doc-id tie-break machinery: a theta lower bound does not care
    which of several tied docs ranks k-th.
    """

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("shard"), P("shard"),
                  P("dp"), P("dp", "shard"), P("dp", "shard")),
        out_specs=P("dp"),
        check_vma=False,
    )
    def program(block_docs, block_scores, live, hot_cols, W, qb, qi):
        def one_part(bd, bs, lv, hc, qb1, qi1):         # qb1 [Qc, B]
            # HIGHEST: the TPU MXU multiplies bf16 by default, which shifts
            # scores ~1% and breaks exact top-k parity; H is tiny so the
            # 6-pass f32 emulation is free
            dense = jax.lax.dot_general(                # [Qc, D]
                W, hc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            docs = jnp.take(bd, qb1, axis=0)            # [Qc, B, 128]
            sc = qi1[:, :, None] * jnp.take(bs, qb1, axis=0)
            Qc = qb1.shape[0]
            d2 = docs.reshape(Qc, -1)
            s2 = sc.reshape(Qc, -1)
            return jax.vmap(
                lambda d, s, dn: _one_query_topk(d, s, dn, lv, k,
                                                 tiebreak=tiebreak))(
                d2, s2, dense)

        s_scores, s_ords = jax.vmap(
            one_part, in_axes=(0, 0, 0, 0, 1, 1))(
            block_docs, block_scores, live, hot_cols, qb, qi)  # [Sl, Qc, k]
        top_s, shard_of, ord_of = _merge_gathered(
            _gather_parts(s_scores), _gather_parts(s_ords), k)
        return jnp.stack(
            [top_s, _pack_ids(shard_of), _pack_ids(ord_of)], axis=1)

    return program(block_docs, block_scores, live, hot_cols, W, qblocks, qidf)


@partial(jax.jit, static_argnames=("mesh", "k"))
def _lane_program(block_docs, block_scores, live, qblocks, qidf, *, mesh, k):
    """Pass-B variant for query groups with NO hot terms: skips the dense
    [Qc, D] matmul and the dense top-k entirely — for Zipf-tail query mixes
    this removes the dominant O(Qc*D) term from most dispatches."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("shard"),
                  P("dp", "shard"), P("dp", "shard")),
        out_specs=P("dp"),
        check_vma=False,
    )
    def program(block_docs, block_scores, live, qb, qi):
        def one_part(bd, bs, lv, qb1, qi1):
            docs = jnp.take(bd, qb1, axis=0)
            sc = qi1[:, :, None] * jnp.take(bs, qb1, axis=0)
            Qc = qb1.shape[0]
            return jax.vmap(
                lambda d, s: _lane_candidates(d, s, None, lv, k, True))(
                docs.reshape(Qc, -1), sc.reshape(Qc, -1))

        s_scores, s_ords = jax.vmap(
            one_part, in_axes=(0, 0, 0, 1, 1))(
            block_docs, block_scores, live, qb, qi)
        top_s, shard_of, ord_of = _merge_gathered(
            _gather_parts(s_scores), _gather_parts(s_ords), k)
        return jnp.stack(
            [top_s, _pack_ids(shard_of), _pack_ids(ord_of)], axis=1)

    return program(block_docs, block_scores, live, qblocks, qidf)
