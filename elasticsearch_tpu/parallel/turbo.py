"""TurboBM25: the flagship TPU serving engine (int8 column cache + Pallas).

The architecture follows the measured realities of the target TPU (see
kernels.py): everything the chip is fast at (big int8 MXU matmuls, tiled
VPU ops) happens on device; everything it is slow at (scatter, sort,
gather) happens either at column-build time via the outer-product trick or
on the host over provably tiny data.

Per query the terms split three ways:

* **colized** (df >= COLD_DF): the term owns a dense int8 impact column in
  the device cache (LRU over HBM budget, built on device by
  kernels.build_columns — no multi-GB host->device transfer). Scoring is
  one exact-integer matmul sweep producing per-superwindow top-NCAND
  candidate ROWS, globally re-ranked on device (_pick_rows) so only
  ~n_rows row ids per query ever cross the host link.
* **cold** (df < COLD_DF): at most a few thousand postings. The host
  computes EXACT totals for every cold-touched doc that a bound does not
  rule out — the other query terms' impacts come from their columns'
  host index (_column_impacts), no search — so any doc with a cold
  contribution is scored exactly with no device help. The order, a
  (partition, query): COLLECT the cold postings raw (the terms' lists
  laid end to end, each posting beside its doc's whole gathered cold
  contribution; nothing made distinct), BOUND them (_cold_survivors),
  then enumerate (np.unique), give impacts to and exact-rescore the
  SURVIVORS only. The bound's threshold is a lower bound on the final
  k-th score from two sides: the k-th exact total of the picked rows'
  docs, which the column terms choose and which is far too low for a
  query whose rare terms carry the idf, and the cold side's own: a
  gathered contribution less its slack is a lower bound on its doc's
  total (a disjunction's other terms add >= 0), a doc occurs at most
  once a cold term, so the (k x cold terms)-th largest raw value is at
  most the k-th best distinct doc's. The rank tolerates duplicates
  because the raw postings are not distinct yet, and making them so is
  the cost the bound is there to avoid. Both ends of the test carry the
  gather's slack and `_f32_err`, what the exact scorer's f32 arithmetic
  can be off by at the query's boosts: with a tight threshold that
  margin decides who is scored, so it grows with the scores.
* the final top-k merges both sides, once a (partition, dispatch chunk)
  (_finish_chunk): the host rescores EVERY doc in the collected rows in
  exact f32 (term-order identical to the reference scorer; a term's
  postings inside a 128-doc row are one span of its list, _rescore_rows)
  and checks a per-query CERTIFICATE that bounds what the
  quantized sweep could have hidden in rows it did NOT collect:

      exact_kth >= max(rowmax_{n_rows+1}, max_sw sw_NCANDth) + e_q

  where e_q is the int8 quantization error bound. Docs with cold lanes
  or collected rows are exact by construction; colized-only docs in
  uncollected rows provably cannot beat the k-th result. If the
  certificate fails (rare), the query falls back to the caller-provided
  exact path.

Ref: this replaces the reference's per-segment BulkScorer loop
(ContextIndexSearcher.java:213-216) and its BlockMaxWAND pruning — the TPU
answer to dynamic pruning is candidate generation at memory bandwidth plus
host verification, not branchy skipping.
"""

from __future__ import annotations

import threading
import time
from collections import Counter
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as _P

from elasticsearch_tpu.common import (
    faults, hbm_ledger, integrity, metrics, tracing,
)
from elasticsearch_tpu.common.errors import DeviceFaultError
from elasticsearch_tpu.common.faults import FaultRecord
from elasticsearch_tpu.common.settings import knob
from elasticsearch_tpu.index.positions import phrase_freqs
from elasticsearch_tpu.index.segment import tf_at
from elasticsearch_tpu.ops import bm25_idf
from elasticsearch_tpu.parallel.blockmax import _host_block_scores
from elasticsearch_tpu.parallel.kernels import (
    BITSET_CLAUSES, BITSET_COLD_ROWS, BITSET_NEGS, COLSCALE, COLSCALE2,
    MAX_GROUP_ROWS, N_CHUNKS, NCAND, ROWS_PER_STEP, SG_FIRST, SG_PICK,
    SG_SCATTER, SPARSE_GRAN, SPARSE_IMP_MAX, SW, SW_WORD_ROWS, TILE,
    bitset_repack, bitset_write_rows, build_columns, intersect_bitset,
    mask_chunk_counts, mask_live_counts, sparse_gather, sparse_pool_update,
    sweep_rowmax, sweep_rowmax_bitset, sweep_rowmax_conj,
)
from elasticsearch_tpu.parallel.spmd import StackedBM25

COLD_DF = 16384        # below this, terms are host-scored
K1_PLUS1 = 2.2         # BM25 idf-free impact upper bound
_K1 = 1.2              # BM25 k1 (must equal serving.K1)
_B = 0.75              # BM25 b  (must equal serving.B)
_GLOBAL_ROWS = 33      # candidate posting rows collected per query
_MAX_REQ = 126         # coverage counts fit int8 with the must_not weight

from functools import partial as _partial  # noqa: E402


# the steps of one engine call (tracing.steps): each takes one histogram
# observation per call, see common/metrics.py
DISPATCH_STEPS = metrics.DISPATCH_TOP_STEPS + (
    "dispatch.slice_build", "dispatch.sparse_gather", "dispatch.rescore",
    "dispatch.rescore_rows", "dispatch.rescore_survivors",
    "dispatch.survivor_bound", "dispatch.merge_cert",
    "dispatch.cert_fallback", "dispatch.bool_resolve",
    "dispatch.phrase_build", "dispatch.bitset_pack")


@_partial(jax.jit, static_argnames=("n_rows",))
def _pick_rows(rm, rr, *, n_rows: int):
    """Device-side global candidate-row pick (was a per-query host loop
    over a ~10MB fetched array — a device->host transfer per batch): from the
    sweep's per-superwindow top-NCAND (rowmax, row) pairs, keep each
    query's global top n_rows rows.

    Returns ONE packed [QC, n_rows + 1] f32 array — row ids as exact
    floats (row < 2^24 always: 24-bit ordinal limit; -1 marks empty
    slots — a bitcast sentinel would be a NaN pattern that transports
    canonicalize) and, in the last column, the max approximate
    score any UNCOLLECTED row could hold: the (n_rows+1)-th global rowmax
    joined with each superwindow's NCAND-th kept rowmax (rows never
    collected in a sw are bounded by it). The host rescores every doc in
    the collected rows EXACTLY, so this bound is all the certificate
    needs."""
    QC = rm.shape[1]
    m = jnp.transpose(rm[:, :, :NCAND], (1, 0, 2)).reshape(QC, -1)
    r = jnp.transpose(rr[:, :, :NCAND], (1, 0, 2)).reshape(QC, -1)
    if m.shape[1] < n_rows + 1:
        pad = n_rows + 1 - m.shape[1]
        m = jnp.pad(m, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        r = jnp.pad(r, ((0, 0), (0, pad)))
    top_m, idx = jax.lax.top_k(m, n_rows + 1)
    valid = top_m[:, :n_rows] > -jnp.inf
    rows = jnp.where(valid,
                     jnp.take_along_axis(r, idx[:, :n_rows], axis=1), -1)
    beyond = top_m[:, n_rows]
    beyond = jnp.where(jnp.isfinite(beyond), beyond, 0.0)
    sw_last = rm[:, :, NCAND - 1]                          # [nsw, QC]
    sw_bound = jnp.max(jnp.where(sw_last > -jnp.inf, sw_last, 0.0), axis=0)
    return jnp.concatenate([
        rows.astype(jnp.float32),
        jnp.maximum(beyond, sw_bound)[:, None],
    ], axis=1)
_LANE128 = np.arange(128, dtype=np.int64)
_LOW_BITS = (np.uint64(1) << np.arange(64, dtype=np.uint64)) - np.uint64(1)


def _flatten_queries(batches: Sequence[List]):
    """Flatten batches of term/(term, boost) query lists into
    (flat [(term, boost)] lists with duplicate terms summed,
    spans [(offset, count)] per batch) — shared by TurboBM25.search_many
    and the fused multi-partition path so both dispatch the exact same
    aggregated weights."""
    flat: List[List[Tuple[str, float]]] = []
    spans = []
    for queries in batches:
        spans.append((len(flat), len(queries)))
        for q in queries:
            agg: Dict[str, float] = {}
            for t in q:
                t, b = (t, 1.0) if isinstance(t, str) else t
                agg[t] = agg.get(t, 0.0) + b
            flat.append(list(agg.items()))
    return flat, spans


def _quantize(ws: Sequence[float]):
    """One query's column weights as the sweep takes them: ([(hi, lo)] int8
    steps per weight, qscale, e_q). w ~ qs * hi + qs / 128 * lo; e_q bounds
    what the quantized sweep can misjudge a doc by (the certificate's
    margin). The ONE place this arithmetic lives: the disjunctive and the
    bool dispatch quantize here, and their finishes read e_q from here."""
    e_q = 1e-7
    if not ws:
        return [], 1.0, e_q
    wmax = max(abs(w) for w in ws)
    qs = max(wmax / 127.0, 1e-9)         # hi step
    qs2 = qs / 128.0                     # lo step
    steps = []
    for w in ws:
        wh = max(-127, min(127, round(w / qs)))
        wl = max(-127, min(127, round((w - qs * wh) / qs2)))
        steps.append((wh, wl))
        w_approx = qs * wh + qs2 * wl
        # a full lo step (not half): the build kernel forces lo >= 1 on
        # presence-only cells so the conjunctive sweep's presence mask
        # stays exact (kernels._build_kernel)
        e_q += (abs(w - w_approx) * K1_PLUS1
                + abs(w_approx) * COLSCALE2)
    # f32 rounding of the in-kernel integer combine
    e_q += 3e-7 * sum(abs(w) for w in ws) * K1_PLUS1
    return steps, qs2 * COLSCALE2, float(e_q)


def _f32_err(n_terms: int, most: float) -> float:
    """How far `_exact_scores`' f32 total of a doc can lie from the real
    sum of its terms: `n_terms` weights rounded to f32, as many products
    and one add fewer, each within 2**-24 of a value no larger than
    `most` = the sum of |idf x boost| x the term's largest impact, so
    (n_terms + 1) x 2**-24 x `most`, taken twice over, above the 1e-5 the
    survivor bound has always carried. A margin that did not grow with
    the boosts would let a boosted query's bound drop a doc whose f32
    total ties the k-th."""
    return 1e-5 + (n_terms + 1) * 2.0 ** -23 * most


_BUILD_BUCKETS = (256, 1024, 4096, 16384, 32768)   # last one bounded by
#   SMEM: 4 prefetch arrays x bucket x 4B must stay well under the 1MB SMEM


def _bucket(n: int) -> int:
    for b in _BUILD_BUCKETS:
        if n <= b:
            return b
    return _BUILD_BUCKETS[-1]


_ROW_BUCKETS = (256, 2048, 16384)   # synthetic phrase-lane row counts are
#   bucketed so build_columns sees a bounded set of lane shapes (each new
#   shape is a fresh jit trace)


def _row_bucket(n: int) -> int:
    for b in _ROW_BUCKETS:
        if n <= b:
            return b
    return -(-n // _ROW_BUCKETS[-1]) * _ROW_BUCKETS[-1]


@dataclass
class _TermInfo:
    ord: int
    df: int
    idf: float
    row_start: int          # first block row
    n_rows: int             # block rows
    smax: float             # max idf-free lane score


@dataclass
class _PhraseInfo:
    """Metadata for a slop-0 phrase treated as a synthetic term: its
    matching docs and per-doc phrase freqs (computed once at column-build
    time by a positions-delta check, index/positions.phrase_freqs) back
    both the int8 adjacency column build and the exact host rescore."""
    key: str                # column-cache key ("\x00p:" + joined terms)
    terms: Tuple[str, ...]
    docs: np.ndarray        # i32 ascending, live-unfiltered
    pf: np.ndarray          # f32 phrase freqs aligned with docs
    idf_sum: float          # sum of member-term idfs, in term order
    smax: float             # max idf-free phrase lane score


def _pkey(terms: Sequence[str]) -> str:
    return "\x00p:" + "\x00".join(terms)


def _once(fn: Callable):
    """`fn`'s result, computed at the first call and kept."""
    got: list = []

    def get():
        if not got:
            got.append(fn())
        return got[0]
    return get


def _intersect_sorted(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Sorted-unique intersection with a galloping gear: when one side is
    tiny relative to the other (the ultra-selective-lead regime that
    ES_TPU_BITSET_HOST_DF routes to host), binary-searching the small
    side's members in the large one (s * log2(b) work) beats np.isin's
    linear merge over both."""
    if len(a) > len(b):
        a, b = b, a
    if not len(a):
        return a
    if len(a) * max(np.log2(len(b)), 1.0) < len(b):
        j = np.searchsorted(b, a)
        jc = np.minimum(j, len(b) - 1)
        return a[(j < len(b)) & (b[jc] == a)]
    return a[np.isin(a, b, assume_unique=True)]


@dataclass
class _BoolQuery:
    """One resolved bool query (TurboBM25.search_bool). Clause lists keep
    the ORIGINAL spec order — the exact rescore iterates them verbatim so
    its f64 accumulation is bit-identical to the serving reference
    (search/serving._conjunctive_partition)."""
    conj: list        # [(term, boost, _TermInfo)] — required, scoring
    should: list      # [(term, boost, _TermInfo)] — optional, scoring
    filters: list     # [(term, _TermInfo)] — required, non-scoring
    must_not: list    # [(term, _TermInfo)] — prohibited
    phrases: list     # [(terms, slop, boost, _PhraseInfo | None, idf_sum)]
    dev_candidate: bool
    # set by _bool_routes for a device-routed query: the rarest required
    # clause's df, whether that lead is a cold TERM (no column: its match
    # set is a cold row of the bitsets), and the route its finish takes.
    # Under cold_df every match fits the exact rescore, so the query is
    # answered from the conjunction mask itself (`by_mask`: no sweep
    # weights, no row pick, no certificate); above it from the sweep's
    # picked rows
    lead_df: int = 0
    cold_lead: bool = False
    by_mask: bool = False

    def required(self) -> list:
        """[(term, _TermInfo)] of the required TERM clauses."""
        return [(t, i) for t, _, i in self.conj] + self.filters


# what a partition adds to a query's `totals` entry when it cannot count
# its matches on the device (a host-routed query, a mask that is a
# superset, a fault): the sum over partitions then stays negative
_NO_TOTAL = -(1 << 40)
_PHRASE_HOST_BYTES = 64 << 20    # scanned phrases kept on the host, a
#   partition: the commonest pair of a 368,409-document segment is 0.8 MB
_ROW_UP_BUCKETS = (8, BITSET_COLD_ROWS)   # cold rows written at once: two
#   shapes of `kernels.bitset_write_rows`, both instantiated when the
#   bitsets are allocated, so no dispatch builds one (a row is 46 KB at
#   368,409 docs: padding a write to 8 rows costs a third of a megabyte)


# node-wide bool-route counters mirrored from every engine's per-instance
# stats so GET /_nodes/stats tpu_turbo surfaces them next to the merge
# counters (serving.turbo_node_stats folds these in); bitset_bytes is a
# gauge-like running total of currently packed bytes, the rest are
# cumulative. The route counters count (partition, request) PAIRS, all
# four of them, so their shares are consistent: bool_device = pairs the
# device route answered, of which bool_cold_lead had a cold term as their
# rarest required clause; bool_host = pairs `_bool_host_exact` answered
# (the router's host route, a faulted partition or chunk); bitset_gallop =
# pairs ES_TPU_BITSET_HOST_DF moved to the host. phrase_builds = adjacency
# columns built
_NODE_BITSET_STATS = {"bitset_packs": 0, "bitset_bytes": 0,
                      "bitset_blocks_skipped": 0,
                      "bitset_gallop": 0, "bool_device": 0, "bool_host": 0,
                      "bool_cold_lead": 0, "phrase_builds": 0
                      }  # guarded by: _NODE_BITSET_LOCK
_NODE_BITSET_LOCK = threading.Lock()


def _node_bitset_add(key: str, n: int) -> None:
    if n == 0:
        return
    with _NODE_BITSET_LOCK:
        _NODE_BITSET_STATS[key] += n


def node_bitset_stats() -> dict:
    with _NODE_BITSET_LOCK:
        return dict(_NODE_BITSET_STATS)


# ---- eager sparse impact tier (ES_TPU_SPARSE) ----
#
# Cold terms (df < COLD_DF) keep their postings as packed
# ``doc << 8 | impact`` int32 lanes in a per-partition granule pool
# (pre-multiplied idf-free BM25 impacts, uint8-quantized with a tracked
# error bound — the BM25S eager-scoring representation). The pool is a
# host-backed HBM region (scrubbed + repairable like the lane arrays),
# and kernels.sparse_gather serves the cold side of every query from it,
# retiring the _cold_contrib host fork from the serving path.
#
# A gather serves a GROUP of queries of one (partition, dispatch chunk):
# a run of consecutive queries whose cold slices are all resident at once
# and whose steps fit one program (_GatherGroup; _start_gathers chooses
# the groups from what it can see: the pool's residency and the ladder
# below, no knob). It has three parts. PLAN (host only): slice residency,
# then the program's flat step list, concatenated from what each slice
# worked out when it was built (its step pairs: the (chunk, tile) that meet)
# with the query's weight and its place in the result broadcast over them;
# per query the posting spans and the bound's slack (_ColdGather). LAUNCH:
# one i32 [4, steps] upload + one program, async, with the result's copy
# to the host started at once. COLLECT: one fetch a group, and each
# query's totals read out of it in posting order, raw (the match finish
# bounds them before it makes anything distinct: _cold_survivors). Nothing the
# gather reads depends on the sweep's output, so search_many plans and
# launches every group of a dispatch chunk right after the chunk's sweep
# and BEFORE it waits for the sweep: the gathers run on the device behind
# the sweep while the host waits and rescores, and finish only collects.
# There is no blocking device round trip inside _finish_chunk. A group of
# one query (the bool route's SHOULD side, a query finish finds without a
# gather) is the same program at its smallest rung.

_SPARSE_DOC_LIMIT = 1 << 23          # packed doc-id headroom in an int32
_SPARSE_RUNGS = ((64, 8), (512, 32), (4096, 256), (16384, 1024))
#   (steps, result chunks) of a gather program: a group takes the first
#   rung that holds both, so kernels.sparse_gather sees a bounded shape
#   set, every member instantiated when the pool reaches its cap
#   (_sp_grow). 4 x 16384 step words are a quarter of the chip's 1 MB of
#   scalar memory; a padding step costs the chip a third of a
#   microsecond and the interpreter of the CPU tests 17, so the first
#   rung is a query's own size, not a chunk's
_SPARSE_QUERY_CHUNKS = 256           # one query's own chunks: above, or
#   with more steps than the last rung, its cold side is host-scored
_SPARSE_UP_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)     # granule-upload
#   batch sizes (sparse_pool_update), padded toward the zero granule


def _sparse_widths() -> Tuple[int, ...]:
    """Slice-width ladder (ES_TPU_SPARSE_WIDTHS), each rung rounded up to
    a granule multiple, ascending. A cold term's slice is padded to the
    first rung >= its df so pool runs recycle at ladder widths only."""
    raw = knob("ES_TPU_SPARSE_WIDTHS") or ""
    ws = set()
    for tok in str(raw).split(","):
        tok = tok.strip()
        if tok:
            ws.add(max(SPARSE_GRAN,
                       -(-int(tok) // SPARSE_GRAN) * SPARSE_GRAN))
    return tuple(sorted(ws)) or (1024, 4096, 16384)


# node-wide sparse-tier and finish counters, folded into GET /_nodes/stats
# tpu_turbo by serving.turbo_node_stats next to the bitset block;
# sparse_bytes is a gauge-like running total of currently resident padded
# slice bytes (evictions subtract), the rest are cumulative.
# sparse_slice_passes = _build_slices calls that placed a slice, so
# sparse_slices over it = slices built a pass (hundreds a closed call's
# chunk, 1-3 a single search; it falls when pool pressure splits a chunk's
# build into per-query retries under launch).
# finish_bulk_pairs = (partition, query) pairs the chunk-wide finish
# answered; finish_pair_fallbacks = pairs that left it (a host-only
# partition, a faulted sweep, a gather not launched or lost, a failed
# certificate): the two sum to partitions x queries.
# cold_enum_docs = RAW cold postings the match finish's pairs laid out
# (`_collect_gather`: a doc in several of a query's cold lists counts once
# a list; nothing is made distinct before the bound);
# cold_survivor_docs = distinct docs of them the survivor bound kept
# (`_cold_survivors`), each exact-rescored; both counted once a pair, so
# survivors over enumerated says how often the bound engages
_NODE_SPARSE_STATS = {"sparse_slices": 0, "sparse_slice_passes": 0,
                      "sparse_bytes": 0, "sparse_queries": 0,
                      "sparse_gather_launches": 0,
                      "sparse_gather_overlapped": 0,
                      "sparse_fallbacks": 0,
                      "finish_bulk_pairs": 0,
                      "finish_pair_fallbacks": 0,
                      "cold_enum_docs": 0, "cold_survivor_docs": 0
                      }  # guarded by: _NODE_SPARSE_LOCK
_NODE_SPARSE_LOCK = threading.Lock()


def _node_sparse_add(key: str, n: int) -> None:
    if n == 0:
        return
    with _NODE_SPARSE_LOCK:
        _NODE_SPARSE_STATS[key] += n


def node_sparse_stats() -> dict:
    with _NODE_SPARSE_LOCK:
        return dict(_NODE_SPARSE_STATS)


def _gather_desc(n_steps: int) -> np.ndarray:
    """An all-padding i32 [4, n_steps] step list for kernels.sparse_gather
    — rows coff, cw (f32 bits), meta, oidx: every step is of no kind,
    reads the zero granule and stays on result chunk 0."""
    return np.zeros((4, n_steps), np.int32)


class _GatherGroup:
    """The queries one gather program serves, between plan and collect.
    `members` in the order their chunks lie in the result and `terms` =
    the slices that must stay resident, both until the launch. `out` is the
    launched program's result with its copy to the host under way (None
    before the launch, and again once fetched or dropped); `flat` the
    fetched block. A group that was not launched (a fault), or whose
    fetch faults, leaves `flat` None and collect host-scores its queries
    a pair at a time. `early` = launched before the dispatch waited for
    its sweep."""

    __slots__ = ("early", "members", "terms", "n_steps", "n_chunks",
                 "launched", "out", "flat")

    def __init__(self, early: bool):
        self.early = early
        self.members: List["_ColdGather"] = []
        self.terms: set = set()
        self.n_steps = self.n_chunks = 0
        self.launched = False
        self.out = self.flat = None


class _ColdGather:
    """One (partition, query)'s cold side: its `group` (None = unsliceable,
    host-scored), where its terms' chunks lie in the group's result
    (`spans`: [(first chunk, df, first posting)]), the bound's `slack`,
    and `slices` [(first granule, dequant weight, step pairs)] for
    the group's step list."""

    __slots__ = ("cold_terms", "group", "slices", "spans", "slack", "host")

    def __init__(self, cold_terms):
        self.cold_terms = cold_terms
        self.group: Optional[_GatherGroup] = None
        self.slices: List[Tuple[int, float, np.ndarray]] = []
        self.spans: List[Tuple[int, int, int]] = []
        self.slack = 0.0
        self.host = False      # collect host-scored this pair


class _ChunkPlan:
    """One dispatch chunk resolved against one partition, ONCE: what the
    sweep's weights, the chunk's gathers and its finish all read. Per
    query: `qterms` (the terms this partition holds, in query order, as
    (term, boost, info, column slot or -1)), `cold` (those without a
    column NOW, as (term, boost, info): `_slot_of` does not move between
    a dispatch's prep and its finish), `e_q` of the colized ones and the
    most and the least they can add to a doc (`col_const`, `col_floor`:
    0 unless a boost is negative), and `f32_err`, how far the f32 exact
    total of ANY doc can lie from the real sum of its terms (it grows with
    the boosts: `_f32_err`).
    `wq` / `qscale` are the sweep's inputs. The p* arrays hold every
    (query, term) pair of the chunk, term POSITION major (`pos_end[j]` =
    pairs at positions <= j), for the row-span rescore: query, column
    slot (-1 = none), first posting, posting -> `_host_scores` offset,
    f32 weight; `cold_pairs` = [(pair, query, first posting, one past
    the last)] of the pairs without a column. `sparse` = the sparse tier
    serves this chunk's cold sides (read once)."""

    __slots__ = ("chunk", "qterms", "cold", "e_q", "col_const",
                 "col_floor", "f32_err", "wq", "qscale", "sparse", "pq",
                 "pslot", "plo", "phb", "pw", "pos_end", "cold_pairs")


class TurboBM25:
    """Single-partition serving engine over a StackedBM25 (S == 1).

    qc_sizes: compiled dispatch widths (queries per kernel launch).
    hbm_budget_bytes: HBM reserved for the int8 column cache.
    fallback: callable(terms: [(term, boost)], k) -> (scores, ords) exact
        results, used when a certificate fails.
    """

    def __init__(self, stacked: StackedBM25, *,
                 hbm_budget_bytes: int = 10 << 30,
                 qc_sizes: Tuple[int, ...] = (8, 256),
                 cold_df: int = COLD_DF,
                 fallback: Optional[Callable] = None,
                 total_docs: Optional[int] = None,
                 avgdl: Optional[float] = None,
                 df_of: Optional[Callable[[str], int]] = None):
        """total_docs / avgdl / df_of override the single-partition stacked
        stats with INDEX-GLOBAL values when this engine serves one partition
        of a multi-segment index (serving.TurboEngine) — scoring must use
        the same global idf/avgdl on every partition (the reference's
        dfs_query_then_fetch semantics, serving.py module docstring)."""
        assert stacked.n_shards == 1, "TurboBM25 v1 serves one partition"
        self.stacked = stacked
        self.fp = stacked.postings[0]
        self.fallback = fallback
        self.cold_df = int(cold_df)
        self._total_docs = int(total_docs) if total_docs else stacked.total_docs
        self._avgdl = float(avgdl) if avgdl else stacked.avgdl
        self._df_of = df_of
        self.D = stacked.doc_counts[0]
        self.Dp = -(-self.D // SW) * SW
        self.nsw = self.Dp // SW
        self.dp_rows = self.Dp // 128
        # dispatch widths: rounded up to ROWS_PER_STEP multiples so the
        # sweep kernel block shapes stay sublane-aligned
        # (ADVICE r4), deduped, ascending
        self.qc_sizes = tuple(sorted(
            {max(ROWS_PER_STEP,
                 -(-int(s) // ROWS_PER_STEP) * ROWS_PER_STEP)
             for s in qc_sizes}))

        fp = self.fp
        # lane arrays with trailing DMA padding rows; the padded host
        # copies stay retained as the scrubber's authoritative fingerprint
        # (and repair) source for these device regions
        pad = np.zeros((MAX_GROUP_ROWS, 128), np.int32)
        self._lane_docs_host = np.concatenate([fp.block_docs, pad], axis=0)
        self.lane_docs = jnp.asarray(self._lane_docs_host)
        bs = _host_block_scores(fp, self._avgdl)
        self._lane_scores_host = np.concatenate(
            [bs, pad.astype(np.float32)], axis=0)
        self.lane_scores = jnp.asarray(self._lane_scores_host)
        self._host_scores = bs       # [T, 128] idf-free lane scores
        # per-block doc ranges for group building (pad lanes are 0 so the
        # row max is the true last doc; row 0 is the reserved zero block)
        self._blo = fp.block_docs[:, 0].astype(np.int64)
        self._bhi = fp.block_docs.max(axis=1).astype(np.int64)

        # live mask as f32 rows
        lh = stacked.live_host[0] if stacked.live_host is not None else None
        lv = np.zeros(self.Dp, np.float32)
        if lh is None:
            lv[: self.D] = 1.0
        else:
            lv[: self.D] = lh[: self.D].astype(np.float32)
        self.live = jnp.asarray(lv.reshape(self.dp_rows, 128))
        self._live_host = lv

        # column cache sizing: slots + 1 scratch slot for padding groups
        # (2 bytes per doc per slot: hi + lo residual layers)
        slots = max(int(hbm_budget_bytes // (2 * self.Dp)), 32)
        n_colizable = int((fp.doc_freq >= self.cold_df).sum())
        slots = min(slots, max(n_colizable, 1) + 8)
        self.Hp = ((slots + 31) // 32) * 32
        dp_chunks = self.dp_rows // 16
        self.cols_hi = jnp.zeros((dp_chunks, self.Hp + 1, 16, 128), jnp.int8)
        self.cols_lo = jnp.zeros((dp_chunks, self.Hp + 1, 16, 128), jnp.int8)
        self._slot_of: Dict[str, int] = {}
        self._lru: Dict[str, int] = {}
        self._free = list(range(self.Hp))
        self._pending_zero: List[tuple] = []
        self._tick = 0
        self._terms: Dict[str, Optional[_TermInfo]] = {}
        self._phrases: Dict[str, Optional[_PhraseInfo]] = {}
        self._phrase_bytes = 0     # of the (docs, pf) arrays in _phrases
        # per-cache-key tile bases touched by the key's build groups, kept
        # so eviction can zero exactly those tiles even for keys (phrases)
        # whose lane arrays are long gone
        self._tile_bases: Dict[str, np.ndarray] = {}
        # the host's index of the terms that own a column, by slot, in
        # 64-doc words: _col_bits[slot, w] bit b = doc 64 * w + b has the
        # term, _col_cnt[slot, w] = the term's postings before doc 64 * w.
        # A picked row r's postings are the span _col_cnt[2r] ..
        # _col_cnt[2r + 2] of the term's list (_rescore_rows), and any
        # doc's posting is found with a popcount, no search
        # (_column_impacts). Written with the column (_index_column); a
        # slot nobody owns is never read, so eviction leaves it. 3/32 of
        # the column cache's bytes, on the host, touched pages only
        self._col_bits = np.zeros((self.Hp, 2 * self.dp_rows), np.uint64)
        self._col_cnt = np.zeros((self.Hp, 2 * self.dp_rows + 1), np.int32)
        self.force_cert_fail = False   # test hook: exercise the fallback
        # partition id for fault-site attribution (set by TurboEngine /
        # ShardedTurbo when this engine serves one partition of many)
        self.part_id = 0
        # bumped whenever cols_hi/cols_lo are rebuilt, so the fused
        # multi-partition cache (ShardedTurbo._refresh) re-syncs only the
        # partitions whose columns actually changed
        self.cols_epoch = 0
        # packed-uint32 per-slot match-set bitsets (ES_TPU_BITSET): built
        # lazily from the column cache on the first bool dispatch and
        # re-packed whenever cols_epoch moves
        self.bits = None
        self._bits_epoch = -1
        self.bits_version = 0     # moves with every write to `bits`
        self.live_bits = None     # the live mask packed as a bitset row
        self._wgr = self.dp_rows // 32
        # cold rows of `bits` (behind the Hp + 2 column rows): the match
        # sets of COLD required / prohibited clauses, packed on the host
        # from the postings when a bool request first names the term
        # (`_ensure_cold_rows`), LRU over BITSET_COLD_ROWS rows. The host
        # copy is what the device rows are written from: `_crow_dirty` =
        # the rows whose host copy the device has not got yet
        self._crow_of: Dict[str, int] = {}
        self._crow_lru: Dict[str, int] = {}
        self._crow_free = list(range(BITSET_COLD_ROWS))
        self._crow_host: Optional[np.ndarray] = None
        self._crow_dirty: set = set()
        # eager sparse impact slices (ES_TPU_SPARSE): cold terms keep
        # packed (doc << 8 | impact) granules in a lazily grown device
        # pool, built in the same ensure_columns pass as the columns, so
        # the serving path never forks to the _cold_contrib host walk
        self._sp_pool = None                  # [G, 8, 128] i32 device pool
        self._sp_host: Optional[np.ndarray] = None   # authoritative mirror
        self._sp_of: Dict[str, tuple] = {}
        #   term -> (granule start, n granules, padded width, quant scale,
        #   step pairs: the (chunk, tile) that MEET, one a gather step, as
        #   chunk << 16 | tile in posting order, i64; worked out when the
        #   slice is built, _pack_slices, because they depend on the slice
        #   alone): a plain tuple of numbers and an array, which
        #   the collector stops tracking (a pool holds thousands, and a
        #   closed loop builds a thousand a call)
        self._sp_lru: Dict[str, int] = {}
        self._sp_free: Dict[int, List[int]] = {}     # run length -> starts
        self._sp_next = 1                     # granule 0 reserved all-zero
        self._sp_cap = max(2, min(int(hbm_budget_bytes) // 4, 64 << 20)
                           // (SPARSE_GRAN * 4))
        self._sp_ok = self.Dp <= _SPARSE_DOC_LIMIT
        self._sp_inflight = 0   # gather programs launched, not yet fetched
        self.stats = {"builds": 0, "build_s": 0.0, "fallbacks": 0,
                      "cold_queries": 0, "dispatches": 0, "degraded": 0,
                      "phrase_builds": 0, "bool_host": 0, "bool_device": 0,
                      "bool_cold_lead": 0,
                      "bitset_packs": 0, "bitset_gallop": 0,
                      "bitset_blocks_skipped": 0, "bitset_bytes": 0,
                      "sparse_queries": 0, "sparse_slices": 0,
                      "sparse_bytes": 0, "sparse_fallbacks": 0,
                      "sparse_gather_launches": 0,
                      "sparse_gather_overlapped": 0,
                      "finish_bulk_pairs": 0, "finish_pair_fallbacks": 0,
                      "cold_enum_docs": 0, "cold_survivor_docs": 0}
        # HBM residency ledger: regions mirror hbm_bytes() exactly so the
        # telemetry cross-check can hold ledger == engine to the byte
        self._hbm = hbm_ledger.register_engine(self, "turbo")
        self._register_hbm_regions()
        self._register_scrub_regions()

    def _register_hbm_regions(self) -> None:
        self._hbm.set_region("cols_hi", self.cols_hi.nbytes)
        self._hbm.set_region("cols_lo", self.cols_lo.nbytes)
        self._hbm.set_region("cols_bits", self._bits_nbytes())
        self._hbm.set_region(
            "sparse_pool",
            0 if self._sp_pool is None else self._sp_pool.nbytes)
        self._hbm.set_region("lane_docs", self.lane_docs.nbytes)
        self._hbm.set_region("lane_scores", self.lane_scores.nbytes)
        self._hbm.set_region("live", self.live.nbytes)

    def _register_scrub_regions(self) -> None:
        """Integrity-plane fingerprints next to the ledger registrations:
        host-sourced regions are host-backed (repair = re-upload the
        retained copy); the column cache is device-built, so it scrubs
        against a per-epoch baseline — jax arrays rebind on every
        legitimate functional update, making array identity the epoch —
        and repairs by dropping the cache (rebuilds lazily, certified)."""
        integrity.register_scrub_region(
            self, "live", lambda o: o.live,
            expected=lambda o: o._live_host,
            repair=lambda o: setattr(o, "live", jnp.asarray(
                o._live_host.reshape(o.dp_rows, 128))))
        integrity.register_scrub_region(
            self, "lane_docs", lambda o: o.lane_docs,
            expected=lambda o: o._lane_docs_host,
            repair=lambda o: setattr(
                o, "lane_docs", jnp.asarray(o._lane_docs_host)))
        integrity.register_scrub_region(
            self, "lane_scores", lambda o: o.lane_scores,
            expected=lambda o: o._lane_scores_host,
            repair=lambda o: setattr(
                o, "lane_scores", jnp.asarray(o._lane_scores_host)))
        for name in ("cols_hi", "cols_lo"):
            integrity.register_scrub_region(
                self, name, lambda o, n=name: getattr(o, n),
                epoch=lambda o, n=name: id(getattr(o, n)),
                repair=lambda o: o._reset_columns())

    def _bits_nbytes(self) -> int:
        return (0 if self.bits is None
                else self.bits.nbytes + self.live_bits.nbytes)

    def _count(self, key: str, n: int = 1) -> None:
        """A bool-route counter: this engine's `stats` and the node's."""
        self.stats[key] += n
        _node_bitset_add(key, n)

    def hbm_bytes(self) -> int:
        return (self.cols_hi.nbytes + self.cols_lo.nbytes
                + self._bits_nbytes()
                + (0 if self._sp_pool is None else self._sp_pool.nbytes)
                + self.lane_docs.nbytes + self.lane_scores.nbytes
                + self.live.nbytes)

    # ---------------- term metadata ----------------

    def _term(self, term: str) -> Optional[_TermInfo]:
        if term in self._terms:
            return self._terms[term]
        fp = self.fp
        o = fp.ord(term)
        if o < 0:
            self._terms[term] = None
            return None
        df = int(fp.doc_freq[o])
        start, cnt = int(fp.block_start[o]), int(fp.block_count[o])
        smax = float(self._host_scores[start: start + cnt].max()) if cnt else 0.0
        # df for cache/cold decisions is partition-LOCAL (it sizes local
        # work); idf uses the global df when an override is installed
        df_g = self._df_of(term) if self._df_of is not None else df
        info = _TermInfo(ord=o, df=df,
                         idf=bm25_idf(self._total_docs, df_g),
                         row_start=start, n_rows=cnt, smax=smax)
        self._terms[term] = info
        return info

    def extend_qc_sizes(self, sizes) -> None:
        """Widen the compiled dispatch-width ladder (the adaptive
        scheduler's bucket hook): each new width is one more cached
        kernel shape, with the same ROWS_PER_STEP rounding as the
        constructor. Existing widths keep their jit cache entries —
        extending is monotonic and idempotent."""
        merged = set(self.qc_sizes)
        merged.update(
            max(ROWS_PER_STEP, -(-int(s) // ROWS_PER_STEP) * ROWS_PER_STEP)
            for s in sizes)
        self.qc_sizes = tuple(sorted(merged))
        hbm_ledger.note_primed("turbo", self.qc_sizes)
        hbm_ledger.note_primed("turbo_bitset", self.qc_sizes)
        # the sparse gather's shape axis is its step-count rung, whose
        # ladder is static — priming it here keeps a cold start retrace-free
        hbm_ledger.note_primed("turbo_sparse",
                               [ns for ns, _ in _SPARSE_RUNGS])

    # ---------------- column cache ----------------

    def _term_groups(self, info: _TermInfo, slot: int):
        """(rows, nrows, bases, slots) arrays for one term's build groups —
        one group per touched 16384-doc tile."""
        lo = self._blo[info.row_start: info.row_start + info.n_rows]
        hi = self._bhi[info.row_start: info.row_start + info.n_rows]
        t0, t1 = int(lo[0]) // TILE, int(hi[-1]) // TILE
        tiles = np.arange(t0, t1 + 1, dtype=np.int64)
        starts = np.searchsorted(hi, tiles * TILE, side="left")
        ends = np.searchsorted(lo, (tiles + 1) * TILE, side="left")
        n = (ends - starts).astype(np.int32)
        keep = n > 0
        return (info.row_start + starts[keep].astype(np.int32),
                n[keep],
                (tiles[keep] * TILE).astype(np.int32),
                np.full(int(keep.sum()), slot, np.int32))

    def _evict(self, key: str) -> None:
        slot = self._slot_of.pop(key)
        del self._lru[key]
        self._free.append(slot)
        # zero the evicted key's touched tiles so the reused slot carries
        # no phantom scores. Rows are pinned to 0 (n = 0 groups DMA rows
        # [0, MAX_GROUP_ROWS) and write nothing) so these groups can ride
        # along ANY later build dispatch regardless of its lane arrays —
        # phrase builds use synthetic lane arrays where a term's row ids
        # would be out of bounds.
        bases = self._tile_bases.pop(key, None)
        if bases is not None and len(bases):
            z = np.zeros(len(bases), np.int32)
            self._pending_zero.append(
                (z, z, bases, np.full(len(bases), slot, np.int32)))
        # churn accounting: a slot is 2 bytes/padded-doc (hi + lo layers)
        self._hbm.note_eviction(freed_bytes=2 * self.Dp)
        self._hbm.note_zeroed_tiles(0 if bases is None else len(bases))
        # (a phrase's metadata, its (docs, pf) arrays, outlives its column:
        # the positions scan that made them is what a phrase costs, and
        # the phrases users repeat are the frequent, expensive ones;
        # `_phrase` bounds what is kept)

    def _reset_columns(self) -> None:
        """Drop the whole column cache. After a failed build dispatch the
        device-side slot contents are unknown — a partially-built column
        would score wrong silently — so the cache restarts empty and
        rebuilds lazily on the next query."""
        dp_chunks = self.dp_rows // 16
        self.cols_hi = jnp.zeros((dp_chunks, self.Hp + 1, 16, 128),
                                 jnp.int8)
        self.cols_lo = jnp.zeros((dp_chunks, self.Hp + 1, 16, 128),
                                 jnp.int8)
        self._hbm.note_eviction(count=len(self._slot_of),
                                freed_bytes=2 * self.Dp * len(self._slot_of))
        self._slot_of.clear()
        self._lru.clear()
        self._free = list(range(self.Hp))
        self._pending_zero = []
        self._tile_bases.clear()
        self.cols_epoch += 1
        self._register_hbm_regions()

    def ensure_columns(self, terms: Sequence[str],
                       protect_extra: Sequence[str] = ()) -> None:
        # injected faults fire BEFORE any slot-pool mutation so containment
        # never observes a half-mutated cache
        faults.fault_point("column_upload", self.part_id)
        self._tick += 1
        need: List[_TermInfo] = []
        sparse_need: List[Tuple[str, _TermInfo]] = []
        for t in dict.fromkeys(terms):
            info = self._term(t)
            if info is None or info.df < self.cold_df:
                if info is not None and info.df:
                    sparse_need.append((t, info))
                continue
            if t in self._slot_of:
                self._lru[t] = self._tick
                continue
            need.append((t, info))
        # eager sparse slices ride the same upload pass as the columns: a
        # cold start builds the cold tier's device representation here, so
        # serving never primes it with host-path queries (ROADMAP item 2)
        if sparse_need and self._sp_ok and bool(knob("ES_TPU_SPARSE")):
            try:
                self._ensure_sparse(sparse_need)
            except DeviceFaultError:
                pass   # query-time gather retries, then host-falls-back
        if not need:
            return
        protect = set(t for t, _ in need) | set(terms) | set(protect_extra)
        # slots the eviction pass may NOT reclaim this batch (cached keys
        # pinned by protect, plus the incoming builds) vs total capacity
        self._hbm.note_protect_pressure(
            sum(1 for t in self._slot_of if t in protect) + len(need),
            self.Hp)
        deficit = len(need) - len(self._free)
        if deficit > 0:
            victims = [t for t in sorted(self._lru, key=self._lru.get)
                       if t not in protect][:deficit]
            if len(victims) < deficit:
                # capacity overflow: colize the highest-df terms (where a
                # missing column hurts most) and leave the rest cold for
                # this batch — the host scores them exactly (ADVICE r4:
                # this used to raise ValueError on the serving path)
                capacity = len(self._free) + len(victims)
                need.sort(key=lambda ti: -ti[1].df)
                self.stats["degraded"] += len(need) - capacity
                need = need[:capacity]
            for v in victims:
                self._evict(v)
        rows_l, n_l, base_l, slot_l = [], [], [], []
        for r, n, b, s in self._pending_zero:
            rows_l.append(r); n_l.append(n); base_l.append(b); slot_l.append(s)
        self._pending_zero = []
        if not need and not rows_l:
            # full degradation (every slot protected, nothing evictable,
            # no zeroing pending): nothing to dispatch
            return
        for t, info in need:
            slot = self._free.pop()
            self._slot_of[t] = slot
            self._lru[t] = self._tick
            r, n, b, s = self._term_groups(info, slot)
            self._tile_bases[t] = b
            self._index_column(slot, info)
            rows_l.append(r); n_l.append(n); base_l.append(b); slot_l.append(s)
        self._build_columns(rows_l, n_l, base_l, slot_l,
                            self.lane_docs, self.lane_scores, len(need))
        self._register_hbm_regions()

    def _index_column(self, slot: int, info: _TermInfo) -> None:
        """`_col_bits` / `_col_cnt` of the term taking `slot`."""
        lo = int(self.fp.post_start[info.ord])
        has = np.zeros(self.dp_rows * 128, np.uint8)
        has[self.fp.post_doc[lo: lo + info.df]] = 1
        bits = np.packbits(has, bitorder="little").view("<u8")
        self._col_bits[slot] = bits
        np.cumsum(np.bitwise_count(bits), out=self._col_cnt[slot, 1:])

    def _build_columns(self, rows_l, n_l, base_l, slot_l, lane_docs,
                       lane_scores, n_built: int) -> None:
        """The device side of `ensure_columns` / `ensure_phrases`: the
        int8 column build of the given row groups over the given lane
        arrays, giant (cold-start) builds split into bounded dispatches."""
        rows = np.concatenate(rows_l)
        nrows = np.concatenate(n_l)
        bases = np.concatenate(base_l)
        slots = np.concatenate(slot_l)
        t0 = time.monotonic()
        try:
            with tracing.phase("engine_build.columns", built=n_built), \
                    faults.device_errors("column_upload", self.part_id):
                for off in range(0, len(rows), _BUILD_BUCKETS[-1]):
                    part = slice(off, off + _BUILD_BUCKETS[-1])
                    r_p, n_p, b_p, s_p = (rows[part], nrows[part],
                                          bases[part], slots[part])
                    ng = _bucket(len(r_p))
                    pad = ng - len(r_p)
                    self.cols_hi, self.cols_lo = build_columns(
                        jnp.asarray(np.concatenate(
                            [r_p, np.zeros(pad, np.int32)])),
                        jnp.asarray(np.concatenate(
                            [n_p, np.zeros(pad, np.int32)])),
                        jnp.asarray(np.concatenate(
                            [b_p, np.zeros(pad, np.int32)])),
                        jnp.asarray(np.concatenate(
                            [s_p, np.full(pad, self.Hp, np.int32)])),
                        lane_docs, lane_scores,
                        self.cols_hi, self.cols_lo, n_groups=ng)
        except DeviceFaultError:
            self._reset_columns()
            raise
        self.cols_epoch += 1
        self.stats["builds"] += n_built
        self.stats["build_s"] += time.monotonic() - t0

    # ---------------- phrase columns ----------------

    def _phrase(self, terms: Sequence[str]) -> Optional[_PhraseInfo]:
        """Metadata for a slop-0 phrase (cached; None if a member term is
        missing from this partition). The full-corpus positions-delta scan
        runs once per phrase; its (docs, pf) arrays then back both the
        adjacency-column build and the exact host rescore."""
        terms = tuple(terms)
        key = _pkey(terms)
        if key in self._phrases:
            info = self._phrases[key] = self._phrases.pop(key)   # newest
            return info
        infos = [self._term(t) for t in terms]
        if any(i is None for i in infos):
            self._phrases[key] = None
            return None
        within = None
        if len(terms) > 2:
            # every occurrence lies in a doc that holds each adjacent pair
            # side by side: the rarest pair's docs (a phrase of its own,
            # scanned once and kept like any) bound the walk
            j = min(range(len(terms) - 1),
                    key=lambda j: infos[j].df * infos[j + 1].df)
            pair = self._phrase(terms[j: j + 2])
            within = pair.docs if pair is not None else np.empty(0, np.int32)
        docs, pf = phrase_freqs(self.fp, list(terms), slop=0,
                                docs_filter=within)
        docs = np.asarray(docs, np.int32)
        pf = np.asarray(pf, np.float32)
        # idf-free phrase lane scores: same shape as a term's BM25 lane
        # score with tf := phrase freq, so the K1_PLUS1 impact bound and
        # the COLSCALE int8 quantization both hold unchanged
        smax = 0.0
        if len(docs):
            dl = self.fp.doc_len[docs]
            denom = pf + _K1 * (1.0 - _B + _B * dl / max(self._avgdl, 1e-9))
            smax = float((pf * (_K1 + 1.0) / denom).max())
        info = _PhraseInfo(
            key=key, terms=terms, docs=docs, pf=pf,
            idf_sum=float(sum(i.idf for i in infos)), smax=smax)
        self._phrases[key] = info
        self._phrase_bytes += docs.nbytes + pf.nbytes
        for old in list(self._phrases):      # oldest first
            if self._phrase_bytes <= _PHRASE_HOST_BYTES:
                break
            if old not in self._slot_of and old != key:
                gone = self._phrases.pop(old)
                if gone is not None:
                    self._phrase_bytes -= gone.docs.nbytes + gone.pf.nbytes
        return info

    def _phrase_lane(self, info: _PhraseInfo) -> np.ndarray:
        """f32 idf-free lane scores aligned with info.docs."""
        dl = self.fp.doc_len[info.docs]
        denom = info.pf + _K1 * (1.0 - _B + _B * dl
                                 / max(self._avgdl, 1e-9))
        return (info.pf * (_K1 + 1.0) / denom).astype(np.float32)

    def ensure_phrases(self, phrase_lists: Sequence[Sequence[str]],
                       protect_extra: Sequence[str] = ()) -> None:
        """Colize slop-0 phrases: pack each phrase's (docs, lane score)
        pairs into synthetic 128-wide lane arrays and run them through the
        SAME build_columns outer-product kernel and LRU slot pool as term
        columns. Eviction/zeroing discipline is shared (_evict)."""
        faults.fault_point("column_upload", self.part_id)
        self._tick += 1
        need: List[_PhraseInfo] = []
        for terms in dict.fromkeys(tuple(p) for p in phrase_lists):
            info = self._phrase(terms)
            if info is None or not len(info.docs):
                continue
            if info.key in self._slot_of:
                self._lru[info.key] = self._tick
                continue
            need.append(info)
        if not need:
            return
        protect = {i.key for i in need} | set(protect_extra)
        deficit = len(need) - len(self._free)
        if deficit > 0:
            victims = [t for t in sorted(self._lru, key=self._lru.get)
                       if t not in protect][:deficit]
            if len(victims) < deficit:
                # capacity overflow: grant the highest-df phrases (whose
                # host intersections are the most expensive to run) and
                # leave the rest for the exact host path this batch
                capacity = len(self._free) + len(victims)
                need.sort(key=lambda pi: -len(pi.docs))
                self.stats["degraded"] += len(need) - capacity
                need = need[:capacity]
            for v in victims:
                self._evict(v)
        rows_l, n_l, base_l, slot_l = [], [], [], []
        for r, n, b, s in self._pending_zero:
            rows_l.append(r); n_l.append(n); base_l.append(b); slot_l.append(s)
        self._pending_zero = []
        if not need and not rows_l:
            # full degradation (every slot protected, nothing evictable,
            # no zeroing pending): nothing to dispatch
            return
        drows, dvals = [], []
        cursor = 0
        for info in need:
            slot = self._free.pop()
            self._slot_of[info.key] = slot
            self._lru[info.key] = self._tick
            lane = self._phrase_lane(info)
            nr = -(-len(info.docs) // 128)
            d2 = np.zeros((nr, 128), np.int32)
            v2 = np.zeros((nr, 128), np.float32)
            d2.ravel()[: len(info.docs)] = info.docs
            v2.ravel()[: len(info.docs)] = lane
            # tile partitioning mirrors _term_groups over the synthetic
            # rows; docs are ascending so row lo/hi are monotone (trailing
            # zero pad lanes keep the row max the true last doc)
            lo = d2[:, 0].astype(np.int64)
            hi = d2.max(axis=1).astype(np.int64)
            t0, t1 = int(lo[0]) // TILE, int(hi[-1]) // TILE
            tiles = np.arange(t0, t1 + 1, dtype=np.int64)
            starts = np.searchsorted(hi, tiles * TILE, side="left")
            ends = np.searchsorted(lo, (tiles + 1) * TILE, side="left")
            ng = (ends - starts).astype(np.int32)
            keep = ng > 0
            bases = (tiles[keep] * TILE).astype(np.int32)
            rows_l.append(cursor + starts[keep].astype(np.int32))
            n_l.append(ng[keep])
            base_l.append(bases)
            slot_l.append(np.full(int(keep.sum()), slot, np.int32))
            self._tile_bases[info.key] = bases
            drows.append(d2); dvals.append(v2)
            cursor += nr
        # trailing DMA pad + row-count bucketing (bounded jit traces)
        pad_rows = _row_bucket(cursor) + MAX_GROUP_ROWS - cursor
        drows.append(np.zeros((pad_rows, 128), np.int32))
        dvals.append(np.zeros((pad_rows, 128), np.float32))
        lane_docs = jnp.asarray(np.concatenate(drows, axis=0))
        lane_scores = jnp.asarray(np.concatenate(dvals, axis=0))
        self._build_columns(rows_l, n_l, base_l, slot_l,
                            lane_docs, lane_scores, len(need))
        self._count("phrase_builds", len(need))

    def _cold_contrib(self, cold_terms):
        """(docs i64 unique-sorted, contrib f64, inv) — the cold terms'
        summed contributions at their own postings, read straight off each
        term's lane scores (no cross-term binary searches); inv = where
        in docs each posting of the terms' lists, laid end to end, lies."""
        fp = self.fp
        arrs, vals = [], []
        for _, b, info in cold_terms:
            lo, hi = (int(fp.post_start[info.ord]),
                      int(fp.post_start[info.ord + 1]))
            arrs.append(np.asarray(fp.post_doc[lo:hi], np.int64))
            lanes = self._host_scores[
                info.row_start: info.row_start + info.n_rows
            ].ravel()[: hi - lo]
            vals.append(float(info.idf * b) * lanes.astype(np.float64))
        docs = np.concatenate(arrs)
        u, inv = np.unique(docs, return_inverse=True)
        acc = np.zeros(len(u), np.float64)
        np.add.at(acc, inv, np.concatenate(vals))
        return u, acc, inv

    def _cold_raw(self, cold_terms):
        """`_cold_contrib` in `_collect_gather`'s shape: (docs_raw,
        vals_raw, slack 0), the exact sums spread back over the terms'
        postings as they lie, so the host walk takes the one finish."""
        u, acc, inv = self._cold_contrib(cold_terms)
        return u[inv], acc[inv], 0.0

    # ---------------- eager sparse impact slices ----------------

    def _sp_grow(self, new_g: int) -> None:
        """Grow (or first-allocate) the granule pool to `new_g` granules.
        The host mirror is authoritative — growth re-uploads it whole, so
        mirror and device stay byte-identical for the scrubber."""
        old = self._sp_host
        host = np.zeros((new_g, SPARSE_GRAN // 128, 128), np.int32)
        if old is not None:
            host[: old.shape[0]] = old
        self._sp_host = host
        with faults.device_errors("sparse_gather", self.part_id):
            self._sp_pool = jnp.asarray(host)
            if new_g >= self._sp_cap:
                # the pool's size is part of the gather program's shape,
                # and this size is final: instantiate every rung now
                # (all-padding step lists, every step skipped) so that no
                # dispatch builds one later, whatever traffic reaches.
                # Below the cap the pool is still doubling and its
                # programs would be thrown away.
                for ns, nc in _SPARSE_RUNGS:
                    sparse_gather(_gather_desc(ns), self._sp_pool,
                                  n_chunks=nc, n_tiles=self.Dp // TILE)
        if old is None:
            integrity.register_scrub_region(
                self, "sparse_pool", lambda o: o._sp_pool,
                expected=lambda o: o._sp_host,
                repair=lambda o: setattr(
                    o, "_sp_pool", jnp.asarray(o._sp_host)))
        self._hbm.set_region("sparse_pool", self._sp_pool.nbytes)

    def _sp_evict(self, term: str) -> int:
        """Free `term`'s run for reuse at its own width; the bytes freed
        (the caller books them, once a call)."""
        g0, n_g, w, _, _ = self._sp_of.pop(term)
        self._sp_lru.pop(term, None)
        self._sp_free.setdefault(n_g, []).append(g0)
        # the stale granules stay in place (nothing references them, and
        # host mirror == device still holds); reuse overwrites both sides
        return w * 4

    def _reset_sparse(self) -> None:
        """Drop every slice (fault containment / scrub repair): zero both
        sides of the pool so mirror and device agree, and rebuild lazily."""
        delta = -int(self.stats["sparse_bytes"])
        self.stats["sparse_bytes"] = 0
        _node_sparse_add("sparse_bytes", delta)
        self._sp_of.clear()
        self._sp_lru.clear()
        self._sp_free.clear()
        self._sp_next = 1
        if self._sp_host is not None:
            self._sp_host[:] = 0
            self._sp_pool = jnp.asarray(self._sp_host)

    def _sp_alloc(self, need, protect: set) -> Tuple[List[int], int]:
        """ALLOCATE, one walk over a call's `need` [(term, info, width)]:
        a granule run for each slice in order, for as long as the pool can
        place them, and the bytes evicted for them. A run comes from its
        width's free list, then the bump pointer (growing the pool toward
        its cap), then LRU eviction: victims are taken from ONE ordering
        of `_sp_lru` a call, made at the call's first miss (what the call
        itself places is protected, so the order cannot move under it),
        evicted whatever their width until one frees a run of the width
        wanted. A victim's run is reusable only at its own width: no
        coalescing; the ladder is small enough that freed runs recycle
        quickly. `protect` is never evicted; when nothing else is left
        the walk stops and the slices placed so far stand."""
        g0s: List[int] = []
        freed = 0
        victims = None
        for _t, _info, w in need:
            n_g = w // SPARSE_GRAN
            free = self._sp_free.get(n_g)
            if not free:
                cur = 0 if self._sp_pool is None else self._sp_pool.shape[0]
                if self._sp_next + n_g > cur and cur < self._sp_cap:
                    self._sp_grow(min(self._sp_cap,
                                      max(cur * 2, self._sp_next + n_g, 64)))
                    cur = self._sp_pool.shape[0]
                if self._sp_next + n_g <= cur:
                    g0s.append(self._sp_next)
                    self._sp_next += n_g
                    continue
                if victims is None:
                    lru = self._sp_lru
                    victims = iter(sorted(lru, key=lru.get))
                for v in victims:
                    if v in protect or v not in self._sp_of:
                        continue
                    freed += self._sp_evict(v)
                    free = self._sp_free.get(n_g)
                    if free:
                        break
                else:
                    break
            g0s.append(free.pop())
        return g0s, freed

    def _ensure_sparse(self, pairs: Sequence[Tuple[str, _TermInfo]],
                       keep=()) -> bool:
        """Make the given cold (term, info) pairs' device slices resident:
        the ones that are get their LRU tick, the missing ones are built
        TOGETHER, in one pass over all of them (`_build_slices`; under
        `dispatch.slice_build`, counted in `sparse_slice_passes`): runs
        allocated in one walk, ``doc << 8 | impact`` granules packed on
        the host in one set of array operations (the mirror is the
        scrubber's truth), then batch-written into the donated device
        pool. `keep` = terms whose slices may not be recycled for them
        (an open gather group's). Returns False when any term cannot be
        sliced (df above the ladder, or pool pressure with everything
        protected) — the caller host-scores the whole batch so bound math
        never mixes tiers. Impacts are uint8-quantized on a per-term scale
        smax/255; rounding is forced to >= 1 so a real posting never
        vanishes, which widens the per-posting error to one full quant
        step (the lo >= 1 idiom of the column build, mirrored in
        _admit_gather's slack)."""
        if not self._sp_ok:
            return False
        self._tick += 1
        missing: Dict[str, _TermInfo] = {}
        for t, info in pairs:
            if t in self._sp_of:
                self._sp_lru[t] = self._tick
            else:
                missing[t] = info
        if not missing:
            return True
        widths = _sparse_widths()
        need: List[Tuple[str, _TermInfo, int]] = []
        for t, info in missing.items():
            w = next((w for w in widths if w >= info.df), None)
            if w is None:
                return False
            need.append((t, info, w))
        protect = {t for t, _ in pairs}
        protect.update(keep)
        with tracing.phase("dispatch.slice_build", terms=len(need)):
            return self._build_slices(need, protect)

    def _build_slices(self, need, protect) -> bool:
        """The work of `_ensure_sparse` for the terms that have no slice
        yet, all of them in one pass: ALLOCATE (`_sp_alloc`), PACK
        (`_pack_slices`), one upload, and the books once. A call with one
        missing term is the same pass over one term."""
        try:
            g0s, freed = self._sp_alloc(need, protect)
            # pool pressure with everything protected ends the walk early:
            # the slices placed so far still go up (a resident slice the
            # device never received would gather stale granules, silently)
            placed = need[: len(g0s)]
            widths = Counter(w for _t, _info, w in placed)
            grown = 4 * sum(w * n for w, n in widths.items()) - freed
            self.stats["sparse_bytes"] += grown
            _node_sparse_add("sparse_bytes", grown)
            if not placed:
                return False
            idx, upd = self._pack_slices(placed, g0s)
            self.stats["sparse_slices"] += len(placed)
            _node_sparse_add("sparse_slices", len(placed))
            _node_sparse_add("sparse_slice_passes", 1)
            for w, n in widths.items():
                metrics.observe("sparse_slice_width", w, count=n)
            with faults.device_errors("sparse_gather", self.part_id):
                self._sp_pool = sparse_pool_update(
                    self._sp_pool, jnp.asarray(idx), jnp.asarray(upd))
        except DeviceFaultError:
            # a half-written pool would break the mirror == device
            # invariant the scrubber enforces — drop everything
            self._reset_sparse()
            raise
        self._hbm.set_region("sparse_pool", self._sp_pool.nbytes)
        return len(placed) == len(need)

    def _pack_slices(self, placed, g0s: List[int]):
        """PACK: the slices of `placed` [(term, info, width)] at the runs
        `g0s`, all at once and a block ROW (128 lanes) at a time. A term's
        postings lie in `n_rows` consecutive rows of `fp.block_docs` /
        `_host_scores`, and its slice is those rows, packed, followed by
        zero rows up to its width: so every term's rows are read through
        one concatenated row index, quantized against their term's scale
        in one expression (the floats of a build a term at a time, so
        the same granules), and written into the call's granules with one
        scatter of rows; the granules go into the mirror with another and
        come back with their pool indices as the upload (`idx` i32 [n],
        `upd` i32 [n, 8, 128], n a rung of `_SPARSE_UP_BUCKETS`, padded
        toward the zero granule). The (chunk, tile) step pairs of every
        slice, (posting // 1024, doc // 16384) as chunk << 16 | tile, are
        read off the same rows and split at the term offsets. Each term's
        `_sp_of` entry and LRU tick are set."""
        fp, k = self.fp, len(placed)
        terms = [t for t, _info, _w in placed]
        cols = np.array([(info.row_start, info.n_rows, w // SPARSE_GRAN)
                         for _t, info, w in placed], np.int64).T
        row0, n_rows, n_g = cols
        sscale = np.array([max(float(info.smax), 1e-9)
                           for _t, info, _w in placed]) / SPARSE_IMP_MAX
        g_rows = SPARSE_GRAN // 128
        # term -> its first row of the concatenation, and its first
        # granule of the upload
        off = np.zeros((2, k + 1), np.int64)
        np.cumsum(cols[1:], axis=1, out=off[:, 1:])
        roff, goff = off
        n_r, n_gr = int(roff[-1]), int(goff[-1])
        at = np.arange(n_r) - roff[:-1].repeat(n_rows)      # row in its term
        src = at + row0.repeat(n_rows)
        docs = fp.block_docs[src]                            # [n_r, 128] i32
        lanes = self._host_scores[src]
        # a lane holds a posting where its score is over 0 (a posting's
        # tf is 1 or more; _host_block_scores gives the empty lanes, the
        # end of a term's last row, 0.0)
        real = lanes > 0
        q = lanes.astype(np.float64)
        q /= sscale.repeat(n_rows)[:, None]
        q = np.rint(q, out=q).astype(np.int32)
        np.minimum(np.maximum(q, 1, out=q), SPARSE_IMP_MAX, out=q)
        tiles = (docs // TILE).reshape(-1)
        # doc << 8 | impact, and 0 where there is no posting
        docs <<= 8
        docs |= q
        docs *= real
        nb = next((b for b in _SPARSE_UP_BUCKETS if b >= n_gr),
                  -(-n_gr // _SPARSE_UP_BUCKETS[-1]) * _SPARSE_UP_BUCKETS[-1])
        upd = np.zeros((nb * g_rows, 128), np.int32)
        upd[at + (goff[:-1] * g_rows).repeat(n_rows)] = docs
        upd = upd.reshape(nb, g_rows, 128)
        idx = np.zeros(nb, np.int32)
        idx[:n_gr] = np.arange(n_gr) + (np.asarray(g0s, np.int64)
                                        - goff[:-1]).repeat(n_g)
        self._sp_host[idx[:n_gr]] = upd[:n_gr]
        # the step pairs: a posting begins one where the tile moves from
        # the posting before it, or a chunk (8 rows) begins
        new = real.reshape(-1)
        new[1:] &= tiles[1:] != tiles[:-1]
        new.reshape(n_r, 128)[at % g_rows == 0, 0] = True
        hits = np.flatnonzero(new)
        pairs = tiles[hits] | (at // g_rows << 16)[hits >> 7]
        cuts = np.searchsorted(hits, roff * 128).tolist()
        self._sp_of.update(zip(terms, zip(
            g0s, n_g.tolist(), (w for _t, _info, w in placed),
            sscale.tolist(),
            (pairs[a:b].copy() for a, b in zip(cuts, cuts[1:])))))
        self._sp_lru.update(dict.fromkeys(terms, self._tick))
        return idx, upd

    def _sparse_on(self) -> bool:
        return self._sp_ok and bool(knob("ES_TPU_SPARSE"))

    def _admit_gather(self, g: _GatherGroup, h: _ColdGather) -> Optional[bool]:
        """PLAN, a query's part: make `h`'s cold slices resident beside
        the open group's and give it its place in `g`. False = `g` cannot
        take it (the pool cannot hold both at once, or the steps pass the
        last rung): close `g` and offer `h` an empty group. None = no
        group can (a term above the slice ladder, a pool smaller than the
        query's own slices, more chunks or steps than one program holds):
        host-scored. `slack` bounds |contrib - exact| (quantization + f32
        accumulation, the e_q certificate style). DeviceFaultError from
        the slice build passes through: it has dropped every slice, the
        open group's too."""
        cold_terms = h.cold_terms
        if not self._ensure_sparse([(t, i) for t, _b, i in cold_terms],
                                   keep=g.terms):
            return False if g.members else None
        slices, used, slack = [], [], 1e-7
        for t, b, info in cold_terms:
            g0, _n_g, _w, sscale, pairs = self._sp_of[t]
            wt = float(info.idf * b)
            slices.append((g0, wt * sscale, pairs))
            used.append(-(-info.df // SPARSE_GRAN))
            # one posting per (term, doc): quantization error <= one full
            # step per term, plus a generous f32-accumulation margin
            slack += abs(wt) * (sscale + 3e-6 * max(float(info.smax), sscale))
        n_chunks = sum(used)
        n_steps = 2 * sum(len(pairs) for _g0, _cw, pairs in slices)
        if (n_chunks > _SPARSE_QUERY_CHUNKS
                or n_steps > _SPARSE_RUNGS[-1][0]):
            return None
        if (g.n_steps + n_steps > _SPARSE_RUNGS[-1][0]
                or g.n_chunks + n_chunks > _SPARSE_RUNGS[-1][1]):
            return False
        fp = self.fp
        c0 = g.n_chunks
        for n_used, (_t, _b, info) in zip(used, cold_terms):
            h.spans.append((c0, info.df, int(fp.post_start[info.ord])))
            c0 += n_used
        h.slices, h.slack, h.group = slices, float(slack), g
        g.members.append(h)
        g.terms.update(t for t, _b, _i in cold_terms)
        g.n_chunks, g.n_steps = c0, g.n_steps + n_steps
        return True

    def _launch_gather(self, g: _GatherGroup) -> None:
        """PLAN, the group's part, and LAUNCH: the step list (a query at a
        time its scatter steps, then the same pairs as pick steps, terms
        in query order, so a doc's cold terms add up in that order), one
        packed upload + the gather program, asynchronous, and the
        result's copy to the host started at once. The launch keeps the
        pool it read alive; a later slice build (sparse_pool_update
        donates) is ordered behind it on the device. Contained: a fault
        leaves the group un-launched and collect host-scores its pairs."""
        if not g.members:
            return
        try:
            faults.fault_point("sparse_gather", self.part_id)
            # a row a SEGMENT = one slice in one phase of one query:
            # (granule run, weight, result chunk its steps start from,
            # 1 = pick); `steps` its pairs; `firsts` = where queries begin
            segs, steps, firsts = [], [], []
            at = 0
            for h in g.members:
                firsts.append(at)
                q0 = max(h.spans[0][0] - 1, 0)     # the result stays put
                mine = [pairs for _, _, pairs in h.slices]
                steps += mine + mine
                segs += [(g0, cw, q0, 0) for g0, cw, _ in h.slices]
                segs += [(g0, cw, c0, 1) for (g0, cw, _), (c0, _df, _lo)
                         in zip(h.slices, h.spans)]
                at += 2 * sum(map(len, mine))
            ns, nc = next(r for r in _SPARSE_RUNGS
                          if r[0] >= at and r[1] >= g.n_chunks)
            lens = np.fromiter(map(len, steps), np.int64, len(steps))
            g0, weight, obase, pick = (
                np.repeat(np.asarray(col, dt), lens) for col, dt in zip(
                    zip(*segs), (np.int32, np.float32, np.int32, np.int32)))
            cat = np.concatenate(steps)
            chunk = (cat >> 16).astype(np.int32)
            new = np.ones(at, bool)             # a chunk's first pick step
            np.not_equal(chunk[1:], chunk[:-1], out=new[1:])
            new[np.cumsum(lens[:-1])] = True
            desc = np.empty((4, ns), np.int32)
            desc[0, :at] = g0 + chunk
            desc[1, :at] = weight.view(np.int32)
            desc[2, :at] = ((cat & 0xFFFF)
                            | np.where(pick, SG_PICK, SG_SCATTER)
                            | np.where(new & (pick > 0), SG_FIRST, 0))
            desc[2, firsts] |= SG_FIRST
            desc[3, :at] = obase + chunk * pick
            # padding: no kind, and the last step's granule and result
            # chunk, so that nothing is fetched or written for it
            desc[:, at:] = desc[:, at - 1: at]
            desc[2, at:] = 0
            first = hbm_ledger.note_dispatch("turbo_sparse", ns)
            t0 = time.monotonic()
            with faults.device_errors("sparse_gather", self.part_id):
                out = sparse_gather(desc, self._sp_pool, n_chunks=nc,
                                    n_tiles=self.Dp // TILE)
                out.copy_to_host_async()
            if first:
                hbm_ledger.note_compile_done("turbo_sparse", ns,
                                             time.monotonic() - t0)
        except DeviceFaultError:
            return
        finally:
            # the plan is spent; the queries keep the group, not it them
            g.members, g.terms = [], set()
        g.out, g.launched = out, True
        self._sp_inflight += 1
        self.stats["sparse_gather_launches"] += 1
        _node_sparse_add("sparse_gather_launches", 1)

    def _start_gathers(self, colds, early: bool) -> Dict[int, _ColdGather]:
        """Plan + launch the cold sides `colds` [(query index, cold
        terms)] of one dispatch chunk, in that order: {query index:
        gather}. Consecutive queries share a group, and so a program, for
        as long as `_admit_gather` finds room; a group is closed and
        launched BEFORE the next one's slices are built, so those may
        recycle its granules. A slice build that faults has dropped the
        open group's slices with its own: that group is not launched."""
        handles: Dict[int, _ColdGather] = {}
        g = _GatherGroup(early)
        for qi, cold_terms in colds:
            h = handles[qi] = _ColdGather(cold_terms)
            try:
                took = self._admit_gather(g, h)
                if took is False:
                    self._launch_gather(g)
                    g = _GatherGroup(early)
                    self._admit_gather(g, h)
            except DeviceFaultError:
                h.group = g
                g.members, g.terms = [], set()
                g = _GatherGroup(early)
        self._launch_gather(g)
        return handles

    def _chunk_gathers(self, plan: _ChunkPlan) -> Dict[int, _ColdGather]:
        """`_start_gathers` for every query of a dispatch chunk that has a
        cold term here, ahead of its finish and in finish's order (empty
        with the sparse tier off)."""
        if not plan.sparse:
            return {}
        return self._start_gathers(
            [(qi, cold) for qi, cold in enumerate(plan.cold) if cold], True)

    def _discard_gather(self, h: Optional[_ColdGather]) -> None:
        """Drop the launched program of a gather nobody will collect (its
        chunk is being host-scored after a sweep fault, or the call is
        unwinding), for every query of its group."""
        g = None if h is None else h.group
        if g is not None and g.out is not None:
            g.out = None
            self._sp_inflight -= 1

    def _collect_gather(self, h: _ColdGather):
        """COLLECT: (docs_raw, vals_raw, slack) — the pair's cold postings
        AS THEY LIE, the terms' lists laid end to end in query order, and
        beside each the accumulator cell it reads: the doc's WHOLE cold
        contribution, the same value at every one of its occurrences (a
        doc in several of the lists reads one cell several times).
        Nothing is enumerated here, no `np.unique`: the survivor bound
        runs on the raw arrays first (`_cold_survivors`) and only what it
        keeps is made distinct. The group's block is fetched by the first
        of its queries to ask. A pair whose group was not launched, or
        whose fetch faults, is host-scored (`h.host`): `_cold_contrib`'s
        exact sums spread back over the postings, slack 0, and the same
        flow from there."""
        g, flat = h.group, None
        if g is not None:
            if g.out is not None:
                out, g.out = g.out, None
                self._sp_inflight -= 1
                try:
                    faults.fault_point("sparse_gather", self.part_id)
                    with faults.device_errors("sparse_gather", self.part_id):
                        g.flat = np.asarray(out).reshape(-1)
                except DeviceFaultError:
                    pass
            if g.launched and g.early:
                self.stats["sparse_gather_overlapped"] += 1
                _node_sparse_add("sparse_gather_overlapped", 1)
            flat = g.flat
        if flat is None:
            h.host = True
            self.stats["sparse_fallbacks"] += 1
            _node_sparse_add("sparse_fallbacks", 1)
            return self._cold_raw(h.cold_terms)
        fp = self.fp
        docs_l, vals_l = [], []
        for c0, df, lo in h.spans:
            docs_l.append(fp.post_doc[lo: lo + df])
            base = c0 * SPARSE_GRAN
            vals_l.append(flat[base: base + df])
        return (np.concatenate(docs_l),
                np.concatenate(vals_l).astype(np.float64), h.slack)

    def sparse_hot_terms(self) -> List[str]:
        """Terms with a resident sparse slice — the warm-handoff payload a
        relocation source ships so its target can pre-slice the cold tier
        (indices/shard_service.py warm_relocation_handoff)."""
        return sorted(self._sp_of)

    def prewarm_sparse(self, terms: Sequence[str]) -> int:
        """Build slices for the given terms ahead of traffic (relocation
        warm handoff). Best-effort; returns how many slices are resident
        afterwards among the requested cold terms."""
        if not (self._sp_ok and bool(knob("ES_TPU_SPARSE"))):
            return 0
        pairs = []
        for t in dict.fromkeys(terms):
            info = self._term(t)
            if info is not None and info.df and info.df < self.cold_df:
                pairs.append((t, info))
        if not pairs:
            return 0
        try:
            self._ensure_sparse(pairs)
        except DeviceFaultError:
            pass
        return sum(1 for t, _ in pairs if t in self._sp_of)

    def prebuild_columns(self) -> int:
        """Build every colizable term's column now (capacity-capped, by
        df desc). Serving warms lazily; benchmarks and latency-sensitive
        deployments call this so no timed query ever pays a build."""
        fp = self.fp
        terms = [fp.terms[o] for o in
                 np.nonzero(np.asarray(fp.doc_freq) >= self.cold_df)[0]]
        terms.sort(key=lambda t: -int(fp.doc_freq[fp.term_to_ord[t]]))
        terms = terms[: self.Hp]       # capacity-capped: never churn
        self.ensure_columns(terms)
        return len(terms)

    # ---------------- host exact scoring helpers ----------------

    def _impacts_at(self, info: _TermInfo, docs: np.ndarray) -> np.ndarray:
        """Exact idf-free impact of a term at the given doc ids (0 where
        the term does not occur). Indexes the [rows, 128] lane matrix
        directly — ravel()ing the term's lanes here used to copy up to
        df*4 bytes (36MB for a stopword-grade term) per query and was 90%
        of serving batch time at 10M docs."""
        fp = self.fp
        lo, hi = int(fp.post_start[info.ord]), int(fp.post_start[info.ord + 1])
        tdocs = fp.post_doc[lo:hi]
        out = np.zeros(len(docs), np.float32)
        if not len(tdocs):
            return out
        # needles MUST match the postings dtype: int64 needles make numpy
        # promote (= copy/cast the multi-million-entry array) per call —
        # 44ms vs 1.3ms measured for a 9M-df term
        docs = docs.astype(np.int32, copy=False) \
            if docs.dtype != tdocs.dtype else docs
        j = np.searchsorted(tdocs, docs)
        j_c = np.minimum(j, len(tdocs) - 1)
        present = (j < len(tdocs))
        present &= tdocs[j_c] == docs
        jp = j_c[present]
        out[present] = self._host_scores[info.row_start + (jp >> 7),
                                         jp & 127]
        return out

    def _column_impacts(self, info: _TermInfo, slot: int,
                        word: np.ndarray, bit: np.ndarray) -> np.ndarray:
        """`_impacts_at` for a term that owns a column, without the
        search, at docs given as (word = doc // 64, bit = doc % 64 as
        u64): the doc is posting _col_cnt[word] + (set bits of its word
        below `bit`) of the term's list, where its bit is set."""
        w = self._col_bits[slot, word]
        at = self._col_cnt[slot, word] + np.bitwise_count(
            w & _LOW_BITS[bit]).astype(np.int32)
        # (a doc past the term's last posting reads that one: zeroed below)
        out = self._host_scores.reshape(-1)[
            info.row_start * 128 + np.minimum(at, info.df - 1)]
        out[((w >> bit) & np.uint64(1)) == 0] = 0.0
        return out

    def _exact_merge(self, qterms, k: int):
        """Full host posting merge (exact, any df) — the fallback when a
        certificate fails. Term-at-a-time f32 accumulation in query
        order, (score desc, doc asc) rank over live docs."""
        all_docs = []
        for _, _, info in qterms:
            fp = self.fp
            lo, hi = (int(fp.post_start[info.ord]),
                      int(fp.post_start[info.ord + 1]))
            all_docs.append(fp.post_doc[lo:hi])
        if not all_docs:
            return np.empty(0, np.float32), np.empty(0, np.int32)
        docs = np.unique(np.concatenate(all_docs))
        docs = docs[self._live_host[docs] > 0]
        totals = self._exact_scores(qterms, docs)
        pos = totals > 0
        docs, totals = docs[pos], totals[pos]
        sel = np.lexsort((docs, -totals))[:k]
        return totals[sel], docs[sel].astype(np.int32)

    def _exact_scores(self, qterms, docs: np.ndarray) -> np.ndarray:
        """Exact f32 totals at docs, term-at-a-time in query order — the
        same accumulation order as the reference CPU scorer. qterms
        [(term, boost, info)]: every term's list is searched for the docs
        (`_impacts_at`: the host tiers, the fallbacks). The chunk-wide
        finish gives a fourth member where it need not search: the slot
        of the term's column, read through the column's host index
        (`_column_impacts`), or the term's impacts at docs as it already
        holds them (`_survivor_terms`). And candidates that come as picked
        ROWS are row-aligned: given a chunk's plan and its rows [n, R] the
        totals of every doc in them are read by row span, the whole chunk
        at once (`_rescore_rows`: the same expression a cell). A bool
        query's clause sums come as the f64 array `_exact_bool` made of
        them, for their one downcast. Every exact score a finish
        produces leaves through this one function."""
        if isinstance(qterms, _ChunkPlan):
            return self._rescore_rows(qterms, docs)
        if isinstance(qterms, np.ndarray):
            return qterms.astype(np.float32)
        total = np.zeros(len(docs), np.float32)
        word = None
        for _, boost, info, *known in qterms:
            w = np.float32(info.idf * boost)
            if not known:
                impacts = self._impacts_at(info, docs)
            elif isinstance(known[0], np.ndarray):
                impacts = known[0]
            else:
                if word is None:
                    word, bit = docs >> 6, (docs & 63).astype(np.uint64)
                impacts = self._column_impacts(info, known[0], word, bit)
            total = total + w * impacts
        return total

    def point_scores(self, terms, docs: np.ndarray) -> np.ndarray:
        """Exact f32 BM25 of one query at GIVEN docs of this partition
        (0 where no term occurs, or the doc is not live): a host point
        read of `_exact_scores`, at most len(docs) x the query's terms, no
        sweep. `terms`: a term / (term, boost) list as `search_many` takes
        it; duplicate terms sum and the terms this partition lacks fall
        out as there, so a doc the sweep would have returned reads the
        same bits here. For callers that hold candidates the sweep did not
        propose (the hybrid route: the `k` nearest vectors)."""
        docs = np.asarray(docs, np.int32)
        (flat,), _ = _flatten_queries([[terms]])
        qterms = [(t, b, info) for t, b in flat
                  for info in (self._term(t),) if info is not None]
        if not qterms or not len(docs):
            return np.zeros(len(docs), np.float32)
        out = self._exact_scores(qterms, docs)
        out[self._live_host[docs] <= 0] = 0.0
        return out

    # ---------------- search ----------------

    def search_many(self, batches: Sequence[List], k: int = 10, check=None):
        """Pipeline batches of queries; returns per batch
        (scores [Q, k] f32, ords [Q, k] i32). Queries are term lists or
        (term, boost) lists. check: optional cooperative-cancellation
        callable invoked between dispatches (tasks/task_manager)."""
        with tracing.steps(DISPATCH_STEPS):
            return self._search_many(batches, k, check)

    def _search_many(self, batches, k, check):
        flat, spans = _flatten_queries(batches)
        if not flat:
            return [(np.zeros((n, k), np.float32), np.zeros((n, k), np.int32))
                    for _, n in spans]
        # cold terms ride along: ensure_columns builds their eager sparse
        # slices in the same upload pass the columns use
        with tracing.phase("dispatch.prep", queries=len(flat)):
            self.ensure_columns(
                [t for q in flat for t, _ in q
                 if self._term(t) is not None])

        # pass 1: sweep -> row pick, both on device, dispatched async per
        # chunk; only the packed [QC, n_rows+1] pick output crosses to the
        # host (the [nsw, QC, CAND_PAD] sweep output the r4 version fetched
        # is ~100x larger, and the transfer is a sync per batch)
        n_rows = max(_GLOBAL_ROWS, k + 5)
        pending = []
        out_s = np.zeros((len(flat), k), np.float32)
        out_d = np.zeros((len(flat), k), np.int32)
        try:
            self._sweep_chunks(flat, n_rows, check, pending)
            # pass 2: fetch the tiny row sets; EXACT host rescore of every
            # doc in the collected rows, a chunk at a time
            # (_finish_chunk), merged with the cold side, whose gathers
            # are already on their way to the host
            for off, plan, packed_dev, gathers in pending:
                if check is not None:
                    check()
                with tracing.phase("dispatch.device_wait"), \
                        faults.device_errors("turbo_sweep", self.part_id):
                    packed = np.asarray(packed_dev)    # [QC, n_rows + 1]
                n = len(plan.chunk)
                with tracing.phase("dispatch.finish", queries=n):
                    self._finish_chunk(
                        plan, packed[:n, :n_rows].astype(np.int64),
                        packed[:n, n_rows], k, gathers,
                        out_s[off: off + n], out_d[off: off + n])
        finally:
            for *_, gathers in pending:    # a fault or a cancel unwinding
                for h in gathers.values():
                    self._discard_gather(h)
        return [(out_s[o: o + n], out_d[o: o + n]) for o, n in spans]

    def _sweep_chunks(self, flat, n_rows: int, check, pending: list) -> None:
        """Pass 1 of `_search_many`: per chunk the sweep and the row pick,
        then every query's cold-side gather behind them, all asynchronous.
        Appends (offset, plan, picked, {query: gather}) to `pending`."""
        off = 0
        while off < len(flat):
            rem = len(flat) - off
            # smallest compiled width that covers the remainder (ADVICE r4:
            # intermediate qc_sizes used to be dead)
            take = next((s for s in self.qc_sizes if s >= rem),
                        self.qc_sizes[-1])
            chunk = flat[off: off + take]
            if check is not None:
                check()
            # compile-cache telemetry: the first dispatch at a new width
            # IS the XLA trace, so its wall time is the compile cost
            first_trace = hbm_ledger.note_dispatch("turbo", take)
            tc0 = time.monotonic()
            with tracing.phase("dispatch.prep", qc=take):
                plan = self._plan_chunk(chunk, take)
            with tracing.phase("dispatch.launch", qc=take) as ph:
                with faults.device_dispatch("turbo_sweep", self.part_id):
                    rm, rr = sweep_rowmax(
                        jnp.asarray(plan.qscale), self.cols_hi,
                        self.cols_lo, jnp.asarray(plan.wq), self.live,
                        QC=take, nsw=self.nsw)
                with faults.device_errors("turbo_sweep", self.part_id):
                    picked = _pick_rows(rm, rr, n_rows=n_rows)
                if first_trace:
                    hbm_ledger.note_compile_done(
                        "turbo", take, time.monotonic() - tc0)
                gathers = self._chunk_gathers(plan)
                ph.meta["gathers"] = len(gathers)
            pending.append((off, plan, picked, gathers))
            off += len(chunk)
        self.stats["dispatches"] += len(pending)

    def search(self, queries: List[List], k: int = 10):
        return self.search_many([queries], k)[0]

    def _collect_docs(self, rw: np.ndarray) -> np.ndarray:
        """Live doc ids in one bool query's picked rows ([n_rows] i64, -1
        = empty slot) — shared by the solo bool pass 2 and the fused one."""
        rw = rw[rw >= 0]
        docs = (rw[:, None] * 128 + _LANE128[None, :]).ravel()
        if len(docs):
            docs = docs[self._live_host[docs] > 0]
        return docs

    def _plan_chunk(self, chunk, QC: int) -> _ChunkPlan:
        """Resolve one dispatch chunk against this partition (see
        `_ChunkPlan`): terms, the cold / colized split, the quantized
        disjunctive sweep inputs (wq [2, QC, Hp+1] i8, qscale [QC, 1]
        f32) with their e_q, and the (query, term) pair arrays. A None
        entry (a query another partition dispatches but this one does not)
        leaves an all-zero weight row — the kernel scores query columns
        independently, so zero rows change nothing for its peers."""
        plan = _ChunkPlan()
        plan.chunk = chunk
        plan.sparse = self._sparse_on()
        plan.wq = wq = np.zeros((2, QC, self.Hp + 1), np.int8)
        plan.qscale = qscale = np.ones((QC, 1), np.float32)
        plan.qterms, plan.cold = [], []
        plan.e_q, plan.col_const, plan.col_floor = [], [], []
        plan.f32_err = []
        slot_of, term = self._slot_of, self._term
        by_pos: List[List[tuple]] = []
        for qi, terms in enumerate(chunk):
            qterms, cold, slots, ws = [], [], [], []
            col_const = col_floor = most = 0.0
            for t, b in terms or ():
                info = term(t)
                if info is None:
                    continue
                most += abs(info.idf * b) * info.smax
                # colized = owns a column NOW (a term past cold_df may have
                # been left cold by capacity degradation)
                slot = slot_of.get(t, -1)
                w = info.idf * b
                if slot < 0:
                    cold.append((t, b, info))
                else:
                    slots.append(slot)
                    ws.append(w)
                    # the most and the least a column term can add
                    col_const += max(w, 0.0) * info.smax
                    col_floor += min(w, 0.0) * info.smax
                if len(by_pos) == len(qterms):
                    by_pos.append([])
                by_pos[len(qterms)].append(
                    (qi, slot, info.ord, info.row_start, w))
                qterms.append((t, b, info, slot))
            steps, qscale[qi, 0], e_q = _quantize(ws)
            for slot, (wh, wl) in zip(slots, steps):
                wq[0, qi, slot] = np.int8(wh)
                wq[1, qi, slot] = np.int8(wl)
            plan.qterms.append(qterms)
            plan.cold.append(cold)
            plan.e_q.append(e_q)
            plan.col_const.append(col_const)
            plan.col_floor.append(col_floor)
            plan.f32_err.append(_f32_err(len(qterms), most))
        pairs = [p for at in by_pos for p in at]
        cols = list(zip(*pairs)) if pairs else [()] * 5
        plan.pq = np.asarray(cols[0], np.int64)
        plan.pslot = np.asarray(cols[1], np.int64)
        ords = np.asarray(cols[2], np.int64)
        plan.plo = self.fp.post_start[ords].astype(np.int64)
        plan.phb = np.asarray(cols[3], np.int64) * 128 - plan.plo
        plan.pw = np.asarray(cols[4], np.float64).astype(np.float32)
        plan.pos_end = np.cumsum([len(at) for at in by_pos])
        ci = np.flatnonzero(plan.pslot < 0)
        plan.cold_pairs = list(zip(
            ci.tolist(), plan.pq[ci].tolist(), plan.plo[ci].tolist(),
            self.fp.post_start[ords[ci] + 1].tolist()))
        return plan

    # ---------------- the chunk-wide finish ----------------

    def _rescore_rows(self, plan: _ChunkPlan, rows_all: np.ndarray):
        """Exact totals of every doc in the chunk's picked rows
        (rows_all [n, R] i64, -1 = empty slot), as a flat [n * R * 128]
        f32 plane: cell (q * R + slot) * 128 + (doc & 127). The 128 docs of
        a row are consecutive, so a term's postings inside a row are ONE
        span of its list: `_col_cnt` holds its ends for a term that owns a
        column, a search of the row's two edges in the list finds them for
        one that does not. Term POSITION at a time over the whole chunk, each
        posting adding w * impact to its cell in f32: the expression
        `_exact_scores` evaluates, in its order (a term a doc lacks adds
        w * 0.0 there, which changes no total), so the totals are its
        bits."""
        n, R = rows_all.shape
        plane = np.zeros(n * R * 128, np.float32)
        m = len(plan.pq)
        if not m:
            return plane
        fp = self.fp
        valid = rows_all >= 0
        rc = np.where(valid, rows_all, 0)
        s = np.zeros((m, R), np.int64)
        e = np.zeros((m, R), np.int64)
        ci = np.flatnonzero(plan.pslot >= 0)
        if len(ci):
            rq = 2 * rc[plan.pq[ci]]
            sl = plan.pslot[ci][:, None]
            s[ci] = self._col_cnt[sl, rq]
            e[ci] = self._col_cnt[sl, rq + 2]
        # needles of the postings' dtype, or numpy casts the list a call
        # (_impacts_at)
        edges = (np.concatenate([rc, rc + 1], axis=1) * 128).astype(
            fp.post_doc.dtype)
        has_rows = valid.any(axis=1).tolist()
        for i, qi, lo, hi in plan.cold_pairs:
            if has_rows[qi]:
                se = fp.post_doc[lo:hi].searchsorted(edges[qi])
                s[i] = se[:R]
                e[i] = se[R:]
        # every posting of every span: p = its index in fp.post_doc
        lens = np.where(valid[plan.pq], e - s, 0).ravel()
        ends = np.cumsum(lens)
        total = int(ends[-1])
        if not total:
            return plane
        first = ((plan.plo[:, None] + s).ravel() - (ends - lens))
        span = np.repeat(np.arange(m * R), lens)
        p = first[span] + np.arange(total)
        pair, slot = np.divmod(span, R)
        cell = ((plan.pq[pair] * R + slot) * 128
                + (fp.post_doc[p] & 127))
        add = plan.pw[pair] * self._host_scores.reshape(-1)[
            p + plan.phb[pair]]
        a = 0
        for b in ends[plan.pos_end * R - 1].tolist():
            if b > a:
                c = cell[a:b]      # one term a query: no cell twice
                plane[c] = plane[c] + add[a:b]
            a = b
        return plane

    def _cold_survivors(self, docs_raw: np.ndarray, vals_raw: np.ndarray,
                        slack: float, n_terms: int, col_const: float,
                        col_floor: float, f32_err: float, kth_0: float,
                        k: int) -> np.ndarray:
        """THE SURVIVOR BOUND, on the raw postings of one pair's cold side
        (`_collect_gather`): the ascending positions of those whose doc is
        live and could reach or tie the final k-th score. Everything else
        is never enumerated, made distinct or scored.

        `vals_raw` is a doc's whole cold contribution within `slack`
        (`_admit_gather`), the column terms add at most `col_const` and at
        least `col_floor` (0 unless a boost is negative), and `f32_err`
        (`_f32_err`: 1e-5 and what the query's boosts add to it) covers
        the exact scorer's f32 weights and accumulation, so a live doc's
        exact total lies in [vals - slack - f32_err + col_floor,
                             vals + slack + f32_err + col_const].
        The upper end has always been tested against `kth_0`, the k-th
        best exact total among the docs of the picked ROWS. Those rows are
        picked by the column terms; a query's rare terms are cold, carry
        the idf, and their docs are mostly elsewhere, so `kth_0` is far
        under the true k-th (0 for a cold-only query). The cold side
        supplies the better threshold itself, from the LOWER end: a doc
        occurs at most once a cold term, so at most `n_terms` times in
        the raw values, and the (k x n_terms)-th largest live raw value is
        at most the k-th best DISTINCT live doc's (the rank has to
        tolerate duplicates because nothing is distinct yet: `_top_k`
        makes the same argument with 2k). k live docs therefore have an
        exact total of at least that value's lower end: it is a lower
        bound on the final k-th score, as `kth_0` is, and a doc whose
        upper end is under the larger of the two can neither enter the
        top k nor tie with it. One `np.partition` a pair, no exact score
        computed to get it. The test depends on the doc alone (every
        occurrence holds the same value), so a surviving doc keeps ALL
        its cold postings, which `_survivor_terms` relies on."""
        live_host = self._live_host
        kth, rank, n = kth_0, k * n_terms, len(vals_raw)
        low = None          # the rank-th largest raw value of a live doc
        if n >= rank:
            # liveness is looked up where it can matter only: at the best
            # `rank` raw values (all live, nearly always: they are then
            # the live values' best too), and below at what passed
            best = np.argpartition(vals_raw, n - rank)[n - rank:]
            if (live_host[docs_raw[best]] > 0).all():
                low = vals_raw[best].min()
            else:           # a deleted doc among the best: rank the live
                lv = vals_raw[live_host[docs_raw] > 0]
                if len(lv) >= rank:
                    low = np.partition(lv, len(lv) - rank)[len(lv) - rank]
        if low is not None:
            kth = max(kth, float(low) - slack - f32_err + col_floor)
        sel = np.flatnonzero(vals_raw >= kth - (slack + col_const + f32_err))
        return sel[live_host[docs_raw[sel]] > 0]

    def _survivor_terms(self, qterms, sel: np.ndarray, at: np.ndarray,
                        n: int):
        """A plan's qterms for `_exact_scores` at the cold side's `n`
        survivors: `sel` the surviving postings' positions in the raw
        layout (the cold terms' lists laid end to end in query order,
        ascending), `at` the survivor each belongs to. A surviving doc's
        cold postings all survive, so a cold term's impacts at the
        survivors are written out from the surviving postings' own
        positions (one search of the cumulative df finds the terms'
        boundaries in `sel`) and nothing df-sized is touched; a term
        that owns a column keeps its slot."""
        ends = np.cumsum([q[2].df for q in qterms if q[3] < 0])
        cuts = iter(np.searchsorted(sel, ends).tolist())
        lanes = self._host_scores.reshape(-1)
        out, a, o = [], 0, 0         # a: into sel, o: the term's first posting
        for t, boost, info, known in qterms:
            if known < 0:
                b = next(cuts)
                known = np.zeros(n, np.float32)
                known[at[a:b]] = lanes[
                    sel[a:b] + (info.row_start * 128 - o)]
                a, o = b, o + info.df
            out.append((t, boost, info, known))
        return out

    def _finish_chunk(self, plan: _ChunkPlan, rows_all, bounds, k: int,
                      gathers: Dict[int, _ColdGather], out_s, out_d) -> None:
        """Pass 2 of one dispatch chunk on this partition: merge the
        device-collected candidates + the cold side into exact top-k,
        written to out_s / out_d [n, k].

        rows_all [n, R] the rows the device picked — every live doc in
        them is rescored EXACTLY (`_rescore_rows`, the whole chunk at
        once), so quantization error only matters for UNCOLLECTED rows;
        bounds [n] — the max approximate score any of those could hold
        (device pick output); gathers — the queries' cold sides as
        `_chunk_gathers` launched them behind the sweep. The cold side,
        the merge and the certificate run a query at a time, the cold
        side in this order: collect the pair's cold postings RAW
        (`_collect_gather`: no `np.unique`), bound them
        (`_cold_survivors`: liveness and the test against the larger of
        the rows' k-th exact total and the cold side's own k-th lower
        bound, the (k x cold terms)-th largest raw value less its slack,
        a rank that tolerates a doc's duplicates among the raw values),
        and only then make the SURVIVORS distinct, write their impacts
        (`_survivor_terms`) and score them exactly. A cold-only query
        takes the same test. What the bound drops is provably under the
        final k-th score; every exact score still leaves through
        `_exact_scores`, so the answers are the same bits.

        Every ms is a named step of the engine call (`DISPATCH_STEPS`):
        the chunk-wide rescore is a phase (`dispatch.rescore_rows`); the
        steps of the per-query loop are timed by clock reads, lap after
        lap, and go into the call's accumulator once a (partition, chunk)
        (`tracing.steps.add`: one span each, laid end to end from the
        loop's start), the loop under ONE annotation
        (`es.dispatch.finish_pairs`), not under two phases a query.
        `dispatch.rescore` = rows + survivors; the survivors' `np.unique`
        is booked under `dispatch.rescore_survivors` (it is part of
        scoring them), `dispatch.sparse_gather` stays the collect alone
        and `dispatch.survivor_bound` the test on the raw postings. The
        spans' `docs`: raw postings enumerated (`sparse_gather`,
        `survivor_bound`), distinct survivors (`rescore_survivors`);
        the same two sums feed `tpu_turbo.cold_enum_docs` /
        `cold_survivor_docs`."""
        n, R = rows_all.shape
        # (`es.dispatch.rescore` stays the profiler's name for the
        # chunk-wide rescore: an annotation, the histogram is fed below)
        with tracing.annotation("dispatch.rescore", queries=n), \
                tracing.phase("dispatch.rescore_rows", queries=n, rows=int(
                    np.count_nonzero(rows_all >= 0))) as rows:
            plane = self._exact_scores(plan, rows_all)
            # candidates = cells with a positive total whose doc is live
            cells = np.flatnonzero(plane > 0)
            cdocs = rows_all.reshape(-1)[cells >> 7] * 128 + (cells & 127)
            lv = self._live_host[cdocs] > 0
            cells, cdocs = cells[lv], cdocs[lv]
            cand_s = plane[cells]
            q_off = np.searchsorted(
                cells, np.arange(n + 1) * (R * 128)).tolist()
            rows.meta["candidates"] = len(cells)
        left = 0
        clock = time.monotonic_ns
        # ns in each step of the loop, and what sized it
        ns_gather = ns_bound = ns_surv = ns_merge = 0
        n_cold = n_enum = n_surv = n_merged = 0
        t_loop = clock()
        with tracing.annotation("dispatch.finish_pairs", queries=n):
            for qi in range(n):
                qterms = plan.qterms[qi]
                if not qterms:
                    continue
                docs = cdocs[q_off[qi]: q_off[qi + 1]]
                totals = cand_s[q_off[qi]: q_off[qi + 1]]
                cold_terms = plan.cold[qi]
                colized = len(cold_terms) < len(qterms)
                host_scored = False
                # (the slices above, and below the books and a gather not
                # hoisted, are the loop's own remainder under `finish`)
                t = clock()

                # ---- cold side, bound-pruned (the 10M-doc bottleneck was
                # exact-scoring EVERY cold-touched doc — up to 2 x cold_df
                # of them — with binary searches into multi-million-entry
                # colized posting lists; a doc whose cold contribution plus
                # the colized terms' maximum possible addend cannot reach
                # a lower bound on the k-th score needs no lookup at all,
                # and is not even enumerated) ----
                if cold_terms:
                    if plan.sparse:
                        self.stats["sparse_queries"] += 1
                        _node_sparse_add("sparse_queries", 1)
                        h = gathers.pop(qi, None)
                        if h is None:     # not hoisted: back to back, here
                            h = self._start_gathers([(qi, cold_terms)],
                                                    False)[qi]
                        # `dispatch.sparse_gather` is the collect alone, as
                        # the phase a query it replaces was
                        t = clock()
                        docs_r, vals_r, slack = self._collect_gather(h)
                        t1 = clock()
                        ns_gather += t1 - t
                        host_scored = h.host
                    else:
                        self.stats["cold_queries"] += 1
                        docs_r, vals_r, slack = self._cold_raw(cold_terms)
                        t1 = clock()
                    # the bound BEFORE the enumeration (`_cold_survivors`:
                    # the k-th of the picked rows' exact totals, raised by
                    # the cold side's own k-th lower bound); a cold-only
                    # query has no row candidates and takes the same test
                    kth_0 = 0.0
                    if len(totals) >= k:
                        kth_0 = float(np.partition(
                            totals, len(totals) - k)[len(totals) - k])
                    sel = self._cold_survivors(
                        docs_r, vals_r, slack, len(cold_terms),
                        plan.col_const[qi], plan.col_floor[qi],
                        plan.f32_err[qi], kth_0, k)
                    t = clock()
                    ns_bound += t - t1
                    n_cold += 1
                    n_enum += len(docs_r)
                    if len(sel):
                        # the survivors made distinct: booked, with their
                        # impacts and exact scores, under rescore_survivors
                        cold_docs, into = np.unique(docs_r[sel],
                                                    return_inverse=True)
                        cold_s = self._exact_scores(
                            self._survivor_terms(qterms, sel, into,
                                                 len(cold_docs)),
                            cold_docs)
                        t1 = clock()
                        ns_surv += t1 - t
                        t = t1
                        n_surv += len(cold_docs)
                        pos = cold_s > 0
                        docs = np.concatenate([docs, cold_docs[pos]])
                        totals = np.concatenate([totals, cold_s[pos]])

                ns_fallback = 0
                if len(docs):
                    n_merged += len(docs)
                    s, d = self._top_k(docs, totals, k)

                    # ---- certificate ----
                    if colized:
                        # every collected doc is EXACT; a doc outside the
                        # pool sits in an uncollected row, whose approximate
                        # rowmax bound plus the quantization error bounds
                        # its true score
                        uncollected = float(bounds[qi])
                        limit = uncollected + plan.e_q[qi]
                        kth = float(s[k - 1]) if len(s) >= k else 0.0
                        short = len(s) < k and uncollected > 0
                        if short or (len(s) >= k and kth < limit
                                     and uncollected > 0):
                            self.stats["fallbacks"] += 1
                            host_scored = True
                            with tracing.phase("dispatch.cert_fallback") \
                                    as fallback:
                                if self.fallback is not None:
                                    s, d = self.fallback(plan.chunk[qi], k)
                                else:
                                    s, d = self._exact_merge(
                                        [q[:3] for q in qterms], k)
                            ns_fallback = int(fallback.ms * 1e6)
                    left += host_scored
                    out_s[qi, : len(s)] = s
                    out_d[qi, : len(d)] = d
                ns_merge += clock() - t - ns_fallback
        at = t_loop
        for name, ns, meta in (
                ("dispatch.sparse_gather", ns_gather,
                 {"pairs": n_cold, "docs": n_enum}),
                ("dispatch.survivor_bound", ns_bound, {"docs": n_enum}),
                ("dispatch.rescore_survivors", ns_surv, {"docs": n_surv}),
                ("dispatch.merge_cert", ns_merge, {"docs": n_merged})):
            tracing.steps.add(name, ns / 1e6, at, **meta)
            at += ns
        tracing.steps.add("dispatch.rescore", rows.ms + ns_surv / 1e6)
        self._count_finish(n - left, left, n_enum, n_surv)

    def _count_finish(self, bulk: int, left: int, enum: int = 0,
                      surv: int = 0) -> None:
        for key, v in (("finish_bulk_pairs", bulk),
                       ("finish_pair_fallbacks", left),
                       ("cold_enum_docs", enum),
                       ("cold_survivor_docs", surv)):
            self.stats[key] += v
            _node_sparse_add(key, v)

    @staticmethod
    def _top_k(docs: np.ndarray, totals: np.ndarray, k: int):
        """(scores, ords) of the k best by (score desc, doc asc) among
        docs with their exact positive totals. A doc may come twice, once
        from the picked rows and once from the cold side, with the same
        total: the 2k-th largest total is then at most the k-th best
        DOC's, so only what reaches it needs sorting."""
        n = len(totals)
        if n > 2 * k:
            floor = np.partition(totals, n - 2 * k)[n - 2 * k]
            top = totals >= floor
            docs, totals = docs[top], totals[top]
        docs, first = np.unique(docs, return_index=True)
        totals = totals[first]
        sel = np.lexsort((docs, -totals))[:k]
        return totals[sel], docs[sel].astype(np.int32)

    # ---------------- bool / phrase search ----------------
    #
    # A bool request is a conjunction: its matches are the docs every
    # required clause (must, filter, slop-0 phrase) holds and no prohibited
    # one does. The DEVICE computes that set for every (partition, query):
    # each clause has a row of the packed bitsets (a term with a column:
    # the column's presence, exact because the build kernel forces lo >= 1
    # on presence-only cells; a phrase: its adjacency column's; a COLD term,
    # df < cold_df: a cold row packed from its postings when first named,
    # `_ensure_cold_rows`), `intersect_bitset` ANDs / AND-NOTs them, and the
    # population count of the result among live docs is the partition's
    # exact hit count (`mask_live_counts`: hits.total comes from the route
    # that found the hits). The top k then come one of two ways, chosen by
    # what the plan can see, the rarest required clause's df:
    #
    # * under cold_df (a cold lead, a rare phrase) every match fits an exact
    #   rescore: the query's mask row is fetched, its set bits ARE the
    #   candidates, `_exact_bool` scores them (`_finish_mask`). No sweep
    #   weights, no certificate;
    # * at or above it the mask gates the int8 sweep (`sweep_rowmax_bitset`
    #   skips chunks without a surviving bit), the device picks candidate
    #   rows, the host rescores every doc in them exactly and a certificate
    #   bounds what uncollected rows could hold, just like the disjunctive
    #   path; cold SHOULD terms ride a sparse gather. A failed certificate
    #   falls back to the mask, not to a host intersection.
    #
    # Both end in `_exact_bool`, as the host route does, so all three are
    # bit-identical. `_bool_host_exact` (sorted-array intersection on the
    # host) is what a faulted partition or chunk falls back to, what a
    # query the device cannot represent takes (slop != 0, no scoring
    # clause), and what ES_TPU_BITSET=0's dense coverage engine
    # (`sweep_rowmax_conj`) leaves cold required clauses to.

    def _resolve_bool(self, spec: dict,
                      use_bits: bool = True) -> Optional[_BoolQuery]:
        """Resolve one bool spec; None means provably zero matches.

        spec keys (all optional): "must"/"should" [(term, boost)],
        "filter"/"must_not" [term], "phrases" [(terms, slop, boost)].
        A phrase's `_PhraseInfo` is taken where it is cached; the scan of
        one that is not is `_scan_phrases`' (device candidates only)."""
        conj, should, filters, must_not, phrases = [], [], [], [], []
        for t, b in spec.get("must", ()):
            info = self._term(t)
            if info is None:
                return None
            conj.append((t, float(b), info))
        for t in spec.get("filter", ()):
            info = self._term(t)
            if info is None:
                return None
            filters.append((t, info))
        for t, b in spec.get("should", ()):
            info = self._term(t)
            if info is not None:
                should.append((t, float(b), info))
        req_names = {t for t, _, _ in conj} | {t for t, _ in filters}
        for t in spec.get("must_not", ()):
            if t in req_names:
                return None          # required AND prohibited
            info = self._term(t)
            if info is not None:
                must_not.append((t, info))
        phrase_specs = [(tuple(p[0]), int(p[1]), float(p[2]))
                        for p in spec.get("phrases", ())]
        req_infos = [i for _, _, i in conj] + [i for _, i in filters]
        dev = ((use_bits or all(i.df >= self.cold_df for i in req_infos))
               and all(s == 0 for _, s, _ in phrase_specs)
               and len(req_infos) + len(phrase_specs) <= _MAX_REQ
               and bool(any(b != 0.0 for _, b, _ in conj) or should
                        or any(b != 0.0 for _, _, b in phrase_specs)))
        for terms, slop, boost in phrase_specs:
            infos = [self._term(t) for t in terms]
            if any(i is None for i in infos):
                return None          # phrase term absent: no phrase match
            idf_sum = float(sum(i.idf for i in infos))
            pinfo = self._phrases.get(_pkey(terms)) if slop == 0 else None
            if pinfo is not None and not len(pinfo.docs):
                return None          # required phrase matches nothing
            phrases.append((terms, slop, boost, pinfo, idf_sum))
        return _BoolQuery(conj=conj, should=should, filters=filters,
                          must_not=must_not, phrases=phrases,
                          dev_candidate=dev)

    def _scan_phrases(self, resolved: List[Optional[_BoolQuery]]) -> None:
        """The positions scan of every slop-0 phrase a device candidate
        names for the first time (`_phrase`: cached after it); a query
        whose required phrase matches nothing resolves to None, in
        place. Host-routed queries verify positions docs_filter'd to the
        term intersection instead."""
        for qi, r in enumerate(resolved):
            if r is None or not r.dev_candidate:
                continue
            for j, (terms, slop, boost, pinfo, idf_sum) in enumerate(
                    r.phrases):
                if pinfo is None:
                    pinfo = self._phrase(terms)
                    r.phrases[j] = (terms, slop, boost, pinfo, idf_sum)
                if pinfo is None or not len(pinfo.docs):
                    resolved[qi] = None
                    break

    def _row_of(self, t: str, info: _TermInfo) -> Optional[int]:
        """The bitset row of a term clause NOW: its column's slot, or for
        a cold term its cold row; None = neither is resident."""
        if info.df >= self.cold_df:
            return self._slot_of.get(t)
        c = self._crow_of.get(t)
        return None if c is None else self.Hp + 2 + c

    def _bool_resident(self, r: _BoolQuery) -> bool:
        for t, info in r.required():
            if self._row_of(t, info) is None:
                return False
        for terms, _, _, pinfo, _ in r.phrases:
            if pinfo is None or pinfo.key not in self._slot_of:
                return False
        return True

    def _ensure_bool(self, resolved: List[Optional[_BoolQuery]],
                     use_bits: bool = True) -> None:
        """Everything the device route of a resolved batch reads, made
        resident (shared by search_bool and the fused multi-partition
        path; the three steps are `dispatch.prep`'s children beside
        `dispatch.bool_resolve`): term columns (and, by ensure_columns'
        sparse hook, the slices of cold SHOULD terms: `slice_build`);
        the phrases' scans and adjacency columns (`phrase_build`); the
        packed bitsets with the cold clauses' rows (`bitset_pack`)."""
        ens_terms: List[str] = []
        pkeys = set()
        cold_df = self.cold_df
        for r in resolved:
            if r is None or not r.dev_candidate:
                continue
            req = r.required()
            ens_terms += [t for t, i in req + r.must_not
                          if i.df >= cold_df]
            if all(i.df >= cold_df for _, i in req):
                # (a query with a cold required term is answered from
                # its mask: it scores nothing on the device)
                ens_terms += [t for t, _, _ in r.should]
            pkeys.update(_pkey(p[0]) for p in r.phrases)
        if ens_terms:
            self.ensure_columns(ens_terms, protect_extra=pkeys)
        with tracing.phase("dispatch.phrase_build"):
            self._scan_phrases(resolved)
            ens_phr = [p[3].terms for r in resolved
                       if r is not None and r.dev_candidate
                       for p in r.phrases if p[3] is not None]
            if ens_phr:
                self.ensure_phrases(ens_phr,
                                    protect_extra=set(ens_terms) | pkeys)
        if use_bits and any(r is not None and r.dev_candidate
                            for r in resolved):
            with tracing.phase("dispatch.bitset_pack"):
                self._ensure_cold_rows(resolved)
                self._ensure_bits()

    def _bool_routes(self, resolved: Sequence[Optional[_BoolQuery]]):
        """(device_idx, host_idx) routing AFTER `_ensure_bool` — device
        iff the query is a device candidate and every required clause's
        row is resident NOW; sets the device-routed queries' lead."""
        device_idx: List[int] = []
        host_idx: List[int] = []
        for qi, r in enumerate(resolved):
            if r is None:
                continue
            if r.dev_candidate and self._bool_resident(r):
                device_idx.append(qi)
                leads = ([(i.df, i.df < self.cold_df)
                          for _, i in r.required()]
                         + [(len(p[3].docs), False) for p in r.phrases])
                r.lead_df, r.cold_lead = min(leads, default=(1 << 60, False))
                r.by_mask = r.lead_df < self.cold_df
            else:
                host_idx.append(qi)
        return device_idx, host_idx

    def _count_routes(self, resolved, device_idx) -> None:
        self._count("bool_device", len(device_idx))
        self._count("bool_cold_lead",
                    sum(1 for qi in device_idx if resolved[qi].cold_lead))

    def _bool_slots(self, r: _BoolQuery):
        """(scoring [(slot, w, smax)], required slots, must_not slots)
        over columns resident NOW — the single source of what _sweep_bool
        quantizes, reused by _finish_bool so the certificate's e_q mirrors
        the dispatched weights exactly."""
        ws: Dict[int, float] = {}
        smax: Dict[int, float] = {}
        req = set()
        for t, b, info in r.conj:
            slot = self._slot_of.get(t)
            if slot is None:
                continue
            ws[slot] = ws.get(slot, 0.0) + info.idf * b
            smax[slot] = info.smax
            req.add(slot)
        for t, info in r.filters:
            slot = self._slot_of.get(t)
            if slot is not None:
                req.add(slot)
        for t, b, info in r.should:
            slot = self._slot_of.get(t)
            if slot is not None:
                ws[slot] = ws.get(slot, 0.0) + info.idf * b
                smax[slot] = info.smax
        for terms, _, boost, pinfo, idf_sum in r.phrases:
            if pinfo is None:
                continue
            slot = self._slot_of.get(pinfo.key)
            if slot is not None:
                ws[slot] = ws.get(slot, 0.0) + idf_sum * boost
                smax[slot] = pinfo.smax
                req.add(slot)
        mn = set()
        for t, info in r.must_not:
            slot = self._slot_of.get(t)
            if slot is not None and slot not in req:
                mn.add(slot)
        scoring = [(s, w, smax[s]) for s, w in ws.items() if w != 0.0]
        return scoring, req, mn

    def _bool_weights(self, chunk, QC: int):
        """Quantized conjunctive sweep inputs for one dispatch chunk:
        (wq [2, QC, Hp+1] i8, wp [QC, Hp+1] i8, nreq [QC, 1] i32,
        qscale [QC, 1] f32). A None entry (a query this partition routes
        to host while a fused peer dispatches it) leaves all-zero rows:
        nreq 0 keeps the coverage test vacuous and zero weights score 0
        (-inf after the positivity mask), so the row never surfaces. So
        does a query answered from its mask (`by_mask`)."""
        wq = np.zeros((2, QC, self.Hp + 1), np.int8)
        wp = np.zeros((QC, self.Hp + 1), np.int8)
        nreq = np.zeros((QC, 1), np.int32)
        qscale = np.ones((QC, 1), np.float32)
        for qi, r in enumerate(chunk):
            if r is None or r.by_mask:
                continue
            scoring, req, mn = self._bool_slots(r)
            nreq[qi, 0] = len(req)
            for s in req:
                wp[qi, s] = 1
            for s in mn:
                # one prohibited presence pushes the coverage sum below 0,
                # unreachable by any subset of +1 weights (n_req <= 126
                # keeps this in int8)
                wp[qi, s] = np.int8(-(len(req) + 1))
            steps, qscale[qi, 0], _ = _quantize([w for _, w, _ in scoring])
            for (slot, _, _), (wh, wl) in zip(scoring, steps):
                wq[0, qi, slot] = np.int8(wh)
                wq[1, qi, slot] = np.int8(wl)
        return wq, wp, nreq, qscale

    def _sweep_bool(self, chunk: Sequence[_BoolQuery], QC: int):
        wq, wp, nreq, qscale = self._bool_weights(chunk, QC)
        with faults.device_dispatch("turbo_sweep", self.part_id):
            return sweep_rowmax_conj(
                jnp.asarray(qscale), jnp.asarray(nreq), self.cols_hi,
                self.cols_lo, jnp.asarray(wq), jnp.asarray(wp), self.live,
                QC=QC, nsw=self.nsw)

    # ---------------- packed-bitset engine (ES_TPU_BITSET) ----------------

    def _doc_bits(self, docs: np.ndarray) -> np.ndarray:
        """A doc set as one bitset row [wgr * 128] u32, in the layout
        `kernels.pack_presence_bits` gives a column's: bit j of word
        [g, l] = doc (32 g + j) * 128 + l."""
        d = np.asarray(docs, np.int64)
        word = (d >> 12) * 128 + (d & 127)
        bit = np.left_shift(1, (d >> 7) & 31).astype(np.float64)
        # (distinct docs: a word's bits add up below 2^32, exact in f64)
        return np.bincount(word, weights=bit,
                           minlength=self._wgr * 128).astype(np.uint32)

    def _mask_docs(self, words: np.ndarray) -> np.ndarray:
        """The docs (i64, ascending) of a bitset row [wgr * 128] u32."""
        w = np.flatnonzero(words)
        bits = np.unpackbits(words[w].view(np.uint8).reshape(-1, 4),
                             axis=1, bitorder="little")
        wi, j = np.nonzero(bits)
        g, lane = np.divmod(w[wi], 128)
        return np.sort((g * 32 + j) * 128 + lane)

    def _repack_bits(self) -> None:
        """Derive the per-slot match-set bitsets from the column cache
        (presence is exact there — kernels._build_kernel forces lo >= 1),
        into the first Hp + 2 rows of `bits`; the cold rows behind them
        are written from their host copy (`_sync_cold_rows`).
        device_errors only, no fault_point: callers inject through
        _ensure_bits; scrub repairs must not be separately injectable."""
        with faults.device_errors("bitset_intersect", self.part_id):
            if self.bits is None:
                self.bits = jnp.zeros(
                    (self.Hp + 2 + BITSET_COLD_ROWS, self._wgr, 128),
                    jnp.uint32)
                self.live_bits = jnp.asarray(self._doc_bits(
                    np.flatnonzero(self._live_host > 0)
                ).reshape(self._wgr, 128))
                for n in _ROW_UP_BUCKETS:     # (zero rows over zero rows)
                    self.bits = bitset_write_rows(
                        self.bits,
                        jnp.full((n,), self.Hp + 2, jnp.int32),
                        jnp.zeros((n, self._wgr, 128), jnp.uint32))
            self.bits = bitset_repack(self.bits, self.cols_hi, self.cols_lo)
        self._bits_epoch = self.cols_epoch
        self.bits_version += 1
        self._count("bitset_packs")
        _node_bitset_add("bitset_bytes",
                         self._bits_nbytes() - self.stats["bitset_bytes"])
        self.stats["bitset_bytes"] = self._bits_nbytes()
        self._register_hbm_regions()

    def _reset_bits(self) -> None:
        """Scrub repair: re-pack from the (separately scrubbed) column
        cache and rewrite every cold row from its host copy — host
        postings remain the source of truth two hops up, so a repaired
        bitset region serves bit-identical results."""
        self._repack_bits()
        self._crow_dirty.update(self._crow_of.values())
        self._sync_cold_rows()

    def _ensure_cold_rows(self, resolved) -> None:
        """A cold row for every COLD required / prohibited term of the
        batch's device candidates: host copy only (`_sync_cold_rows`
        writes the device rows). LRU over the rows; a batch that names
        more cold clauses than there are rows leaves the rest without
        one (a required clause without a row host-routes its query; a
        prohibited one leaves the mask a superset)."""
        need: Dict[str, _TermInfo] = {}
        for r in resolved:
            if r is None or not r.dev_candidate:
                continue
            for t, i in r.required() + r.must_not:
                if i.df < self.cold_df:
                    need[t] = i
        if not need:
            return
        self._tick += 1
        if self._crow_host is None:
            self._crow_host = np.zeros(
                (BITSET_COLD_ROWS, self._wgr * 128), np.uint32)
        fp = self.fp
        for t, info in need.items():
            if t in self._crow_of:
                self._crow_lru[t] = self._tick
                continue
            if self._crow_free:
                row = self._crow_free.pop()
            else:
                victim = next((v for v in sorted(self._crow_lru,
                                                 key=self._crow_lru.get)
                               if v not in need), None)
                if victim is None:
                    self.stats["degraded"] += 1
                    continue
                row = self._crow_of.pop(victim)
                del self._crow_lru[victim]
            lo = int(fp.post_start[info.ord])
            self._crow_host[row] = self._doc_bits(
                fp.post_doc[lo: lo + info.df])
            self._crow_dirty.add(row)
            self._crow_of[t] = row
            self._crow_lru[t] = self._tick

    def _sync_cold_rows(self) -> None:
        """Write the cold rows whose host copy moved into `bits`."""
        if not self._crow_dirty:
            return
        dirty = sorted(self._crow_dirty)
        n = next(b for b in _ROW_UP_BUCKETS if b >= len(dirty))
        idx = np.asarray(dirty + dirty[:1] * (n - len(dirty)))
        with faults.device_errors("bitset_intersect", self.part_id):
            self.bits = bitset_write_rows(
                self.bits, jnp.asarray((self.Hp + 2 + idx).astype(np.int32)),
                jnp.asarray(self._crow_host[idx].reshape(n, self._wgr, 128)))
        self._crow_dirty.clear()
        self.bits_version += 1

    def _ensure_bits(self) -> None:
        """Pack (or re-pack after a cols_epoch move) the bitsets and
        write the cold rows that moved, before a bitset-engine dispatch;
        registers the scrub region on first build so the PR-15 integrity
        plane fingerprints the new columns."""
        if self.bits is not None and self._bits_epoch == self.cols_epoch:
            self._sync_cold_rows()
            return
        faults.fault_point("bitset_intersect", self.part_id)
        first = self.bits is None
        self._repack_bits()
        self._sync_cold_rows()
        if first:
            integrity.register_scrub_region(
                self, "cols_bits", lambda o: o.bits,
                epoch=lambda o: id(o.bits),
                repair=lambda o: o._reset_bits())

    def _bitset_slots(self, r: _BoolQuery):
        """(required rows rarest-df-first, must_not rows largest-first,
        exact) for the intersect kernel's prefetch rows. Clauses beyond
        the BITSET_CLAUSES / BITSET_NEGS fan-in, and clauses without a
        resident row, are dropped from the MASK only — dropping an AND
        (or an AND-NOT) term leaves the mask a SUPERSET of the true match
        set, and the exact host rescore re-tests every clause, so top-k
        stays bit-identical (the cost is spurious candidates, never missed
        ones). `exact` = nothing was dropped: the mask IS the match set,
        and its population count the hit count."""
        req: Dict[int, int] = {}
        exact = True
        for t, info in r.required():
            row = self._row_of(t, info)
            if row is None:
                exact = False
            else:
                req[row] = min(req.get(row, 1 << 60), info.df)
        for terms, _, _, pinfo, _ in r.phrases:
            slot = None if pinfo is None else self._slot_of.get(pinfo.key)
            if slot is None:
                exact = False
            else:
                req[slot] = min(req.get(slot, 1 << 60), len(pinfo.docs))
        ordered = sorted(req, key=lambda s: (req[s], s))
        mn = []
        for t, info in r.must_not:
            row = self._row_of(t, info)
            if row is None:
                exact = False
            elif row not in req:
                mn.append((info.df, row))
        exact = (exact and 0 < len(ordered) <= BITSET_CLAUSES
                 and len(mn) <= BITSET_NEGS)
        mn = [s for _, s in sorted(mn, reverse=True)[:BITSET_NEGS]]
        return ordered[:BITSET_CLAUSES], mn, exact

    def _bitset_prefetch(self, chunk, QC: int):
        """(q_slots [QC, BITSET_CLAUSES], q_neg [QC, BITSET_NEGS]) i32 —
        the intersect kernel's scalar-prefetch rows — and exact [QC]
        bool (`_bitset_slots`). Sentinels: slot Hp (the build scratch
        slot, always zero) is the AND-NOT identity and the empty mask;
        slot Hp + 1 is the packed all-ones row. A None entry (a query a
        fused peer host-routes) points EVERY clause at the zero sentinel
        so its mask is empty and its chunks all skip; an active query
        with no resident required clause pads with the ones sentinel
        (every live doc passes, as with nreq=0)."""
        zero_s, ones_s = self.Hp, self.Hp + 1
        q_slots = np.full((QC, BITSET_CLAUSES), zero_s, np.int32)
        q_neg = np.full((QC, BITSET_NEGS), zero_s, np.int32)
        exact = np.zeros(QC, bool)
        for qi, r in enumerate(chunk):
            if r is None:
                continue
            req, mn, exact[qi] = self._bitset_slots(r)
            if not req:
                q_slots[qi, :] = ones_s
            else:
                for j in range(BITSET_CLAUSES):
                    q_slots[qi, j] = req[j] if j < len(req) else req[0]
            q_neg[qi, : len(mn)] = mn
        return q_slots, q_neg, exact

    def _sweep_bool_bits(self, chunk: Sequence[_BoolQuery], QC: int):
        """Bitset-engine twin of _sweep_bool: blockwise AND / AND-NOT of
        the clauses' packed match sets on device, then the mask-gated
        sweep that skips all-zero chunks. Returns (rm, rr, mask, (nonzero
        chunks, live matches) [2, QC] i32, exact [QC] bool)."""
        wq, _, _, qscale = self._bool_weights(chunk, QC)
        q_slots, q_neg, exact = self._bitset_prefetch(chunk, QC)
        with faults.device_dispatch("bitset_intersect", self.part_id):
            mask = intersect_bitset(
                jnp.asarray(q_slots), jnp.asarray(q_neg), self.bits,
                QC=QC, nsw=self.nsw)
            counts = jnp.stack([mask_chunk_counts(mask),
                                mask_live_counts(mask, self.live_bits)])
        with faults.device_dispatch("turbo_sweep", self.part_id):
            rm, rr = sweep_rowmax_bitset(
                jnp.asarray(qscale), self.cols_hi, self.cols_lo,
                jnp.asarray(wq), mask, self.live, QC=QC, nsw=self.nsw)
        return rm, rr, mask, counts, exact

    def _gallop_routes(self, resolved, device_idx, host_idx):
        """ES_TPU_BITSET_HOST_DF (default 0 = off: a rare lead is a cold
        lead, answered from its mask on the device): when a query's
        rarest required clause has df below the knob, the galloping
        sorted intersection (_intersect_sorted) answers it on the host."""
        thr = int(knob("ES_TPU_BITSET_HOST_DF") or 0)
        if thr <= 0:
            return device_idx, host_idx
        keep: List[int] = []
        moved: List[int] = []
        for qi in device_idx:
            (moved if resolved[qi].lead_df < thr else keep).append(qi)
        self._count("bitset_gallop", len(moved))
        return keep, sorted(host_idx + moved)

    def _note_bitset_counts(self, cnt, total: Optional[int] = None) -> None:
        """Fold one dispatch's nonzero-chunk tallies into the skip
        counters + histograms (`_nodes/stats` tpu_turbo surfaces the
        stats keys; metrics feed the flight recorder)."""
        if total is None:
            total = self.nsw * N_CHUNKS
        for c in cnt:
            skipped = max(total - int(c), 0)
            self.stats["bitset_blocks_skipped"] += skipped
            _node_bitset_add("bitset_blocks_skipped", skipped)
            metrics.observe("bitset_blocks_skipped", skipped)
            metrics.observe("bitset_block_occupancy",
                            int(c) / max(total, 1))

    def _phrase_pf(self, terms, slop, pinfo, docs: np.ndarray):
        """(pf f32[n], present bool[n]) of a phrase at candidate docs."""
        if pinfo is not None:
            pdocs, ppf = pinfo.docs, pinfo.pf
        else:
            flt = np.unique(np.asarray(docs, np.int64)).astype(np.int32)
            pdocs, ppf = phrase_freqs(self.fp, list(terms), slop=slop,
                                      docs_filter=flt)
        pf = np.zeros(len(docs), np.float32)
        if len(pdocs):
            d = docs.astype(pdocs.dtype, copy=False) \
                if docs.dtype != pdocs.dtype else docs
            j = np.searchsorted(pdocs, d)
            jc = np.minimum(j, len(pdocs) - 1)
            hit = (j < len(pdocs)) & (pdocs[jc] == d)
            pf[hit] = ppf[jc[hit]]
        return pf, pf > 0

    def _exact_bool(self, r: _BoolQuery, docs: np.ndarray):
        """(scores f32[n], match bool[n]) at docs — expression-for-
        expression the serving conjunctive reference
        (search/serving._conjunctive_partition: f64 accumulation, clause
        order conj -> should -> phrases, one f32 downcast at the end), so
        Turbo's bool path is bit-identical to the REST host columnar
        path. Clause lists are iterated in ORIGINAL spec order."""
        fp = self.fp
        n = len(docs)
        match = np.ones(n, bool)
        dl = fp.doc_len[docs]
        norm = _K1 * (1.0 - _B + _B * dl / max(self._avgdl, 1e-9))
        scores = np.zeros(n, np.float64)
        for t, w, info in r.conj:
            tf, present = tf_at(fp, t, docs)
            match &= present
            scores += w * info.idf * tf * (_K1 + 1.0) / (tf + norm)
        for t, _ in r.filters:
            _, present = tf_at(fp, t, docs)
            match &= present
        for t, w, info in r.should:
            tf, present = tf_at(fp, t, docs)
            contrib = (w * info.idf * tf * (_K1 + 1.0)
                       / np.maximum(tf + norm, 1e-9))
            scores += np.where(present, contrib, 0.0)
        for terms, slop, boost, pinfo, idf_sum in r.phrases:
            pf, present = self._phrase_pf(terms, slop, pinfo, docs)
            match &= present
            if boost == 0.0:
                continue
            scores += boost * idf_sum * pf * (_K1 + 1.0) / (pf + norm)
        for t, _ in r.must_not:
            _, present = tf_at(fp, t, docs)
            match &= ~present
        return self._exact_scores(scores, docs), match

    def _rank_exact(self, r: _BoolQuery, cand: np.ndarray, k: int):
        """Exact top-k among candidate docs that hold every match (live
        or not): the shared tail of the host route and the mask finish."""
        empty = (np.empty(0, np.float32), np.empty(0, np.int32))
        cand = cand[self._live_host[cand] > 0]
        if not len(cand):
            return empty
        with tracing.phase("dispatch.rescore", docs=len(cand)):
            s, m = self._exact_bool(r, cand)
        keep = m & (s > 0)
        cand, s = cand[keep], s[keep]
        sel = np.lexsort((cand, -s))[:k]
        return s[sel], cand[sel].astype(np.int32)

    def _bool_host_exact(self, r: _BoolQuery, k: int):
        """Exact host bool top-k: sorted-array intersection of the
        required clauses, then the shared exact rescore. Complete without
        any certificate — every match lies inside the rarest required
        clause's postings. Serves host-routed queries and what a faulted
        partition or chunk leaves behind."""
        self._count("bool_host")
        fp = self.fp
        empty = (np.empty(0, np.float32), np.empty(0, np.int32))
        req: List[np.ndarray] = []
        for _, _, info in r.conj:
            lo, hi = (int(fp.post_start[info.ord]),
                      int(fp.post_start[info.ord + 1]))
            req.append(fp.post_doc[lo:hi])
        for _, info in r.filters:
            lo, hi = (int(fp.post_start[info.ord]),
                      int(fp.post_start[info.ord + 1]))
            req.append(fp.post_doc[lo:hi])
        for _, _, _, pinfo, _ in r.phrases:
            if pinfo is not None:
                req.append(pinfo.docs)
        cand: Optional[np.ndarray] = None
        if req:
            req.sort(key=len)
            cand = req[0]
            for s in req[1:]:
                cand = _intersect_sorted(cand, s)
                if not len(cand):
                    return empty
        for terms, slop, _, pinfo, _ in r.phrases:
            if pinfo is not None:
                continue
            cand, _ = phrase_freqs(fp, list(terms), slop=slop,
                                   docs_filter=cand)
            if not len(cand):
                return empty
        if cand is None:
            # no required clauses: candidates are the should-term union
            arrs = []
            for _, _, info in r.should:
                lo, hi = (int(fp.post_start[info.ord]),
                          int(fp.post_start[info.ord + 1]))
                arrs.append(fp.post_doc[lo:hi])
            if not arrs:
                return empty
            cand = np.unique(np.concatenate(arrs))
        return self._rank_exact(r, cand, k)

    def _fetch_mask(self, mask_dev) -> np.ndarray:
        with faults.device_errors("bitset_intersect", self.part_id):
            return np.asarray(mask_dev)

    def _finish_mask(self, r: _BoolQuery, words: np.ndarray, k: int):
        """The mask finish: the query's intersected match set as the
        device computed it (its mask row [wgr * 128] u32, fetched) IS the
        candidate set — a superset where `_bitset_slots` dropped a
        clause, which `_exact_bool` re-tests. Complete, so no
        certificate."""
        return self._rank_exact(r, self._mask_docs(words), k)

    def _finish_bool(self, r: _BoolQuery, cand_docs, bound: float, k: int,
                     words=None):
        """The sweep route's merge: exact rescore of collected docs +
        cold-SHOULD enumeration + certificate, a query at a time (the
        disjunctive finish is `_finish_chunk`). `words`: a callable that
        fetches the query's mask row; a failed certificate finishes from
        it (`_finish_mask`), on the dense coverage engine (None) from the
        host intersection."""
        scoring, req, mn = self._bool_slots(r)
        e_q = _quantize([w for _, w, _ in scoring])[2]

        cand_s = np.empty(0, np.float32)
        if len(cand_docs):
            cand_docs = np.asarray(cand_docs, np.int64)
            with tracing.phase("dispatch.rescore", docs=len(cand_docs)):
                s, m = self._exact_bool(r, cand_docs)
            keep = m & (s > 0)
            cand_docs, cand_s = cand_docs[keep], s[keep]
        else:
            cand_docs = np.empty(0, np.int64)

        # cold SHOULD terms: a match the sweep scored without them (or,
        # when every scoring clause is cold, never surfaced at all) gets
        # its exact total here; bound-pruned like the disjunctive path
        cold_should = [(t, b, i) for t, b, i in r.should
                       if t not in self._slot_of]
        cold_docs = np.empty(0, np.int64)
        cold_s = np.empty(0, np.float32)
        if cold_should:
            if self._sparse_on():
                self.stats["sparse_queries"] += 1
                _node_sparse_add("sparse_queries", 1)
                # the same plan / launch / collect, back to back
                h = self._start_gathers([(0, cold_should)], False)[0]
                # (read through the old shape, distinct docs, and today's
                # bound: a match must ALSO hold the required clauses, so a
                # cold contribution is no lower bound on a hit's score
                # here and `_cold_survivors`' threshold does not apply)
                with tracing.phase("dispatch.sparse_gather",
                                   terms=len(cold_should)):
                    docs_r, vals_r, slack = self._collect_gather(h)
                    # every occurrence of a doc holds the same value: the
                    # first one stands for it, `_cold_contrib`'s shape
                    docs_c, first = np.unique(docs_r.astype(np.int64),
                                              return_index=True)
                    contrib = vals_r[first]
            else:
                self.stats["cold_queries"] += 1
                docs_c, contrib, _ = self._cold_contrib(cold_should)
                slack = 0.0
            lv = self._live_host[docs_c] > 0
            docs_c, contrib = docs_c[lv], contrib[lv]
            kth_0 = 0.0
            if len(cand_s) >= k:
                kth_0 = float(np.partition(cand_s, len(cand_s) - k)[
                    len(cand_s) - k])
            col_const = sum(abs(w) * sm for _, w, sm in scoring)
            # slack widens the bound for sparse quantization: superset of
            # the host path's survivors, extras exact-rescored below
            survivors = docs_c[contrib + slack + col_const + 1e-5 >= kth_0]
            if len(survivors):
                with tracing.phase("dispatch.rescore",
                                   docs=len(survivors)):
                    s, m = self._exact_bool(r, survivors)
                keep = m & (s > 0)
                cold_docs, cold_s = survivors[keep], s[keep]

        docs = np.concatenate([cand_docs, cold_docs])
        totals = np.concatenate([cand_s, cold_s])
        if len(docs):
            docs, first = np.unique(docs, return_index=True)
            totals = totals[first]
        sel = np.lexsort((docs, -totals))[:k]
        out_s, out_d = totals[sel], docs[sel].astype(np.int32)

        # certificate: collected docs are exact; a doc hidden in an
        # uncollected row passed the same (exact) coverage mask, so its
        # true colized score is bounded by the row bound + e_q, and any
        # cold-should addend it has was enumerated above
        uncollected = float(bound)
        limit = uncollected + e_q
        kth = float(out_s[k - 1]) if len(out_s) >= k else 0.0
        short = len(out_s) < k and uncollected > 0
        if (short
                or (len(out_s) >= k and kth < limit and uncollected > 0)
                or self.force_cert_fail):
            self.stats["fallbacks"] += 1
            if words is not None:
                return self._finish_mask(r, words(), k)
            return self._bool_host_exact(r, k)
        return out_s, out_d

    def _finish_bool_chunk(self, resolved, sel, act, packed, masks,
                           counts, exact, k: int, n_rows: int,
                           out_s, out_d, totals) -> None:
        """Pass 2 of one device chunk on this partition: `sel` the
        chunk's query indices, `act` which of them this partition
        dispatched (None = all), `packed` [QC, n_rows + 1] the picked
        rows, `masks` a callable that fetches this partition's masks of
        the chunk ([QC, >= wgr, 128] u32; None on the dense coverage
        engine), called only if a query finishes from its mask; `counts`
        [2, QC] and `exact` [QC] of `_sweep_bool_bits`."""
        def row(j):
            return masks()[j, : self._wgr].reshape(-1)

        rows_all = packed[:, :n_rows].astype(np.int64)
        bounds = packed[:, n_rows]
        for j, qi in enumerate(sel):
            if act is not None and qi not in act:
                continue
            r = resolved[qi]
            if r.by_mask:
                s, d = self._finish_mask(r, row(j), k)
            else:
                s, d = self._finish_bool(
                    r, self._collect_docs(rows_all[j]), float(bounds[j]), k,
                    words=None if masks is None else (lambda j=j: row(j)))
            out_s[qi, : len(s)] = s
            out_d[qi, : len(d)] = d
            if totals is not None:
                totals[qi] += (int(counts[1, j])
                               if counts is not None and exact[j]
                               else _NO_TOTAL)

    def search_bool(self, queries: Sequence[dict], k: int = 10,
                    check=None, totals=None):
        """(scores [Q, k] f32, ords [Q, k] i32) for bool query specs (see
        _resolve_bool for the spec shape). Matches with non-positive
        scores are dropped (the BlockMax search_bool contract). Device
        and host routes return bit-identical results — both rescore
        through _exact_bool. `totals` (optional, i64 [Q]): this
        partition ADDS each query's exact live match count as the device
        counted it, `_NO_TOTAL` where it did not."""
        with tracing.steps(DISPATCH_STEPS):
            return self._search_bool(queries, k, check, totals)

    def _search_bool(self, queries, k, check, totals=None):
        Q = len(queries)
        out_s = np.zeros((Q, k), np.float32)
        out_d = np.zeros((Q, k), np.int32)
        use_bits = bool(knob("ES_TPU_BITSET"))
        with tracing.phase("dispatch.prep", queries=Q):
            with tracing.phase("dispatch.bool_resolve", queries=Q):
                resolved = [self._resolve_bool(spec, use_bits)
                            for spec in queries]
            self._ensure_bool(resolved, use_bits)
            device_idx, host_idx = self._bool_routes(resolved)
            if use_bits:
                device_idx, host_idx = self._gallop_routes(
                    resolved, device_idx, host_idx)
        self._count_routes(resolved, device_idx)

        # device pipeline (same two-pass shape as search_many)
        n_rows = max(_GLOBAL_ROWS, k + 5)
        pending = []
        off = 0
        while off < len(device_idx):
            rem = len(device_idx) - off
            take = next((s for s in self.qc_sizes if s >= rem),
                        self.qc_sizes[-1])
            sel = device_idx[off: off + take]
            if check is not None:
                check()
            mask = counts = exact = None
            with tracing.phase("dispatch.launch", qc=take):
                if use_bits:
                    first_trace = hbm_ledger.note_dispatch(
                        "turbo_bitset", take)
                    tc0 = time.monotonic()
                    rm, rr, mask, counts, exact = self._sweep_bool_bits(
                        [resolved[i] for i in sel], take)
                else:
                    rm, rr = self._sweep_bool([resolved[i] for i in sel],
                                              take)
                with faults.device_errors("turbo_sweep", self.part_id):
                    picked = _pick_rows(rm, rr, n_rows=n_rows)
            if use_bits and first_trace:
                hbm_ledger.note_compile_done(
                    "turbo_bitset", take, time.monotonic() - tc0)
            pending.append((sel, picked, mask, counts, exact))
            off += len(sel)
        self.stats["dispatches"] += len(pending)

        for sel, packed_dev, mask, counts, exact in pending:
            if check is not None:
                check()
            with tracing.phase("dispatch.device_wait"):
                with faults.device_errors("turbo_sweep", self.part_id):
                    packed = np.asarray(packed_dev)
                if counts is not None:
                    with faults.device_errors("bitset_intersect",
                                              self.part_id):
                        counts = np.asarray(counts)
                        self._note_bitset_counts(counts[0, : len(sel)])
            with tracing.phase("dispatch.finish", queries=len(sel)):
                self._finish_bool_chunk(
                    resolved, sel, None, packed,
                    None if mask is None else _once(
                        lambda m=mask: self._fetch_mask(m)),
                    counts, exact, k, n_rows, out_s, out_d, totals)
        with tracing.phase("dispatch.finish", queries=len(host_idx)):
            for qi in host_idx:
                if check is not None:
                    check()
                s, d = self._bool_host_exact(resolved[qi], k)
                out_s[qi, : len(s)] = s
                out_d[qi, : len(d)] = d
                if totals is not None:
                    totals[qi] += _NO_TOTAL
        return out_s, out_d

    def search_phrase(self, phrases: Sequence[Sequence[str]], k: int = 10,
                      slop: int = 0, check=None):
        """(scores [Q, k], ords [Q, k]) for bare phrase queries — sugar
        over search_bool; slop-0 phrases ride the adjacency columns."""
        specs = [{"phrases": [(list(p), slop, 1.0)]} for p in phrases]
        return self.search_bool(specs, k=k, check=check)

    # ---------------- host fallback tier (zero device dispatches) ----------

    def _exact_query(self, terms, k: int):
        """Exact host top-k for one flat [(term, boost)] query — the
        containment fallback when this partition's device path faulted.
        Bit-identical to the certificate-passing device route (both end in
        _exact_scores over the same candidate set ordering)."""
        qterms = []
        for t, b in terms:
            info = self._term(t)
            if info is not None:
                qterms.append((t, b, info))
        if not qterms:
            return np.empty(0, np.float32), np.empty(0, np.int32)
        return self._exact_merge(qterms, k)

    def search_many_host(self, batches: Sequence[List], k: int = 10,
                         check=None):
        """search_many semantics served entirely on host — the
        circuit-open fallback tier (BM25S-style exact merge; no device
        dispatch, no column cache mutation)."""
        flat, spans = _flatten_queries(batches)
        out_s = np.zeros((len(flat), k), np.float32)
        out_d = np.zeros((len(flat), k), np.int32)
        for qi, terms in enumerate(flat):
            if check is not None:
                check()
            s, d = self._exact_query(terms, k)
            out_s[qi, : len(s)] = s
            out_d[qi, : len(d)] = d
        return [(out_s[o: o + n], out_d[o: o + n]) for o, n in spans]

    def search_bool_host(self, queries: Sequence[dict], k: int = 10,
                         check=None):
        """search_bool semantics served entirely on host (the
        _bool_host_exact route every device bool result is already
        bit-identical to)."""
        Q = len(queries)
        out_s = np.zeros((Q, k), np.float32)
        out_d = np.zeros((Q, k), np.int32)
        for qi, spec in enumerate(queries):
            if check is not None:
                check()
            r = self._resolve_bool(spec)
            if r is None:
                continue
            s, d = self._bool_host_exact(r, k)
            out_s[qi, : len(s)] = s
            out_d[qi, : len(d)] = d
        return out_s, out_d


# --------------------------------------------------------------------------
# fused multi-partition dispatch (ICI-sharded S > 1)
# --------------------------------------------------------------------------


@_partial(jax.jit, static_argnames=("mesh", "QC", "nsw", "n_rows"))
def _fused_sweep_disj(qscale, cols_hi, cols_lo, wq, live, *,
                      mesh, QC: int, nsw: int, n_rows: int):
    """ONE launch, every partition: disjunctive sweep + row pick over the
    partition-sharded fused column cache. All inputs carry the partition
    axis on dim 0, sharded P('shard'):

    qscale [Sp, QC, 1] f32 · cols_hi/lo [Sp, dpc, Hpt, 16, 128] i8 ·
    wq [Sp, 2, QC, Hpt] i8 · live [Sp, dp_rows, 128] f32

    Returns [Sp, QC, n_rows + 1] f32 — per partition, exactly the
    _pick_rows packing a solo dispatch would produce (padding partitions
    and padded superwindows are dead: live 0 ⇒ -inf ⇒ rows -1, bound 0).
    """
    spec = _P("shard")

    @_partial(_shard_map, mesh=mesh, in_specs=(spec,) * 5,
              out_specs=spec, check_vma=False)
    def program(qs, ch, cl, w, lv):
        outs = []
        for i in range(qs.shape[0]):    # static local-partition loop
            rm, rr = sweep_rowmax(qs[i], ch[i], cl[i], w[i], lv[i],
                                  QC=QC, nsw=nsw)
            outs.append(_pick_rows(rm, rr, n_rows=n_rows))
        return jnp.stack(outs)

    return program(qscale, cols_hi, cols_lo, wq, live)


@_partial(jax.jit, static_argnames=("mesh", "QC", "nsw", "n_rows"))
def _fused_sweep_bool(qscale, nreq, cols_hi, cols_lo, wq, wp, live, *,
                      mesh, QC: int, nsw: int, n_rows: int):
    """Conjunctive twin of _fused_sweep_disj (adds the coverage inputs
    nreq [Sp, QC, 1] i32 and wp [Sp, QC, Hpt] i8)."""
    spec = _P("shard")

    @_partial(_shard_map, mesh=mesh, in_specs=(spec,) * 7,
              out_specs=spec, check_vma=False)
    def program(qs, nr, ch, cl, w, p, lv):
        outs = []
        for i in range(qs.shape[0]):
            rm, rr = sweep_rowmax_conj(qs[i], nr[i], ch[i], cl[i], w[i],
                                       p[i], lv[i], QC=QC, nsw=nsw)
            outs.append(_pick_rows(rm, rr, n_rows=n_rows))
        return jnp.stack(outs)

    return program(qscale, nreq, cols_hi, cols_lo, wq, wp, live)


@_partial(jax.jit, static_argnames=("mesh", "QC", "nsw", "n_rows"))
def _fused_sweep_bitset(qscale, q_slots, q_neg, bits, live_bits, cols_hi,
                        cols_lo, wq, live, *, mesh, QC: int, nsw: int,
                        n_rows: int):
    """Bitset twin of _fused_sweep_bool: per local partition, the packed
    clause intersection (intersect_bitset) feeds the mask-gated sweep —
    still ONE launch for every partition. Extra sharded inputs:
    q_slots [Sp, QC, BITSET_CLAUSES] i32 · q_neg [Sp, QC, BITSET_NEGS]
    i32 · bits [Sp, Hp+2+cold rows, nsw * SW_WORD_ROWS, 128] u32 ·
    live_bits [Sp, nsw * SW_WORD_ROWS, 128] u32. Returns (picked
    [Sp, QC, n_rows+1] f32, (nonzero chunks, live matches) [Sp, 2, QC]
    i32, the masks [Sp, QC, nsw * SW_WORD_ROWS, 128] u32, which stay on
    the device unless a query finishes from its mask)."""
    spec = _P("shard")

    @_partial(_shard_map, mesh=mesh, in_specs=(spec,) * 9,
              out_specs=(spec, spec, spec), check_vma=False)
    def program(qs, sl, ng, bt, lb, ch, cl, w, lv):
        outs, cnts, masks = [], [], []
        for i in range(qs.shape[0]):
            mask = intersect_bitset(sl[i], ng[i], bt[i], QC=QC, nsw=nsw)
            rm, rr = sweep_rowmax_bitset(qs[i], ch[i], cl[i], w[i], mask,
                                         lv[i], QC=QC, nsw=nsw)
            outs.append(_pick_rows(rm, rr, n_rows=n_rows))
            cnts.append(jnp.stack([mask_chunk_counts(mask),
                                   mask_live_counts(mask, lb[i])]))
            masks.append(mask)
        return jnp.stack(outs), jnp.stack(cnts), jnp.stack(masks)

    return program(qscale, q_slots, q_neg, bits, live_bits, cols_hi,
                   cols_lo, wq, live)


@_partial(jax.jit, static_argnames=("i",), donate_argnums=(0,))
def _set_part(stacked, part, *, i: int):
    """stacked[i, :part.shape[0], :part.shape[1], ...] = part, in the
    (donated) stacked array's own buffer: a partition's slice of the fused
    cache re-synced without a second copy of the cache in HBM."""
    return jax.lax.dynamic_update_slice(
        stacked, part[None], (i,) + (0,) * part.ndim)


class ShardedTurbo:
    """S > 1 TurboBM25 partitions fused into ONE device dispatch per
    query chunk (the paper's ICI-sharded serving design): each
    partition's int8 column cache is padded to shared (nsw, Hp) maxima,
    stacked on dim 0 and placed across the mesh's 'shard' axis — the
    spmd._put_sharded placement discipline — so the per-partition sweep
    and row pick run data-parallel over ICI instead of S sequential
    launches. Padding is provably inert: dead superwindows/partitions
    have live == 0 and zero columns, so every padded score is -inf and
    every real (query, partition) output is bit-identical to a solo
    dispatch (the kernels compute query columns independently).

    The exact host rescore + certificate (and any host-exact fallback)
    still run per partition on host — this class returns per-partition
    (scores, ords) shaped exactly like `[t.search_*(..) for t in turbos]`
    so serving.TurboEngine can merge either on host (_merge3) or on
    device (spmd.merge_partition_topk)."""

    def __init__(self, turbos: Sequence[TurboBM25], mesh):
        assert len(turbos) > 1, "fusion needs S > 1 partitions"
        assert mesh.shape.get("dp", 1) == 1, \
            "fused turbo shards partitions over 'shard' only"
        self.turbos = list(turbos)
        self.mesh = mesh
        G = mesh.shape["shard"]
        S = len(turbos)
        self.Sp = -(-S // G) * G          # padded partition count
        self.nsw = max(t.nsw for t in turbos)
        self.Hp = max(t.Hp for t in turbos)
        self.qc_sizes = turbos[0].qc_sizes
        dp_rows = self.nsw * (SW // 128)
        dpc = dp_rows // 16
        sh = NamedSharding(mesh, _P("shard"))
        lv = np.zeros((self.Sp, dp_rows, 128), np.float32)
        for i, t in enumerate(turbos):
            lv[i, : t.dp_rows] = t._live_host.reshape(t.dp_rows, 128)
        # translation only (device_errors, no fault_point): construction
        # runs outside the serving containment ladder, so injecting here
        # would fail engine build instead of degrading a query
        with faults.device_errors("column_upload"):
            self.live = jax.device_put(lv, sh)
            zeros = np.zeros((self.Sp, dpc, self.Hp + 1, 16, 128), np.int8)
            self.cols_hi = jax.device_put(zeros, sh)
            self.cols_lo = jax.device_put(zeros, sh)
        self._sharding = sh
        self._live_host = lv     # retained: scrub fingerprint + repair src
        self._epochs = [-1] * S
        # stacked per-partition bitsets (allocated lazily on the first
        # bitset-engine refresh; padded partitions stay all-zero = empty)
        self.bits = None
        self.live_bits = None
        self._bits_versions = [-1] * S
        self.fused_dispatches = 0
        # fused cache is a separate device allocation on top of the
        # per-partition engines' own regions
        self._hbm = hbm_ledger.register_engine(
            self, "fused_turbo", devices=G)
        self._register_hbm_regions()
        self._register_scrub_regions()

    def _register_hbm_regions(self) -> None:
        self._hbm.set_region("cols_hi", self.cols_hi.nbytes)
        self._hbm.set_region("cols_lo", self.cols_lo.nbytes)
        self._hbm.set_region("cols_bits", self._bits_nbytes())
        self._hbm.set_region("live", self.live.nbytes)

    def _register_scrub_regions(self) -> None:
        integrity.register_scrub_region(
            self, "live", lambda o: o.live,
            expected=lambda o: o._live_host,
            repair=lambda o: o._repair_live())
        for name in ("cols_hi", "cols_lo"):
            integrity.register_scrub_region(
                self, name, lambda o, n=name: getattr(o, n),
                epoch=lambda o, n=name: id(getattr(o, n)),
                repair=lambda o: o._reset_fused_columns())

    def _repair_live(self) -> None:
        """Scrub repair: re-upload the live mask from the host copy."""
        # translation only (device_errors, no fault_point): repairs must
        # not be separately injectable rungs
        with faults.device_errors("column_upload"):
            self.live = jax.device_put(self._live_host, self._sharding)

    def _reset_fused_columns(self) -> None:
        """Scrub repair: zero the fused cache and re-sync every partition
        slice from the per-partition engines (their own caches are scrubbed
        separately), restoring bit-identical column state."""
        zeros = np.zeros(self.cols_hi.shape, np.int8)
        with faults.device_errors("column_upload"):
            self.cols_hi = jax.device_put(zeros, self._sharding)
            self.cols_lo = jax.device_put(zeros, self._sharding)
        self._epochs = [-1] * len(self.turbos)
        self._refresh()

    def _reset_fused_bits(self) -> None:
        """Scrub repair for the stacked bitsets: zero, then re-sync every
        partition slice from the engines' own (separately scrubbed)
        bits."""
        if self.bits is None:
            return
        zeros = np.zeros(self.bits.shape, np.uint32)
        with faults.device_errors("column_upload"):
            self.bits = jax.device_put(zeros, self._sharding)
        self._bits_versions = [-1] * len(self.turbos)
        for i in range(len(self.turbos)):
            self._refresh_bits_part(i)

    def extend_qc_sizes(self, sizes) -> None:
        """Bucket-ladder hook, fused flavor: keeps the fused chunker and
        the per-partition engines (host rescore / fallback paths) on the
        same widened width set."""
        for t in self.turbos:
            t.extend_qc_sizes(sizes)
        self.qc_sizes = self.turbos[0].qc_sizes
        hbm_ledger.note_primed("fused_turbo", self.qc_sizes)
        hbm_ledger.note_primed("fused_turbo_bool", self.qc_sizes)
        hbm_ledger.note_primed("fused_turbo_bitset", self.qc_sizes)

    def _refresh_part(self, i: int) -> None:
        """Re-sync one partition's fused column slice if its cache was
        rebuilt since the last dispatch (cols_epoch discipline)."""
        t = self.turbos[i]
        if self._epochs[i] != t.cols_epoch:
            with faults.device_dispatch("column_upload", part=i):
                self.cols_hi = jax.device_put(
                    _set_part(self.cols_hi, t.cols_hi, i=i), self._sharding)
                self.cols_lo = jax.device_put(
                    _set_part(self.cols_lo, t.cols_lo, i=i), self._sharding)
            self._epochs[i] = t.cols_epoch
            self._register_hbm_regions()
        # the bitsets are packed lazily (first bool dispatch), possibly
        # long after the columns they derive from were synced here
        self._refresh_bits_part(i)

    def _refresh_bits_part(self, i: int) -> None:
        """Re-sync one partition's stacked bitset slice. The stacked
        array is allocated lazily on the first sync (disjunction-only
        serving never pays the HBM) — partition-local slot numbering is
        preserved, so each engine's own sentinels (t.Hp zeros, t.Hp + 1
        ones) land inside its slice and padding slots stay all-zero."""
        t = self.turbos[i]
        if t.bits is None or self._bits_versions[i] == t.bits_version:
            return
        first = self.bits is None
        if first:
            wgr = self.nsw * SW_WORD_ROWS
            zeros = np.zeros(
                (self.Sp, self.Hp + 2 + BITSET_COLD_ROWS, wgr, 128),
                np.uint32)
            with faults.device_errors("column_upload"):
                self.bits = jax.device_put(zeros, self._sharding)
                self.live_bits = jax.device_put(
                    np.zeros((self.Sp, wgr, 128), np.uint32),
                    self._sharding)
        with faults.device_dispatch("column_upload", part=i):
            if self._bits_versions[i] < 0:
                self.live_bits = jax.device_put(
                    _set_part(self.live_bits, t.live_bits, i=i),
                    self._sharding)
            # (the whole slice, cold rows and all, whatever moved: a
            # row-level sync is what a later PR may take, ROADMAP S10)
            self.bits = jax.device_put(
                _set_part(self.bits, t.bits, i=i), self._sharding)
        self._bits_versions[i] = t.bits_version
        if first:
            _node_bitset_add("bitset_bytes", self._bits_nbytes())
            integrity.register_scrub_region(
                self, "cols_bits", lambda o: o.bits,
                epoch=lambda o: id(o.bits),
                repair=lambda o: o._reset_fused_bits())
        self._register_hbm_regions()

    def _refresh(self) -> None:
        for i in range(len(self.turbos)):
            self._refresh_part(i)

    def _bits_nbytes(self) -> int:
        return (0 if self.bits is None
                else self.bits.nbytes + self.live_bits.nbytes)

    def hbm_bytes(self) -> int:
        return (self.cols_hi.nbytes + self.cols_lo.nbytes
                + self._bits_nbytes() + self.live.nbytes)

    # ---------------- fused dispatches ----------------

    def _dispatch_disj(self, plans, QC: int, n_rows: int, skip=()):
        """One chunk's fused sweep from its partitions' `_ChunkPlan`s, and
        behind it — before anything waits for the sweep — the cold side
        of every (partition, query) of the chunk, in the order finish
        collects them. Returns (packed rows, {partition: {query:
        gather}}); partitions in `skip` are host-scored and launch none."""
        with tracing.phase("dispatch.prep", qc=QC):
            wq = np.zeros((self.Sp, 2, QC, self.Hp + 1), np.int8)
            qs = np.ones((self.Sp, QC, 1), np.float32)
            for i, plan in enumerate(plans):
                wq[i, :, :, : plan.wq.shape[2]] = plan.wq
                qs[i] = plan.qscale
        # the counter moves AFTER the launch so a faulted dispatch is not
        # counted — the circuit tests pin "zero device dispatches" while
        # open by watching it
        t0 = time.monotonic()
        first_trace = hbm_ledger.note_dispatch("fused_turbo", QC)
        # the call returning, not the sweep: the launch is async, and
        # holds trace + lower + compile when the program is new
        with tracing.phase("dispatch.launch", qc=QC,
                           partitions=len(self.turbos)) as ph:
            with faults.device_dispatch("fused_dispatch"):
                out = _fused_sweep_disj(
                    jnp.asarray(qs), self.cols_hi, self.cols_lo,
                    jnp.asarray(wq), self.live, mesh=self.mesh, QC=QC,
                    nsw=self.nsw, n_rows=n_rows)
            self.fused_dispatches += 1
            if first_trace:
                hbm_ledger.note_compile_done(
                    "fused_turbo", QC, time.monotonic() - t0)
            gathers = {si: t._chunk_gathers(plans[si])
                       for si, t in enumerate(self.turbos)
                       if si not in skip}
            ph.meta["gathers"] = sum(len(g) for g in gathers.values())
        return out, gathers

    def _dispatch_bool(self, resolved, dev_sets, sel, QC: int,
                       n_rows: int, use_bits: bool = False):
        """Returns (packed rows, (nonzero chunks, live matches) [Sp, 2,
        QC], the masks on the device, exact [S, QC]) — the last three
        None on the dense (coverage-matmul) engine. A query a partition
        host-routes rides the fused launch with inert inputs: all-zero
        weights on both engines, and on the bitset engine every clause
        slot pointed at that partition's zero sentinel (empty mask)."""
        with tracing.phase("dispatch.prep", qc=QC):
            wq = np.zeros((self.Sp, 2, QC, self.Hp + 1), np.int8)
            wp = np.zeros((self.Sp, QC, self.Hp + 1), np.int8)
            nreq = np.zeros((self.Sp, QC, 1), np.int32)
            qs = np.ones((self.Sp, QC, 1), np.float32)
            if use_bits:
                # padded partitions keep slot 0: their bits slice is all-zero,
                # so every mask word is 0 and every chunk skips
                q_slots = np.zeros((self.Sp, QC, BITSET_CLAUSES), np.int32)
                q_neg = np.zeros((self.Sp, QC, BITSET_NEGS), np.int32)
                exact = np.zeros((len(self.turbos), QC), bool)
            for i, t in enumerate(self.turbos):
                chunk = [resolved[i][qi] if qi in dev_sets[i] else None
                         for qi in sel]
                w, p, nr, q = t._bool_weights(chunk, QC)
                hp = w.shape[2]
                wq[i, :, :, :hp] = w
                wp[i, :, :hp] = p
                nreq[i] = nr
                qs[i] = q
                if use_bits:
                    q_slots[i], q_neg[i], exact[i] = t._bitset_prefetch(
                        chunk, QC)
        t0 = time.monotonic()
        kind = "fused_turbo_bitset" if use_bits else "fused_turbo_bool"
        first_trace = hbm_ledger.note_dispatch(kind, QC)
        cnts = masks = None
        with tracing.phase("dispatch.launch", qc=QC,
                           partitions=len(self.turbos)), \
                faults.device_dispatch("fused_dispatch"):
            if use_bits:
                out, cnts, masks = _fused_sweep_bitset(
                    jnp.asarray(qs), jnp.asarray(q_slots),
                    jnp.asarray(q_neg), self.bits, self.live_bits,
                    self.cols_hi, self.cols_lo, jnp.asarray(wq), self.live,
                    mesh=self.mesh, QC=QC, nsw=self.nsw, n_rows=n_rows)
            else:
                out = _fused_sweep_bool(
                    jnp.asarray(qs), jnp.asarray(nreq), self.cols_hi,
                    self.cols_lo, jnp.asarray(wq), jnp.asarray(wp),
                    self.live, mesh=self.mesh, QC=QC, nsw=self.nsw,
                    n_rows=n_rows)
        self.fused_dispatches += 1
        if first_trace:
            hbm_ledger.note_compile_done(
                kind, QC, time.monotonic() - t0)
        return out, cnts, masks, exact if use_bits else None

    # ---------------- search ----------------

    def search_many(self, batches: Sequence[List], k: int = 10,
                    check=None, fault_log=None):
        """per[si][bi] = (scores [Q, k] f32, ords [Q, k] i32) — the same
        values `self.turbos[si].search_many(batches)` returns solo, but
        every partition's sweep rides one fused dispatch per chunk.

        Device-fault containment: a partition whose column ensure/upload
        faults, or any query chunk whose fused dispatch faults, is scored
        on host via the exact-merge path (bit-identical) — the batch
        still completes. Contained faults append `FaultRecord`s to
        fault_log (when given) so the serving layer can report
        failed-then-recovered shards."""
        with tracing.steps(DISPATCH_STEPS):
            return self._search_many(batches, k, check, fault_log)

    def _search_many(self, batches, k, check, fault_log):
        flat, spans = _flatten_queries(batches)
        S = len(self.turbos)
        if not flat:
            return [[(np.zeros((n, k), np.float32),
                      np.zeros((n, k), np.int32)) for _, n in spans]
                    for _ in range(S)]
        failed: Dict[int, DeviceFaultError] = {}
        with tracing.phase("dispatch.prep", queries=len(flat)):
            for i, t in enumerate(self.turbos):
                try:
                    t.ensure_columns(
                        [tm for q in flat for tm, _ in q
                         if t._term(tm) is not None])
                    self._refresh_part(i)
                except DeviceFaultError as e:
                    failed[i] = e
        n_rows = max(_GLOBAL_ROWS, k + 5)
        # per chunk (offset, n, the sweep's packed rows or None: faulted,
        # each partition's plan, {partition: {query: its cold side's
        # gather}})
        pending = []
        fused_err: Optional[DeviceFaultError] = None
        out_s = np.zeros((S, len(flat), k), np.float32)
        out_d = np.zeros((S, len(flat), k), np.int32)
        try:
            off = 0
            while off < len(flat):
                rem = len(flat) - off
                take = next((s for s in self.qc_sizes if s >= rem),
                            self.qc_sizes[-1])
                chunk = flat[off: off + take]
                if check is not None:
                    check()
                with tracing.phase("dispatch.prep", qc=take):
                    plans = [t._plan_chunk(chunk, take) for t in self.turbos]
                try:
                    packed_dev, gathers = self._dispatch_disj(
                        plans, take, n_rows, skip=failed)
                except DeviceFaultError as e:
                    packed_dev, gathers, fused_err = None, {}, e
                pending.append((off, len(chunk), packed_dev, plans, gathers))
                off += len(chunk)
            for entry in pending:
                if check is not None:
                    check()
                fused_err = self._finish_chunk(
                    flat, k, n_rows, entry, failed, out_s, out_d) or fused_err
        finally:
            for *_, gathers in pending:    # a cancel unwinding
                for si, hs in gathers.items():
                    for h in hs.values():
                        self.turbos[si]._discard_gather(h)
        if fault_log is not None:
            for i, e in sorted(failed.items()):
                fault_log.append(FaultRecord.from_error(e, partition=i))
            if fused_err is not None:
                fault_log.append(FaultRecord.from_error(fused_err))
        return [[(out_s[si, o: o + n], out_d[si, o: o + n])
                 for o, n in spans] for si in range(S)]

    def _finish_chunk(self, flat, k: int, n_rows: int, entry, failed,
                      out_s, out_d) -> Optional[DeviceFaultError]:
        """Pass 2 for one chunk: wait for its sweep, then a partition at
        a time the chunk-wide finish (`TurboBM25._finish_chunk`): the exact
        rescore merged with the cold sides, whose gathers are already on
        their way to the host. A partition in `failed`, or a chunk whose
        sweep faulted (at its launch, or here at the fetch: returned), is
        host-scored a (partition, query) at a time and drops its
        gathers."""
        off, n, packed_dev, plans, gathers = entry
        packed = err = None
        if packed_dev is not None:
            try:
                with tracing.phase("dispatch.device_wait"), \
                        faults.device_errors("fused_dispatch"):
                    packed = np.asarray(packed_dev)
            except DeviceFaultError as e:     # async fault at fetch
                err = e
        with tracing.phase("dispatch.finish", queries=n):
            for si, t in enumerate(self.turbos):
                mine = gathers.get(si, {})
                if si not in failed and packed is not None:
                    t._finish_chunk(
                        plans[si], packed[si, :n, :n_rows].astype(np.int64),
                        packed[si, :n, n_rows], k, mine,
                        out_s[si, off: off + n], out_d[si, off: off + n])
                    continue
                for qi in range(n):
                    t._discard_gather(mine.pop(qi, None))
                    s, d = t._exact_query(flat[off + qi], k)
                    out_s[si, off + qi, : len(s)] = s
                    out_d[si, off + qi, : len(d)] = d
                t._count_finish(0, n)
        return err

    def search_bool(self, queries: Sequence[dict], k: int = 10,
                    check=None, fault_log=None, totals=None):
        """per[si] = (scores [Q, k] f32, ords [Q, k] i32), matching each
        turbo's solo search_bool bitwise. Partitions may route the same
        query differently (device vs host): the fused sweep dispatches
        the UNION of device-routed queries with all-zero weight rows for
        partitions that host-route one — inert because the kernels score
        query columns independently. `totals` as TurboBM25.search_bool's:
        every partition adds its count.

        Fault containment mirrors search_many: a faulted partition (or a
        faulted fused chunk) serves its queries through _bool_host_exact,
        which every device bool result is bit-identical to anyway."""
        with tracing.steps(DISPATCH_STEPS):
            return self._search_bool(queries, k, check, fault_log, totals)

    def _search_bool(self, queries, k, check, fault_log, totals=None):
        Q = len(queries)
        S = len(self.turbos)
        out_s = np.zeros((S, Q, k), np.float32)
        out_d = np.zeros((S, Q, k), np.int32)
        use_bits = bool(knob("ES_TPU_BITSET"))
        failed: Dict[int, DeviceFaultError] = {}
        routes = []
        with tracing.phase("dispatch.prep", queries=Q):
            with tracing.phase("dispatch.bool_resolve", queries=Q):
                resolved = [[t._resolve_bool(spec, use_bits)
                             for spec in queries] for t in self.turbos]
            for si, t in enumerate(self.turbos):
                try:
                    t._ensure_bool(resolved[si], use_bits)
                    self._refresh_part(si)
                    rt = t._bool_routes(resolved[si])
                    if use_bits:
                        rt = t._gallop_routes(resolved[si], *rt)
                    routes.append(rt)
                except DeviceFaultError as e:
                    failed[si] = e
                    # every resolvable query host-routes for this partition
                    routes.append(
                        ([], [qi for qi, r in enumerate(resolved[si])
                              if r is not None]))
                t._count_routes(resolved[si], routes[si][0])
        dev_sets = [set(dev) for dev, _ in routes]
        union = sorted({qi for ds in dev_sets for qi in ds})
        n_rows = max(_GLOBAL_ROWS, k + 5)
        pending = []
        fused_err: Optional[DeviceFaultError] = None
        off = 0
        while off < len(union):
            rem = len(union) - off
            take = next((s for s in self.qc_sizes if s >= rem),
                        self.qc_sizes[-1])
            sel = union[off: off + take]
            if check is not None:
                check()
            try:
                entry = self._dispatch_bool(
                    resolved, dev_sets, sel, take, n_rows,
                    use_bits=use_bits)
            except DeviceFaultError as e:
                entry, fused_err = (None,) * 4, e
            pending.append((sel,) + entry)
            off += len(sel)
        for sel, packed_dev, cnts_dev, masks_dev, exact in pending:
            if check is not None:
                check()
            packed = cc = masks = None
            if packed_dev is not None:
                try:
                    with tracing.phase("dispatch.device_wait"), \
                            faults.device_errors("fused_dispatch"):
                        packed = np.asarray(packed_dev)
                        if cnts_dev is not None:
                            cc = np.asarray(cnts_dev)
                            masks = _once(lambda m=masks_dev: self._fetch(m))
                except DeviceFaultError as e:
                    packed, cc, fused_err = None, None, e
            with tracing.phase("dispatch.finish", queries=len(sel)):
                for si, t in enumerate(self.turbos):
                    if packed is None:
                        for qi in sel:
                            if qi in dev_sets[si]:
                                s, d = t._bool_host_exact(
                                    resolved[si][qi], k)
                                out_s[si, qi, : len(s)] = s
                                out_d[si, qi, : len(d)] = d
                                if totals is not None:
                                    totals[qi] += _NO_TOTAL
                        continue
                    if cc is not None:
                        act = [j for j, qi in enumerate(sel)
                               if qi in dev_sets[si]]
                        if act:
                            t._note_bitset_counts(
                                cc[si, 0, act], total=self.nsw * N_CHUNKS)
                    t._finish_bool_chunk(
                        resolved[si], sel, dev_sets[si], packed[si],
                        None if masks is None
                        else (lambda si=si: masks()[si]),
                        None if cc is None else cc[si],
                        None if exact is None else exact[si], k, n_rows,
                        out_s[si], out_d[si], totals)
        with tracing.phase("dispatch.finish", host_routed=True):
            for si, t in enumerate(self.turbos):
                for qi in routes[si][1]:
                    if check is not None:
                        check()
                    s, d = t._bool_host_exact(resolved[si][qi], k)
                    out_s[si, qi, : len(s)] = s
                    out_d[si, qi, : len(d)] = d
                    if totals is not None:
                        totals[qi] += _NO_TOTAL
        if fault_log is not None:
            for i, e in sorted(failed.items()):
                fault_log.append(FaultRecord.from_error(e, partition=i))
            if fused_err is not None:
                fault_log.append(FaultRecord.from_error(fused_err))
        return [(out_s[si], out_d[si]) for si in range(S)]

    @staticmethod
    def _fetch(dev) -> np.ndarray:
        with faults.device_errors("fused_dispatch"):
            return np.asarray(dev)
