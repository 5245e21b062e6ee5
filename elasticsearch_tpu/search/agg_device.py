"""Device-native analytics tier: the fused batched aggregation engine.

Generalizes the old one-off terms-agg device seam (`_terms_device_counts`)
into a subsystem executing terms / histogram / date_histogram bucket
counting — plus one level of metric-under-bucket sub-aggregation — as
fused segment-reduce dispatches over HBM-resident columns (ROADMAP item
5; the eager-precompute pattern BM25S proved for scoring, applied to
bucketing):

  * **Precompute at column-upload time.** Per (segment, agg shape) an
    `_AggLayout` bakes the segment-static side of the reduction into ONE
    device-resident i32 column: (doc, bucket-id) pairs grouped by bucket
    for terms; (doc, uniq-value-rank) pairs for histogram and
    date_histogram (values truncated at a fixed granularity ladder —
    hour/minute/second for dates, raw for numerics — so any per-request
    interval/offset/calendar unit composes ON HOST by folding the uniq
    representatives through the host aggregator's own `_key_of`); plus
    the bucket × metric-value cross pairs for sub-aggs. Per query the
    engine pays one masked gather + segment reduce (kernels.py
    `agg_segment_counts` / `agg_two_level_counts`).

  * **Bit-identical to the host aggregators.** The device computes only
    exact integer quantities (doc/value counts via f32 one-hot matmuls,
    exact below 2^24 pairs — gated). Float metrics are exact-refined on
    host: cross pairs are stable-sorted by bucket at build time, so a
    bucket's selected metric values come back in exactly the doc-major
    CSR order the host's `_numeric_all(bucket_mask)` produces, and numpy
    reduces the same f64 sequence — bitwise identical partials.
    `ES_TPU_AGG=0` restores the host path verbatim for A/B.

  * **Batched as bulk-tier scheduler work.** Agg collects route through
    `serving_dispatch(tier=TIER_BULK)` on their own (engine, k) lane:
    concurrent requests sharing a layout merge into one padded device
    batch (rungs = the scheduler bucket ladder, primed via
    `extend_qc_sizes` so retraces stay 0), and they back-fill interactive
    pad slack instead of widening interactive dispatches.

  * **Engine contract end to end.** Layout columns are charged to the
    HBM ledger (one region per layout, reconciling exactly with
    `hbm_bytes()`), registered in the PR-15 scrub registry with
    host-backed repair, capped by ES_TPU_AGG_HBM_FRAC, and `agg_reduce`
    is a first-class fault site: a faulted dispatch poisons only its own
    layout group, and each poisoned collect falls back to the host
    aggregator (counted in `agg_host_fallbacks`).

  * **One dispatch a request (filter + bucket route).** A size-0
    request whose query is `match_all` or a `bool` of `filter` clauses
    (`range` / `term` on numeric fields) over ONE `date_histogram`
    (`search/serving.py: extract_filter_agg_plan`) is one
    `FilterAggWork`: every segment's reduction goes in the same
    scheduler dispatch, concurrent requests merge into one padded batch
    a (segment, layout), and the match set is MADE ON THE DEVICE. Each
    filtered field lives beside the layouts as an i32 column of value
    RANKS (`_FilterColumn`: one ledger + scrub region a (segment,
    field)); the host turns a request's bounds into rank intervals by
    binary search over the segment's sorted distinct values, so the
    device's integer comparison is exact to the field's last bit, and
    `hits.total` is the match set's size from the same program
    (`kernels.agg_filter_counts`). The engine call runs the four
    `dispatch.*` steps, `dispatch.agg_plan` (in prep) and
    `dispatch.agg_fold` (in finish) one observation a dispatch each.
    Counters: `filter_device` / `filter_host` ((request, segment) match
    sets made on the device / made on the host and uploaded),
    `agg_reductions` ((segment, layout) reductions answered; over
    `agg_device_dispatches` = reductions a dispatch).

  * **A range runs only the pair chunks it can touch.** Beside a filter
    column the host keeps a ZONE MAP a layout: the least and the
    greatest rank of the column over each 1024-pair chunk (`_zone_map`,
    numpy, no device bytes). The plan turns a request's rank intervals
    into the bounding range of the chunks that can hold a match
    (`_chunk_ranges`), the kernel's chunk axis is as long as the
    batch's widest range, and a reduction no request of which can touch
    a chunk (a segment the range misses) launches no program. Nothing is
    assumed of the document order: on a column that is not in order the
    range is wide, up to the whole layout, and the answer the same.
    Counters: `agg_chunks_total` / `agg_chunks_run` (chunks of the
    layouts a dispatch's requests meet / chunks inside their ranges),
    `agg_reductions_pruned` (reductions answered with no program).

Fallback matrix (host path serves whenever any gate fails): knob off,
leaf below AGG_DEVICE_MIN_DOCS, missing/script params, keyword-metric
value_count, non-numeric histogram field, > 2^24 pairs, > 2^16 uniq
bucket values, sub-aggs that are not plain metrics or span multiple
metric fields, HBM budget exceeded, device fault. The filter + bucket
route adds: an envelope it does not recognise, a deleted document in the
snapshot, a segment under the size floor or without the field, a
multi-valued or NaN filter field; all of those are answered as before.
"""

from __future__ import annotations

import itertools
import threading
import weakref
from typing import Any, Dict, List, Optional

import numpy as np

from elasticsearch_tpu.common import (
    faults, hbm_ledger, integrity, metrics, tracing,
)
from elasticsearch_tpu.common.errors import DeviceFaultError
from elasticsearch_tpu.common.settings import knob

AGG_PAIR_GRAN = 1024      # pairs per kernel chunk (kernels.AGG_PAIR_GRAN)
AGG_SEG_TILE = 16384      # bucket ids per kernel tile (kernels.AGG_SEG_TILE)
MAX_PAIRS = 1 << 24       # f32 one-hot count accumulation exact below this
MAX_UNIQ = 1 << 16        # uniq-rank bucket ceiling per layout
_DATE_GRANS = (3_600_000, 60_000, 1000)   # hour / minute / second, ms
_MAX_EXACT = float(1 << 53)               # f64 exact-integer ceiling
_I32_MAX = (1 << 31) - 1

# the steps of one engine call (tracing.steps): each takes one histogram
# observation per call (0.0 when it did not run), see common/metrics.py
DISPATCH_STEPS = metrics.DISPATCH_TOP_STEPS + (
    "dispatch.agg_plan", "dispatch.agg_fold", "dispatch.rescore")

# metric sub-agg types the two-level route serves (partials reproduced by
# _metric_partial in exactly the host collect's shape)
DEVICE_METRICS = frozenset({
    "min", "max", "sum", "avg", "value_count", "stats", "extended_stats",
})


# --------------------------------------------------------------------------
# node counters (the tpu_agg section of GET /_nodes/stats)
# --------------------------------------------------------------------------

_COUNTS_LOCK = threading.Lock()
_COUNTS = {"agg_queries": 0, "agg_device_dispatches": 0,
           "agg_host_fallbacks": 0, "agg_bytes": 0,
           "agg_reductions": 0, "filter_device": 0,
           "filter_host": 0, "agg_chunks_total": 0, "agg_chunks_run": 0,
           "agg_reductions_pruned": 0}   # guarded by: _COUNTS_LOCK


def _count(key: str, n: int = 1) -> None:
    with _COUNTS_LOCK:
        _COUNTS[key] += n
    metrics.counter_add(key, n)


def agg_stats() -> dict:
    """The `tpu_agg` section of GET /_nodes/stats."""
    eng = default_engine()
    with _COUNTS_LOCK:
        out = dict(_COUNTS)
    out["enabled"] = bool(knob("ES_TPU_AGG"))
    out["hbm_bytes"] = eng.hbm_bytes()
    out["layouts"] = len(eng.layout_serials())
    return out


def reset_for_tests() -> None:
    with _COUNTS_LOCK:
        for k in _COUNTS:
            _COUNTS[k] = 0


# --------------------------------------------------------------------------
# layouts: one device-resident i32 column per (segment, agg shape)
# --------------------------------------------------------------------------

_layout_serials = itertools.count(1)


def _pack_pairs(doc: np.ndarray, seg: np.ndarray):
    """Pad (doc, bucket) pairs to the 1024-pair chunk granule and compute
    each chunk's inclusive bucket-tile range (the kernel's skip scalars).
    Pad pairs carry doc 0 / bucket -1, which the kernel's ok-gate drops."""
    p0 = len(doc)
    p = max(AGG_PAIR_GRAN, -(-p0 // AGG_PAIR_GRAN) * AGG_PAIR_GRAN)
    d = np.zeros(p, np.int32)
    s = np.full(p, -1, np.int32)
    d[:p0] = doc
    s[:p0] = seg
    nc = p // AGG_PAIR_GRAN
    ct0 = np.ones(nc, np.int32)
    ct1 = np.zeros(nc, np.int32)
    for c in range(nc):
        chunk = s[c * AGG_PAIR_GRAN:(c + 1) * AGG_PAIR_GRAN]
        live = chunk[chunk >= 0]
        if len(live):
            ct0[c] = int(live.min()) // AGG_SEG_TILE
            ct1[c] = int(live.max()) // AGG_SEG_TILE
    return d, s, ct0, ct1


class _AggLayout:
    """One agg shape's precomputed device column for one segment. Owns
    the ledger region and the scrub region; lifecycle is tied to the
    segment's device cache (`seg._device`), so dropping the segment drops
    the region through the weakref finalizer."""

    def __init__(self, kind: str, n_docs: int, sections: List[np.ndarray],
                 meta: dict):
        import jax.numpy as jnp

        self.kind = kind
        self.n_docs = n_docs
        self.serial = next(_layout_serials)
        self.meta = meta
        self.host = np.ascontiguousarray(
            np.concatenate([a.astype(np.int32, copy=False).ravel()
                            for a in sections]))
        self.dev = jnp.asarray(self.host)
        self.nbytes = int(self.host.nbytes)
        self.region_name = f"aggcol{self.serial}_{kind}"

    def _reupload(self) -> None:
        import jax.numpy as jnp

        self.dev = jnp.asarray(self.host)


class _FilterColumn:
    """One numeric field of one segment as the device filters on it: each
    doc's RANK among the segment's sorted distinct values (`uniq`, kept
    on the host), -1 where the doc has no value. A request's bounds
    become a rank interval by binary search over `uniq`, so the device
    compares i32 and the answer is exact to the field's last bit (a
    date's millisecond). Ledger + scrub region like a layout's."""

    kind = "filter"

    def __init__(self, field: str, uniq: np.ndarray, rank: np.ndarray):
        import jax.numpy as jnp

        self.field = field
        self.uniq = uniq
        self.n_docs = len(rank)
        self.serial = next(_layout_serials)
        self.host = np.ascontiguousarray(rank, np.int32)
        self.dev = jnp.asarray(self.host)
        self.nbytes = int(self.host.nbytes)
        self.region_name = "aggflt%d_%s" % (self.serial, "".join(
            c if c.isalnum() else "_" for c in field))

    def _reupload(self) -> None:
        import jax.numpy as jnp

        self.dev = jnp.asarray(self.host)

    def rank_interval(self, lo: float, inc_lo: bool, hi: float,
                      inc_hi: bool):
        """[lo rank, hi rank) of the values inside the bounds."""
        return (int(np.searchsorted(self.uniq, lo,
                                    "left" if inc_lo else "right")),
                int(np.searchsorted(self.uniq, hi,
                                    "right" if inc_hi else "left")))


# --------------------------------------------------------------------------
# the engine: scheduler-facing dispatch adapter
# --------------------------------------------------------------------------


class _AggWork:
    """One agg collect's device work item: a layout + a query mask. The
    engine fills `result` (np count arrays) or `error` (device fault →
    this collect falls back to host). Mutable slots instead of return
    values because the scheduler contract returns fixed-shape score
    arrays, which bucket counts are not."""

    __slots__ = ("layout", "mask", "result", "error")

    def __init__(self, layout: _AggLayout, mask: np.ndarray):
        self.layout = layout
        self.mask = mask
        self.result = None
        self.error: Optional[BaseException] = None


class FilterAggWork:
    """One REQUEST of the filter + bucket route: its histogram, its
    filter clauses as (field, lo, include lo, hi, include hi) in the
    field's own doc-value scale, and every segment of the snapshot (all
    documents live). The engine fills `partial` (the shard's reduced
    histogram partial, what the host's collect + reduce give), `result`
    (the finalized `aggregations` entry, where `finalize`) and `total`
    (the match set's size), or `error` (the caller then answers the old
    way)."""

    __slots__ = ("agg", "clauses", "segments", "finalize", "partial",
                 "result", "total", "error", "_parts")

    def __init__(self, agg, clauses, segments, finalize: bool = True):
        self.agg = agg
        self.clauses = list(clauses)
        self.segments = list(segments)
        self.finalize = finalize
        self.partial = None
        self.result = None
        self.total = 0
        self.error: Optional[BaseException] = None
        self._parts: List[Any] = []


class _Unservable(Exception):
    """A request the route cannot take after all (a layout or column
    refused): its caller answers it the old way."""


class _Reduction:
    """One (segment, layout) reduction of a dispatch: the works that
    take part, the union of the fields they filter on, those fields'
    columns and their zone maps over the layout's chunks."""

    __slots__ = ("layout", "seg", "works", "fields", "cols", "zones", "out")

    def __init__(self, layout, seg):
        self.layout = layout
        self.seg = seg
        self.works: List[FilterAggWork] = []
        self.fields: List[str] = []
        self.cols: List[_FilterColumn] = []
        self.zones: List[Any] = []
        self.out = None


class AggDeviceEngine:
    """Batched device aggregation engine. Speaks the scheduler's
    `search_many` contract so agg collects ride the AdaptiveDispatch
    Scheduler's bulk tier like any other engine's queries; the score
    triple it returns is all zeros (results travel on the works)."""

    kind = "agg"

    def __init__(self):
        self.qc_sizes = (1, 4, 16, 64, 256)   # scheduler ladder rungs
        self._hbm = hbm_ledger.register_engine(self, kind="agg")
        self._lock = threading.Lock()
        self._bytes = 0                        # guarded by: _lock
        self._live: "weakref.WeakValueDictionary[int, _AggLayout]" = \
            weakref.WeakValueDictionary()
        hbm_ledger.note_primed("agg_reduce", self.qc_sizes)

    # ---- HBM accounting ----

    def hbm_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def ledger_bytes(self) -> int:
        return self._hbm.total_bytes()

    def layout_serials(self) -> Dict[str, int]:
        """Live layouts, region name -> serial (tests build fault specs
        and scrub targets from these)."""
        return {lay.region_name: s for s, lay in list(self._live.items())}

    def _budget(self) -> int:
        return int(float(knob("ES_TPU_AGG_HBM_FRAC"))
                   * float(knob("ES_TPU_TURBO_HBM")))

    def adopt_layout(self, layout: _AggLayout) -> bool:
        """Charge a freshly built layout to the ledger + register its
        scrub region (host-backed repair). False = over the
        ES_TPU_AGG_HBM_FRAC budget — the caller serves from host."""
        with self._lock:
            if self._bytes + layout.nbytes > self._budget():
                return False
            self._bytes += layout.nbytes
            self._live[layout.serial] = layout
        self._hbm.set_region(layout.region_name, layout.nbytes)
        integrity.register_scrub_region(
            layout, layout.region_name, lambda o: o.dev,
            expected=lambda o: o.host,
            repair=lambda o: o._reupload())
        weakref.finalize(layout, self._drop_layout, layout.region_name,
                         layout.nbytes)
        _count("agg_bytes", layout.nbytes)
        return True

    def _drop_layout(self, region_name: str, nbytes: int) -> None:
        with self._lock:
            self._bytes -= nbytes
        self._hbm.drop_region(region_name)

    # ---- scheduler engine contract ----

    def extend_qc_sizes(self, sizes) -> None:
        """Scheduler bucket-ladder hook: widen the padded query-batch
        rungs and mark them primed (the shape axis that drives retraces
        for agg dispatches is the padded batch width)."""
        merged = sorted(set(self.qc_sizes) | {int(s) for s in sizes})
        self.qc_sizes = tuple(merged)
        hbm_ledger.note_primed("agg_reduce", self.qc_sizes)

    def search_many(self, batches, k: int = 1, check=None, fault_log=None):
        out = []
        for works in batches:
            works = list(works)
            with tracing.steps(DISPATCH_STEPS):
                self._run_filter_works(
                    [w for w in works if isinstance(w, FilterAggWork)])
                self._run_works(
                    [w for w in works if not isinstance(w, FilterAggWork)])
            q = max(1, len(works))
            kk = max(1, int(k))
            out.append((np.zeros((q, kk), np.float32),
                        np.zeros((q, kk), np.int32),
                        np.zeros((q, kk), np.int32)))
        return out

    def _run_works(self, works: List[_AggWork]) -> None:
        groups: Dict[int, List[_AggWork]] = {}
        for w in works:
            groups.setdefault(w.layout.serial, []).append(w)
        for group in groups.values():
            try:
                self._dispatch_group(group)
            except Exception as e:  # containment: only this layout's works
                for w in group:     # fall back to the host collect
                    w.error = e

    def _dispatch_group(self, group: List[_AggWork]) -> None:
        import jax.numpy as jnp

        from elasticsearch_tpu.parallel import kernels

        layout = group[0].layout
        q = len(group)
        qpad = self._qpad(q)
        mask = np.zeros((qpad, layout.n_docs), bool)
        for i, w in enumerate(group):
            mask[i] = w.mask
        hbm_ledger.note_dispatch("agg_reduce", qpad)
        metrics.observe("agg_batch_size", q)
        _count("agg_device_dispatches")
        _count("agg_reductions")
        _count("filter_host", q)    # a host mask a work, uploaded below
        with faults.device_dispatch("agg_reduce", layout.serial):
            if layout.kind == "terms_metric":
                dc, vc = kernels.agg_two_level_counts(
                    jnp.asarray(mask), layout.dev,
                    pd=layout.meta["pd"], pm=layout.meta["pm"],
                    n_segments=layout.meta["n_segments"])
                dc, vc = np.asarray(dc), np.asarray(vc)
                for i, w in enumerate(group):
                    w.result = (dc[i], vc[i])
            else:
                counts = np.asarray(kernels.agg_segment_counts(
                    jnp.asarray(mask), layout.dev, p=layout.meta["p"],
                    n_segments=layout.meta["n_segments"]))
                for i, w in enumerate(group):
                    w.result = counts[i]


    # ---- the filter + bucket route: one dispatch a request ----

    def _qpad(self, q: int) -> int:
        qpad = next((s for s in self.qc_sizes if s >= q), None)
        if qpad is None:
            qpad = -(-q // self.qc_sizes[-1]) * self.qc_sizes[-1]
        return qpad

    def _run_filter_works(self, works: List["FilterAggWork"]) -> None:
        """Every segment's reduction of every request of the batch, in
        ONE dispatch: plan (prep), one program a (segment, layout) that
        a request's range can touch launched and waited for in turn, the
        fold (finish)."""
        if not works:
            return
        with tracing.phase("dispatch.prep", queries=len(works)), \
                tracing.phase("dispatch.agg_plan", queries=len(works)):
            reds = self._plan(works)
        _count("agg_device_dispatches")
        # one reduction on the device at a time: its selection [Q, pairs]
        # is the program's scratch (0.66 GB a segment of 10 M documents at
        # width 16), and programs launched behind each other would hold
        # one each: the peak would follow the batch's mix, not the index
        for r in reds:
            try:
                with tracing.phase("dispatch.launch", width=len(r.works)):
                    self._launch(r)
                with tracing.phase("dispatch.device_wait"), \
                        faults.device_errors("agg_reduce", r.layout.serial):
                    r.out = tuple(np.asarray(a) for a in r.out)
            except Exception as e:   # containment: this reduction's
                self._fail(r, e)     # requests fall back, no others
        with tracing.phase("dispatch.finish", queries=len(works)):
            with tracing.phase("dispatch.agg_fold", queries=len(works)):
                for r in reds:
                    self._fold_reduction(r)
                live = [w for w in works if w.error is None]
                for w in live:
                    try:
                        _reduce_work(w)
                    except Exception as e:
                        w.error = e
            with tracing.phase("dispatch.rescore", queries=len(live)):
                # the exact cross-check of the device's f32-accumulated
                # counts: where every doc holds one value of the
                # bucketed field, a request's buckets sum to its total
                for w in live:
                    if w.error is None and not _counts_add_up(w):
                        w.error = DeviceFaultError(
                            "agg_reduce: bucket counts do not add up to "
                            "the match set's size", site="agg_reduce")

    @staticmethod
    def _fail(r: "_Reduction", e: BaseException) -> None:
        r.out = None
        for w in r.works:
            w.error = e

    def _plan(self, works: List["FilterAggWork"]) -> List["_Reduction"]:
        """Layouts, filter columns and zone maps looked up (built on
        first use), the works grouped a (segment, layout)."""
        reds: Dict[int, _Reduction] = {}
        for w in works:
            w.error, w._parts = None, []      # a solo retry starts afresh
            try:
                fname = w.agg.params["field"]
                gran = _pick_gran(w.agg)
                mine = []
                for seg in w.segments:
                    col = seg.numeric.get(fname)
                    lay = None if col is None else _uniq_layout(
                        seg, fname, col, gran)
                    if lay is None:
                        raise _Unservable(f"no layout for [{fname}]")
                    for f in {c[0] for c in w.clauses} | {fname}:
                        if _filter_column(seg, f) is None:
                            raise _Unservable(f"no filter column [{f}]")
                    mine.append((lay, seg))
            except Exception as e:
                w.error = e
                continue
            for lay, seg in mine:
                r = reds.get(lay.serial)
                if r is None:
                    r = reds[lay.serial] = _Reduction(lay, seg)
                r.works.append(w)
        out = list(reds.values())
        for r in out:
            fname = r.works[0].agg.params["field"]
            r.fields = sorted({c[0] for w in r.works for c in w.clauses}
                              or {fname})
            r.cols = [_filter_column(r.seg, f) for f in r.fields]
            r.zones = [_zone_map(r.seg, fname, r.layout, c) for c in r.cols]
        return out

    def _launch(self, r: "_Reduction") -> None:
        import jax.numpy as jnp

        from elasticsearch_tpu.parallel import kernels

        q = len(r.works)
        qpad = self._qpad(q)
        # a padding row keeps nothing; an unconstrained column keeps all
        bounds = np.zeros((qpad, len(r.cols), 2), np.int32)
        bounds[:q, :, 0] = -1
        bounds[:q, :, 1] = _I32_MAX
        for i, w in enumerate(r.works):
            for f, lo, inc_lo, hi, inc_hi in w.clauses:
                j = r.fields.index(f)
                lo_r, hi_r = r.cols[j].rank_interval(lo, inc_lo, hi, inc_hi)
                bounds[i, j, 0] = max(bounds[i, j, 0], lo_r)
                bounds[i, j, 1] = min(bounds[i, j, 1], hi_r)
        lay = r.layout
        # whole tiles out: segments whose rank counts differ share a program
        n_out = -(-lay.meta["n_segments"] // AGG_SEG_TILE) * AGG_SEG_TILE
        # the pair chunks each row can touch; a padding row's interval
        # is empty and touches none
        crange = _chunk_ranges(bounds, r.zones)
        run = int((crange[:, 1] - crange[:, 0]).sum())
        metrics.observe("agg_batch_size", q)
        _count("agg_reductions")
        _count("filter_device", q)
        _count("agg_chunks_total", q * (lay.meta["p"] // AGG_PAIR_GRAN))
        _count("agg_chunks_run", run)
        if run == 0 and lay.meta["identity"]:
            # no row can match a pair, and every doc is a pair (the
            # segment a range misses): the answer is known, no program
            _count("agg_reductions_pruned")
            r.out = (np.zeros((qpad, n_out), np.int32),
                     np.zeros(qpad, np.int32))
            return
        hbm_ledger.note_dispatch("agg_reduce", qpad)
        with faults.device_dispatch("agg_reduce", lay.serial):
            r.out = kernels.agg_filter_counts(
                jnp.asarray(bounds), jnp.asarray(crange),
                tuple(c.dev for c in r.cols), lay.dev,
                p=lay.meta["p"], n_out=n_out,
                identity=bool(lay.meta["identity"]))

    @staticmethod
    def _fold_reduction(r: "_Reduction") -> None:
        """A reduction's rank counts folded into each request's buckets:
        the partial the host's collect gives for this segment."""
        if r.out is None:
            return
        counts, totals = r.out
        lay = r.layout
        n = lay.meta["n_segments"]
        for i, w in enumerate(r.works):
            if w.error is not None:
                continue
            _count("agg_queries")
            w._parts.append((_fold_ranks(w.agg, lay, counts[i, :n]),
                             int(totals[i]), bool(lay.meta["identity"])))


_ENGINE: Optional[AggDeviceEngine] = None
_ENGINE_LOCK = threading.Lock()


def default_engine() -> AggDeviceEngine:
    global _ENGINE
    with _ENGINE_LOCK:
        if _ENGINE is None:
            _ENGINE = AggDeviceEngine()
        return _ENGINE


def _dispatch(works: List[_AggWork]) -> bool:
    """Route works through the serving dispatch facade as bulk-tier
    scheduler work. True = every work carries a device result."""
    from elasticsearch_tpu.threadpool.scheduler import (
        TIER_BULK,
        serving_dispatch,
    )

    serving_dispatch(default_engine(), works, 1, tier=TIER_BULK)
    ok = True
    for w in works:
        if w.error is not None or w.result is None:
            _count("agg_host_fallbacks")
            ok = False
    return ok


# --------------------------------------------------------------------------
# layout builders (cached on seg._device, refusals cached too)
# --------------------------------------------------------------------------

_BUILD_LOCK = threading.Lock()
_REFUSED = "host"          # cache sentinel: this shape stays on host


def _cached_layout(seg, key: str, build) -> Optional[_AggLayout]:
    with _BUILD_LOCK:
        cached = seg._device.get(key)
        if cached is _REFUSED:
            return None
        if cached is not None:
            return cached
        lay = build()
        if lay is None or not default_engine().adopt_layout(lay):
            seg._device[key] = _REFUSED
            return None
        seg._device[key] = lay
        return lay


def _terms_sections(seg, kc):
    """Level-1 (doc, term-ord) pairs grouped by ord — the old
    `_terms_device_counts` pair layout, now packed into a ledgered blob."""
    counts = kc.ord_start[1:] - kc.ord_start[:-1]
    doc_of_value = np.repeat(np.arange(seg.n_docs, dtype=np.int32), counts)
    order = np.argsort(kc.all_ords, kind="stable")
    return _pack_pairs(doc_of_value[order],
                       kc.all_ords[order].astype(np.int32))


def _terms_layout(seg, fname: str, kc) -> Optional[_AggLayout]:
    def build():
        if len(kc.all_ords) >= MAX_PAIRS:
            return None
        d, s, ct0, ct1 = _terms_sections(seg, kc)
        return _AggLayout("terms", seg.n_docs, [d, s, ct0, ct1],
                          {"p": len(d), "n_segments": len(kc.terms)})

    return _cached_layout(seg, f"aggdev:terms:{fname}", build)


def _terms_metric_layout(seg, fname: str, kc, mfield: str,
                         mcol) -> Optional[_AggLayout]:
    """Two-level layout: level-1 term pairs + the term-ord × metric-value
    cross pairs, both stable-sorted by ord. Within an ord the cross pairs
    keep (doc asc, value CSR order) — exactly the order the host's
    `_numeric_all(bucket_mask)` flattens, so the host float refinement
    reduces identical sequences."""

    def build():
        n = seg.n_docs
        kcounts = (kc.ord_start[1:] - kc.ord_start[:-1]).astype(np.int64)
        mcounts = (mcol.value_start[1:]
                   - mcol.value_start[:-1]).astype(np.int64)
        per_doc = kcounts * mcounts
        pm0 = int(per_doc.sum())
        if pm0 >= MAX_PAIRS or len(kc.all_ords) >= MAX_PAIRS:
            return None
        starts = np.concatenate([[0], np.cumsum(per_doc)])
        mp_doc = np.repeat(np.arange(n, dtype=np.int64), per_doc)
        local = np.arange(pm0, dtype=np.int64) - starts[mp_doc]
        md = mcounts[mp_doc]
        oi = local // np.maximum(md, 1)
        vi = local - oi * md
        ords = kc.all_ords[kc.ord_start[mp_doc] + oi].astype(np.int32)
        val_idx = mcol.value_start[mp_doc] + vi
        order = np.argsort(ords, kind="stable")
        d1, s1, dct0, dct1 = _terms_sections(seg, kc)
        d2, s2, mct0, mct1 = _pack_pairs(
            mp_doc[order].astype(np.int32), ords[order])
        lay = _AggLayout(
            "terms_metric", n,
            [d1, s1, dct0, dct1, d2, s2, mct0, mct1],
            {"pd": len(d1), "pm": len(d2), "n_segments": len(kc.terms)})
        # host refinement data: the cross pairs' docs (selection) and f64
        # values (exact metric reduction), in the device blob's order
        lay.meta["mvals"] = mcol.all_values[val_idx][order]
        lay.meta["mdoc"] = mp_doc[order]
        return lay

    return _cached_layout(seg, f"aggdev:termsm:{fname}:{mfield}", build)


def _uniq_layout(seg, fname: str, col, gran) -> Optional[_AggLayout]:
    """(doc, uniq-value-rank) pairs at a fixed granularity: histogram and
    date_histogram count per RANK on device, and the host folds the
    ranks' representative values through the aggregator's own `_key_of`
    — any interval/offset/calendar unit, bit-identical by construction.
    `gran` is "raw" (ranks of the exact values) or an integer divisor of
    both interval and offset (date ladder), in which case truncation
    cannot move a value across a bucket boundary."""

    def build():
        vals = col.values
        exists = col.exists
        sel_docs = np.nonzero(exists)[0]
        v = vals[sel_docs]
        if np.isnan(v).any():
            return None
        g = gran
        if g != "raw" and (not np.all(v == np.floor(v))
                           or np.abs(v).max(initial=0.0) >= _MAX_EXACT):
            g = "raw"      # truncation only sound on exact-integer values
        tv = np.floor(v / g) * g if g != "raw" else v
        reps, uid = np.unique(tv, return_inverse=True)
        if len(reps) > MAX_UNIQ or len(sel_docs) >= MAX_PAIRS:
            return None
        d, s, ct0, ct1 = _pack_pairs(sel_docs.astype(np.int32),
                                     uid.astype(np.int32))
        lay = _AggLayout("uniq", seg.n_docs, [d, s, ct0, ct1],
                         {"p": len(d), "n_segments": len(reps)})
        lay.meta["reps"] = reps
        uid_of_doc = np.full(seg.n_docs, -1, np.int64)
        uid_of_doc[sel_docs] = uid
        lay.meta["uid_of_doc"] = uid_of_doc
        # pair i IS doc i (every doc holds the field): a selection made
        # on the device is in pair order without a gather
        lay.meta["identity"] = len(sel_docs) == seg.n_docs
        return lay

    return _cached_layout(seg, f"aggdev:uniq:{fname}:{gran}", build)


def _filter_column(seg, fname: str) -> Optional[_FilterColumn]:
    """The segment's filter column of one numeric field, built and
    adopted (ledger, scrub registry, HBM budget) on first use. Refused,
    and the refusal cached: a field the segment lacks, a multi-valued
    one (a range keeps a doc when ANY value lies inside: one rank a doc
    cannot say that), NaN values, a budget that is spent."""

    def build():
        col = seg.numeric.get(fname)
        if col is None or len(col.all_values) != int(col.exists.sum()):
            return None
        vals = col.values[col.exists]
        if np.isnan(vals).any():
            return None
        uniq, inv = np.unique(vals, return_inverse=True)
        rank = np.full(seg.n_docs, -1, np.int32)
        rank[col.exists] = inv
        return _FilterColumn(fname, uniq, rank)

    return _cached_layout(seg, f"aggdev:filter:{fname}", build)


def _zone_map(seg, fname: str, lay: _AggLayout, col: _FilterColumn):
    """(least, greatest) rank of filter column `col` over each 1024-pair
    chunk of the segment's layouts of field `fname`, as the device
    compares them: a pair doc with no value counts as rank -1, a pad pair
    not at all (a chunk of pads alone reads (INT32_MAX, -2): no interval
    meets it). Host arrays [chunks] i32, built on first use and kept
    where the column is; a segment's layouts of one field share their
    pair docs, so they share the map."""
    key = f"aggdev:zone:{fname}:{col.field}"
    with _BUILD_LOCK:
        zone = seg._device.get(key)
        if zone is None:
            p = lay.meta["p"]
            rank = col.host[lay.host[:p]]
            real = lay.host[p:2 * p] >= 0
            chunks = (p // AGG_PAIR_GRAN, AGG_PAIR_GRAN)
            zone = seg._device[key] = (
                np.where(real, rank, _I32_MAX).reshape(chunks).min(axis=1),
                np.where(real, rank, -2).reshape(chunks).max(axis=1))
    return zone


def _chunk_ranges(bounds: np.ndarray, zones) -> np.ndarray:
    """[q, 2] i32: the bounding range [c0, c1) of the chunks row q can
    touch. A chunk is kept iff, for EVERY column, its zone [least,
    greatest] meets the row's rank interval `bounds[q, f]` = [lo, hi);
    (0, 0) where none is. Exact whatever the documents' order: a chunk
    outside the range holds no pair the device's comparison would keep."""
    lo, hi = bounds[:, :, 0, None], bounds[:, :, 1, None]
    cmin = np.stack([z[0] for z in zones])
    cmax = np.stack([z[1] for z in zones])
    keep = ((cmax >= lo) & (cmin < hi) & (hi > lo)).all(axis=1)   # [q, nc]
    out = np.zeros((len(bounds), 2), np.int32)
    hit = keep.any(axis=1)
    out[hit, 0] = keep[hit].argmax(axis=1)
    out[hit, 1] = keep.shape[1] - keep[hit, ::-1].argmax(axis=1)
    return out


def _metric_pair_docs(seg, mfield: str, mcol) -> np.ndarray:
    """Doc id per flattened metric value (CSR order) — cached host array
    for the histogram sub-agg refinement."""
    key = f"aggdev:mdoc:{mfield}"
    out = seg._device.get(key)
    if out is None:
        mcounts = mcol.value_start[1:] - mcol.value_start[:-1]
        out = np.repeat(np.arange(seg.n_docs, dtype=np.int64), mcounts)
        seg._device[key] = out
    return out


# --------------------------------------------------------------------------
# host-exact refinement helpers
# --------------------------------------------------------------------------


def _metric_partial(mtype: str, vals: np.ndarray):
    """Reproduce the host metric collect partial from a bucket's selected
    values — `vals` is f64 in the host's `_numeric_all` order, so every
    float reduction is the same numpy call on the same sequence."""
    n = len(vals)
    if mtype == "min":
        return {"min": float(vals.min()) if n else None}
    if mtype == "max":
        return {"max": float(vals.max()) if n else None}
    if mtype == "sum":
        return {"sum": float(vals.sum())}
    if mtype == "avg":
        return {"sum": float(vals.sum()), "count": int(n)}
    if mtype == "value_count":
        return {"count": int(n)}
    # stats / extended_stats share the StatsAgg partial
    if not n:
        return {"count": 0, "sum": 0.0, "min": None, "max": None,
                "sum2": 0.0}
    return {"count": int(n), "sum": float(vals.sum()),
            "min": float(vals.min()), "max": float(vals.max()),
            "sum2": float((vals.astype(np.float64) ** 2).sum())}


def _sub_supported(agg) -> Optional[str]:
    """Metric field name when EVERY sub-agg is a plain device-servable
    metric on one shared numeric field; None → host path."""
    mfield = None
    for s in agg.sub:
        if s.type_name not in DEVICE_METRICS or s.sub or s.sub_pipelines:
            return None
        if s.params.get("missing") is not None:
            return None
        f = s.params.get("field")
        if not isinstance(f, str):
            return None
        if mfield is None:
            mfield = f
        elif f != mfield:
            return None
    return mfield


# --------------------------------------------------------------------------
# collect entry points (aggregations.py device routes)
# --------------------------------------------------------------------------


def _enabled() -> bool:
    return bool(knob("ES_TPU_AGG"))


def collect_terms(agg, ctx, kc, mask: np.ndarray):
    """Device route for TermsAgg.collect; None → host path."""
    if not _enabled() or not len(kc.terms):
        return None
    seg = ctx.leaf.segment
    sel = mask & kc.exists
    if not agg.sub:
        lay = _terms_layout(seg, agg.params["field"], kc)
        if lay is None:
            _count("agg_host_fallbacks")
            return None
        work = _AggWork(lay, sel)
        if not _dispatch([work]):
            return None
        _count("agg_queries")
        counts = work.result
        nz = np.nonzero(counts)[0]
        return {kc.terms[o]: {"doc_count": int(counts[o]), "sub": {}}
                for o in nz}
    mfield = _sub_supported(agg)
    if mfield is None:
        return None
    mcol = seg.numeric.get(mfield)
    if mcol is None:
        return None
    lay = _terms_metric_layout(seg, agg.params["field"], kc, mfield, mcol)
    if lay is None:
        _count("agg_host_fallbacks")
        return None
    work = _AggWork(lay, sel)
    if not _dispatch([work]):
        return None
    _count("agg_queries")
    doc_counts, val_counts = work.result
    take = sel[lay.meta["mdoc"]]
    vals_sel = lay.meta["mvals"][take]
    bounds = np.concatenate([[0], np.cumsum(val_counts)])
    out: Dict[Any, dict] = {}
    for o in np.nonzero(doc_counts)[0]:
        v = vals_sel[bounds[o]:bounds[o + 1]]
        sub = {s.name: _metric_partial(s.type_name, v) for s in agg.sub}
        out[kc.terms[o]] = {"doc_count": int(doc_counts[o]), "sub": sub}
    return out


def _pick_gran(agg):
    """Largest date granularity dividing both interval and offset (so
    truncated values land in the same bucket as the raw ones); "raw" for
    numeric histograms and anything the ladder can't express."""
    if agg.type_name != "date_histogram":
        return "raw"
    if getattr(agg, "_calendar_unit", lambda: None)() is not None:
        # month/quarter/year truncate UTC datetimes and ignore offset;
        # their boundaries are hour-aligned, so hour ranks suffice
        return 3_600_000
    try:
        interval = float(agg._interval())
        offset = float(agg.params.get("offset", 0.0))
    except Exception:
        return "raw"
    for g in _DATE_GRANS:
        if interval % g == 0 and offset % g == 0:
            return g
    return "raw"


def _fold_ranks(agg, lay: _AggLayout, counts: np.ndarray) -> Dict[float,
                                                                   dict]:
    """Rank counts -> the histogram's collect partial: the ranks'
    representative values go through the aggregator's own `_key_of`
    (any interval and offset the granularity divides), exactly as the
    host's collect keys the raw values."""
    keyed = lay.meta.setdefault("keyed", {})
    spec = (agg.type_name, agg._interval(),
            float(agg.params.get("offset", 0.0)))
    got = keyed.get(spec)
    if got is None:
        if len(keyed) >= 8:       # a few shapes a layout: dashboards
            keyed.clear()         # repeat theirs
        keys = np.round(agg._key_of(lay.meta["reps"]), 10)
        got = keyed[spec] = np.unique(keys, return_inverse=True)
    uk, uinv = got
    dc = np.bincount(uinv, weights=counts, minlength=len(uk)).astype(
        np.int64)
    return {float(uk[i]): {"doc_count": int(dc[i]), "sub": {}}
            for i in np.flatnonzero(dc)}


def _reduce_work(w: FilterAggWork) -> None:
    """A request's segments reduced (and finalized) by the aggregator's
    own `reduce` / `finalize`, as the shard and the coordinator do."""
    w.total = sum(t for _, t, _ in w._parts)
    w.partial = {w.agg.name: w.agg.reduce([p for p, _, _ in w._parts])}
    if w.finalize:
        w.result = {w.agg.name: w.agg.finalize(w.partial[w.agg.name])}


def _counts_add_up(w: FilterAggWork) -> bool:
    return all(not identity
               or sum(b["doc_count"] for b in part.values()) == total
               for part, total, identity in w._parts)


def dispatch_filter_agg(works: List[FilterAggWork]) -> None:
    """The filter + bucket route's one scheduler dispatch: the requests'
    works ride the bulk tier's lane of the aggregation engine, merged
    with whatever else is queued there. A work that comes back with an
    `error` is counted as a host fallback; its caller answers it the old
    way."""
    from elasticsearch_tpu.threadpool.scheduler import (
        TIER_BULK,
        serving_dispatch,
    )

    serving_dispatch(default_engine(), works, 1, tier=TIER_BULK)
    for w in works:
        if w.error is not None or w.partial is None:
            if w.error is None:
                w.error = RuntimeError("no result")
            _count("agg_host_fallbacks")


def collect_histogram(agg, ctx, col, mask: np.ndarray):
    """Device route for HistogramAgg / DateHistogramAgg collect; None →
    host path. Level-1 counting runs on device per uniq value rank; the
    host folds rank counts into request buckets with the aggregator's
    own `_key_of` over the rank representatives."""
    if not _enabled():
        return None
    seg = ctx.leaf.segment
    mfield = None
    mcol = None
    if agg.sub:
        mfield = _sub_supported(agg)
        if mfield is None:
            return None
        mcol = seg.numeric.get(mfield)
        if mcol is None:
            return None
    lay = _uniq_layout(seg, agg.params["field"], col, _pick_gran(agg))
    if lay is None:
        _count("agg_host_fallbacks")
        return None
    sel = mask & col.exists
    work = _AggWork(lay, sel)
    if not _dispatch([work]):
        return None
    _count("agg_queries")
    counts = work.result.astype(np.int64)
    reps = lay.meta["reps"]
    keys = np.round(agg._key_of(reps), 10)
    uk, uinv = np.unique(keys, return_inverse=True)
    dc = np.zeros(len(uk), np.int64)
    np.add.at(dc, uinv, counts)
    if not agg.sub:
        return {float(k): {"doc_count": int(c), "sub": {}}
                for k, c in zip(uk, dc) if c}
    # metric refinement: select cross values on host, stable-sort by the
    # request bucket rank (preserving doc-major CSR order within each
    # bucket — the host `_numeric_all` order), split at the boundaries
    mdoc = _metric_pair_docs(seg, mfield, mcol)
    take = sel[mdoc]
    vals_t = mcol.all_values[take]
    rid = uinv[lay.meta["uid_of_doc"][mdoc[take]]]
    order = np.argsort(rid, kind="stable")
    vals_o = vals_t[order]
    bounds = np.concatenate(
        [[0], np.cumsum(np.bincount(rid, minlength=len(uk)))])
    out: Dict[float, dict] = {}
    for ki in np.nonzero(dc)[0]:
        v = vals_o[bounds[ki]:bounds[ki + 1]]
        sub = {s.name: _metric_partial(s.type_name, v) for s in agg.sub}
        out[float(uk[ki])] = {"doc_count": int(dc[ki]), "sub": sub}
    return out
