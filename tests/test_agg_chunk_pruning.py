"""Chunk pruning of the filter + bucket route (PR 41), kernel and planner,
on the CPU under `interpret=True`.

A request's rank intervals and the filter columns' ZONE MAPS (least and
greatest rank a 1024-pair chunk, `agg_device._zone_map`) give each row of a
batch the bounding range of the chunks that can hold a match
(`agg_device._chunk_ranges`), and `kernels.agg_filter_counts` runs that
range and no other chunk. Held here: the pruned answer is the full-range
answer and the brute-force count, BIT FOR BIT, whatever the documents'
order (on a shuffled column the range is the whole layout and nothing is
saved); the planner never drops a chunk that holds a matching document;
one program serves every range. The served route over a logs corpus is in
tests/test_filter_agg_route.py.
"""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.parallel import kernels
from elasticsearch_tpu.search import agg_device
from elasticsearch_tpu.search.agg_device import (
    AGG_PAIR_GRAN, AGG_SEG_TILE, _AggLayout, _FilterColumn, _chunk_ranges,
    _pack_pairs, _zone_map,
)

I32_MAX = (1 << 31) - 1
FREE = (-1, I32_MAX)          # an unconstrained column
KINDS = ("identity", "gathered", "minute4")
ORDERS = ("ordered", "shuffled")


class _Segment:
    """What the planner reads of a segment, made by hand: a layout of the
    bucketed field `ts` ((doc, bucket) pairs in document order), the rank
    columns of `ts` and `status`, and the dict the zone maps are kept in."""

    def __init__(self, kind: str, order: str, seed: int = 3):
        rng = np.random.default_rng(seed)
        n = 14_000 if kind == "gathered" else 12_000
        has = np.ones(n, bool) if kind != "gathered" else rng.random(n) < 0.85
        t = np.sort(rng.integers(0, 9_000, n))        # arrival = time order
        if order == "shuffled":
            t = rng.permutation(t)
        uniq, inv = np.unique(t[has], return_inverse=True)
        ts_rank = np.full(n, -1, np.int32)
        ts_rank[has] = inv
        status = rng.integers(0, 5, n).astype(np.int32)
        status[rng.random(n) < 0.02] = -1             # a doc with no status
        self.n = n
        self.docs = np.flatnonzero(has).astype(np.int32)     # the pair docs
        # hour-like: a few ranks a bucket, one tile; minute-like: bucket ids
        # spread over FOUR tiles
        self.bucket = (ts_rank[has] * 9 if kind == "minute4"
                       else ts_rank[has] // 4).astype(np.int32)
        self.n_out = (4 if kind == "minute4" else 1) * AGG_SEG_TILE
        assert self.bucket.max() < self.n_out
        if kind == "minute4":
            assert self.bucket.max() >= 3 * AGG_SEG_TILE
        d, s, ct0, ct1 = _pack_pairs(self.docs, self.bucket)
        self.lay = _AggLayout("uniq", n, [d, s, ct0, ct1], {
            "p": len(d), "n_segments": int(self.bucket.max()) + 1,
            "identity": bool(has.all())})
        self.nc = len(d) // AGG_PAIR_GRAN
        self.cols = [_FilterColumn("ts", uniq, ts_rank),
                     _FilterColumn("status", np.arange(5.0), status)]
        self.n_ranks = len(uniq)
        self.seg = types.SimpleNamespace(_device={})

    def zones(self, n_cols):
        return [_zone_map(self.seg, "ts", self.lay, c)
                for c in self.cols[:n_cols]]

    def keep(self, bounds):
        """[q, n] bool: the device's own comparison, in numpy."""
        out = np.ones((len(bounds), self.n), bool)
        for f in range(bounds.shape[1]):
            r = self.cols[f].host[None, :]
            out &= (r >= bounds[:, f, 0:1]) & (r < bounds[:, f, 1:2])
        return out

    def brute(self, bounds):
        keep = self.keep(bounds)
        counts = np.stack([np.bincount(self.bucket[k[self.docs]],
                                       minlength=self.n_out) for k in keep])
        return counts.astype(np.int32), keep.sum(axis=1).astype(np.int32)

    def run(self, bounds, crange):
        f = bounds.shape[1]
        counts, totals = kernels.agg_filter_counts(
            jnp.asarray(bounds), jnp.asarray(crange),
            tuple(c.dev for c in self.cols[:f]), self.lay.dev,
            p=self.lay.meta["p"], n_out=self.n_out,
            identity=self.lay.meta["identity"])
        return np.asarray(counts), np.asarray(totals)


@pytest.fixture(scope="module")
def segments():
    made = {}

    def get(kind, order):
        if (kind, order) not in made:
            made[kind, order] = _Segment(kind, order)
        return made[kind, order]

    return get


def _rows(s: _Segment, q: int, n_cols: int, seed: int):
    """[qpad, F, 2] bounds: `q` rows that hold a query, then padding rows
    ((0, 0): keeps nothing) up to the next rung of 1 / 4 / 16. The rows
    differ: a narrow range, one that ends mid-chunk, the whole column, an
    empty interval (a range that misses the segment), one rank."""
    rng = np.random.default_rng(seed)
    qpad = next(w for w in (1, 4, 16) if w >= q)
    bounds = np.zeros((qpad, n_cols, 2), np.int32)
    r = s.n_ranks
    shapes = [lambda lo: (lo, lo + r // 12),              # about 8 %
              lambda lo: (lo, lo + r // 90 + 1),          # about 1 %
              lambda lo: FREE,
              lambda lo: (lo, lo),                        # empty
              lambda lo: (r, r + 9),                      # past the segment
              lambda lo: (lo, lo + 1)]
    for i in range(q):
        bounds[i, 0] = shapes[(i + seed) % len(shapes)](
            int(rng.integers(0, r - r // 12)))
        if n_cols == 2:
            bounds[i, 1] = FREE if i % 3 == 2 else (i % 5, i % 5 + 1)
    return bounds, q


@pytest.mark.parametrize("n_cols", [1, 2], ids=["one_column", "two_columns"])
@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("q", [1, 3, 11], ids=["Q1", "Q4", "Q16"])
def test_pruned_ranges_answer_as_the_full_range_does(segments, q, kind,
                                                     order, n_cols):
    s = segments(kind, order)
    bounds, q = _rows(s, q, n_cols, seed=q)
    crange = np.zeros((len(bounds), 2), np.int32)
    crange[:q] = _chunk_ranges(bounds[:q], s.zones(n_cols))
    full = np.zeros_like(crange)
    full[:q, 1] = s.nc                    # every row, every chunk
    got = s.run(bounds, crange)
    for a, b, c in zip(got, s.run(bounds, full), s.brute(bounds)):
        assert a.dtype == b.dtype == c.dtype == np.int32
        assert np.array_equal(a, b) and np.array_equal(a, c)
    assert np.all(crange[q:] == 0)        # the padding rows run nothing
    ran = (crange[:, 1] - crange[:, 0])[:q]
    wide = (bounds[:q, 0, 0] < 0) | (bounds[:q, 0, 1] - bounds[:q, 0, 0]
                                     > s.n_ranks // 2)
    gone = bounds[:q, 0, 1] <= np.maximum(bounds[:q, 0, 0], 0)
    assert np.all(ran[gone] == 0) and np.all(ran[wide] == s.nc)
    narrow = ~wide & ~gone & (bounds[:q, 0, 0] < s.n_ranks)
    if order == "ordered":
        # about a twelfth of the ranks: a twelfth of the chunks and the two
        # the range's ends fall in
        assert np.all(ran[narrow] <= s.nc // 12 + 2)
    else:
        # no order to use: a range of many ranks meets every chunk, and
        # the answer above was the same
        many = narrow & (bounds[:q, 0, 1] - bounds[:q, 0, 0] > 100)
        assert np.all(ran[many] >= s.nc - 1)


@pytest.mark.parametrize("kind", ["identity", "gathered"])
def test_a_range_that_ends_mid_chunk_and_one_that_misses(segments, kind):
    """On a column in order the range is the chunks the bounds fall in, to
    the chunk; a rank interval with nothing in it is the empty range."""
    s = segments(kind, "ordered")
    rank = s.cols[0].host[s.docs]                 # the pairs' ranks, in order
    lo, hi = int(rank[3 * AGG_PAIR_GRAN + 17]), int(rank[5 * AGG_PAIR_GRAN + 500])
    bounds = np.asarray([[(lo, hi)], [(s.n_ranks, s.n_ranks)], [(0, 0)],
                         [(hi, hi + 1)]], np.int32)
    crange = _chunk_ranges(bounds, s.zones(1))
    # ranks repeat: the range starts in the chunk that holds the first pair
    # of rank `lo` and ends with the last pair under `hi`
    first = int(np.searchsorted(rank, lo, "left")) // AGG_PAIR_GRAN
    last = (int(np.searchsorted(rank, hi, "left")) - 1) // AGG_PAIR_GRAN
    assert crange[0].tolist() == [first, last + 1]
    assert first <= 3 and last == 5
    assert crange[1].tolist() == [0, 0] and crange[2].tolist() == [0, 0]
    assert crange[3, 1] - crange[3, 0] in (1, 2)
    got = s.run(bounds, crange)
    for a, c in zip(got, s.brute(bounds)):
        assert np.array_equal(a, c)
    assert got[1][0] > 0 and got[1][1] == 0 and not got[0][1].any()
    # every row's range empty: the program, if asked (a gathered layout's
    # total may count documents that are in no pair), runs one gated step
    none = np.zeros((4, 2), np.int32)
    miss = np.repeat(bounds[1:2], 4, axis=0)
    counts, totals = s.run(miss, none)
    assert not counts.any() and not totals.any()


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("order", ORDERS + ("runs",))
@pytest.mark.parametrize("kind", ["identity", "gathered"])
def test_the_planner_never_drops_a_chunk_that_holds_a_match(kind, order,
                                                            seed):
    """Random bounds on two columns against the brute-force mask: every
    chunk with a kept pair lies inside the row's range, and the range's
    two end chunks pass the zone test themselves (it is the BOUNDING
    range of the chunks kept, no wider). `runs`: sorted runs of 700
    documents in shuffled order, neither in order nor without any."""
    s = _Segment(kind, "ordered", seed=seed + 10)
    if order != "ordered":
        rng = np.random.default_rng(seed)
        runs = np.array_split(np.arange(s.n), s.n // 700)
        perm = (rng.permutation(s.n) if order == "shuffled" else
                np.concatenate([runs[j] for j in rng.permutation(len(runs))]))
        for c in s.cols:
            c.host = np.ascontiguousarray(c.host[perm])
        if kind == "gathered":      # the pair docs are those with a value
            s.docs = np.flatnonzero(s.cols[0].host >= 0).astype(np.int32)
            d, sg, ct0, ct1 = _pack_pairs(s.docs, s.bucket)
            s.lay = _AggLayout("uniq", s.n, [d, sg, ct0, ct1], s.lay.meta)
    zones = s.zones(2)
    rng = np.random.default_rng(seed + 100)
    q = 64
    bounds = np.zeros((q, 2, 2), np.int32)
    lo = rng.integers(-3, s.n_ranks + 3, q)
    bounds[:, 0, 0] = lo
    bounds[:, 0, 1] = lo + rng.integers(0, s.n_ranks // 3, q) * (
        rng.random(q) < 0.9)
    bounds[::7, 0] = FREE
    st = rng.integers(0, 5, q)
    bounds[:, 1, 0], bounds[:, 1, 1] = st, st + rng.integers(0, 3, q)
    bounds[::3, 1] = FREE
    crange = _chunk_ranges(bounds, zones)
    keep = s.keep(bounds)[:, s.docs]                       # [q, pairs]
    pad = np.zeros((q, s.nc * AGG_PAIR_GRAN - keep.shape[1]), bool)
    holds = np.concatenate([keep, pad], axis=1).reshape(
        q, s.nc, AGG_PAIR_GRAN).any(axis=2)                # [q, chunks]
    for i in range(q):
        c0, c1 = crange[i]
        hit = np.flatnonzero(holds[i])
        if len(hit):
            assert c0 <= hit[0] and hit[-1] < c1, (i, bounds[i], c0, c1)
        if c1 > c0:
            for c in (c0, c1 - 1):
                for f in range(2):
                    assert zones[f][1][c] >= bounds[i, f, 0]
                    assert zones[f][0][c] < bounds[i, f, 1]
        else:
            assert (c0, c1) == (0, 0) and not len(hit)
    assert 0 < (crange[:, 1] > crange[:, 0]).sum() < q     # both kinds drawn


def test_a_batch_of_different_ranges_is_one_program(segments):
    """Rows of one batch with the whole layout, a twelfth, one chunk and
    nothing: each row's answer is its solo answer, and no range builds a
    program of its own (the chunk axis' length is read on the device)."""
    s = segments("identity", "ordered")
    r = s.n_ranks
    bounds = np.asarray([[FREE], [(r // 2, r // 2 + r // 12)],
                         [(r // 5, r // 5 + 3)], [(r, r)]], np.int32)
    crange = _chunk_ranges(bounds, s.zones(1))
    spans = (crange[:, 1] - crange[:, 0]).tolist()
    assert spans[0] == s.nc and 1 <= spans[1] <= s.nc // 12 + 2
    assert spans[2] in (1, 2) and spans[3] == 0
    counts, totals = s.run(bounds, crange)
    want = s.brute(bounds)
    assert np.array_equal(counts, want[0]) and np.array_equal(totals, want[1])
    built = kernels.agg_filter_counts._cache_size()
    for order in ([3, 2, 1, 0], [1, 1, 3, 2], [2, 3, 3, 3], [3, 3, 3, 3]):
        c, t = s.run(bounds[order], crange[order])
        assert np.array_equal(c, want[0][order])
        assert np.array_equal(t, want[1][order])
    assert kernels.agg_filter_counts._cache_size() == built


def test_a_zone_map_is_built_once_and_shared_by_a_fields_layouts(segments):
    s = segments("gathered", "ordered")
    cmin, cmax = _zone_map(s.seg, "ts", s.lay, s.cols[0])
    assert cmin.shape == cmax.shape == (s.nc,) and cmin.dtype == np.int32
    # the column at the layout's pair docs, a chunk at a time, pads left out
    rank = s.cols[0].host[s.docs]
    for c in (0, s.nc // 2, s.nc - 1):
        part = rank[c * AGG_PAIR_GRAN:(c + 1) * AGG_PAIR_GRAN]
        assert (cmin[c], cmax[c]) == (part.min(), part.max())
    assert len(rank) % AGG_PAIR_GRAN != 0          # the last chunk is padded
    # a minute layout of the same field has the same pair docs: the same
    # map, not another (keyed by the bucketed field and the column)
    minute = _AggLayout("uniq", s.n, list(_pack_pairs(s.docs, s.bucket * 3)),
                        dict(s.lay.meta))
    assert _zone_map(s.seg, "ts", minute, s.cols[0])[0] is cmin
    assert "aggdev:zone:ts:ts" in s.seg._device
    assert all(k.startswith("aggdev:zone:ts:") for k in s.seg._device)
    # host arrays: nothing joins the engine's ledger regions
    assert all(isinstance(a, np.ndarray) for a in (cmin, cmax))


def test_a_chunk_of_valueless_documents_and_a_chunk_of_pads():
    """A document without a value has rank -1, as the device compares it:
    a constrained row drops a chunk of such documents, an unconstrained
    row keeps it (its documents count). A chunk of pad pairs alone is met
    by no interval."""
    n = 3 * AGG_PAIR_GRAN
    rank = np.arange(n, dtype=np.int32)
    rank[AGG_PAIR_GRAN:2 * AGG_PAIR_GRAN] = -1
    col = _FilterColumn("f", np.arange(float(n)), rank)
    docs = np.arange(n, dtype=np.int32)
    d, s, ct0, ct1 = _pack_pairs(docs, docs // 8)
    lay = _AggLayout("uniq", n, [d, s, ct0, ct1],
                     {"p": n, "n_segments": n // 8, "identity": True})
    seg = types.SimpleNamespace(_device={})
    cmin, cmax = _zone_map(seg, "f", lay, col)
    assert (cmin[1], cmax[1]) == (-1, -1)
    bounds = np.asarray([[FREE], [(0, n)], [(5, 9)], [(n - 3, n)]], np.int32)
    assert _chunk_ranges(bounds, [(cmin, cmax)]).tolist() == [
        [0, 3], [0, 3], [0, 1], [2, 3]]
    # the bounding range of chunks 0 and 2 holds chunk 1: the kernel's own
    # comparison drops its documents
    counts, totals = kernels.agg_filter_counts(
        jnp.asarray(bounds), jnp.asarray(_chunk_ranges(bounds, [(cmin, cmax)])),
        (col.dev,), lay.dev, p=n, n_out=AGG_SEG_TILE, identity=True)
    assert np.asarray(totals).tolist() == [n, 2 * AGG_PAIR_GRAN, 4, 3]
    assert np.asarray(counts).sum(axis=1).tolist() == [
        n, 2 * AGG_PAIR_GRAN, 4, 3]
    # a layout of one real pair: its padding fills the chunk, and a layout
    # of none is a chunk of pads no interval meets
    for pairs, want in ((1, [0, 1]), (0, [0, 0])):
        d, s, ct0, ct1 = _pack_pairs(docs[:pairs], docs[:pairs])
        lay = _AggLayout("uniq", n, [d, s, ct0, ct1], {"p": len(d)})
        zone = _zone_map(types.SimpleNamespace(_device={}), "f", lay, col)
        assert _chunk_ranges(bounds[:1], [zone]).tolist() == [want]


def test_the_old_route_runs_every_chunk_as_before():
    """`agg_segment_counts` / `agg_two_level_counts` (a host mask a
    collect: terms, sub-aggregations) have no bounds to prune by: the full
    range, the count of every pair."""
    rng = np.random.default_rng(5)
    n, n_seg = 5_000, 300
    seg_of = np.sort(rng.integers(0, n_seg, n)).astype(np.int32)
    d, s, ct0, ct1 = _pack_pairs(np.arange(n, dtype=np.int32), seg_of)
    blob = jnp.asarray(np.concatenate([d, s, ct0, ct1]))
    mask = rng.random((4, n)) < 0.3
    got = np.asarray(kernels.agg_segment_counts(
        jnp.asarray(mask), blob, p=len(d), n_segments=n_seg))
    want = np.stack([np.bincount(seg_of[m], minlength=n_seg) for m in mask])
    assert np.array_equal(got, want)
    two = jnp.asarray(np.concatenate([d, s, ct0, ct1] * 2))
    dc, vc = kernels.agg_two_level_counts(
        jnp.asarray(mask), two, pd=len(d), pm=len(d), n_segments=n_seg)
    assert np.array_equal(np.asarray(dc), want)
    assert np.array_equal(np.asarray(vc), want)


def test_no_knob_was_added():
    """The pruning observes the request's bounds and the columns' zones:
    no setting, no environment variable turns it on or off."""
    import inspect
    import re

    knobs = set(re.findall(r'knob\("([A-Z_]+)"', inspect.getsource(agg_device)))
    assert knobs == {"ES_TPU_AGG", "ES_TPU_AGG_HBM_FRAC", "ES_TPU_TURBO_HBM"}
