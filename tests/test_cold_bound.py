"""PR 43: the survivor bound runs BEFORE the cold side is enumerated.

`_collect_gather` hands the pair's cold postings back as they lie (no
`np.unique`), `_cold_survivors` tests them against max(k-th exact total of
the picked rows, the cold side's own k-th lower bound), and only what it
keeps is made distinct, given its impacts and exact-rescored. Held here:
the answers are the host's bit for bit on every query shape the bound
meets (cold-only, one to four column terms, ties at the k-th score,
deleted docs among the best cold docs, a doc in several cold lists, fewer
than k hits, slack > 0 and the slack-0 host walk, a negative boost,
boosts of 50 to 1,000 whose f32 rounding passes any fixed margin), the
survivor set holds every doc that reaches the final k-th score, the two
counters say what was enumerated and what survived, and the bool route
(which shares the collect only) keeps its answers and its books.

Runs on the host-simulated CPU mesh of tests/conftest.py (Pallas kernels
interpret on CPU)."""

import numpy as np
import pytest

from elasticsearch_tpu.parallel import turbo as turbo_mod
from elasticsearch_tpu.parallel.spmd import build_stacked_bm25
from elasticsearch_tpu.parallel.turbo import TurboBM25

from test_turbo_bitset import _Seg, _pcorpus, _turbo, _fused, _assert_identical

pytestmark = pytest.mark.multidevice

K = 10
COLD_DF = 800     # _pcorpus(3000, 40, s): t0..t7 own a column, t8.. are cold
COLD_KEYS = ("cold_enum_docs", "cold_survivor_docs")


def _shapes():
    """Queries by the number of column terms (0 to 4) beside one to three
    cold terms; cold terms that share docs; a boost under 1 and over 1."""
    qs = []
    for n_col in range(5):
        cols = [(f"t{i}", 1.0) for i in range(n_col)]
        qs.append(cols + [("t30", 1.0)])
        qs.append(cols + [("t12", 0.6), ("t21", 1.0)])
        qs.append(cols + [("t9", 1.0), ("t10", 2.0), ("t11", 1.0)])
    qs.append([("t38", 1.0), ("absent", 1.0)])
    return qs


def _spy(t):
    """Record what `_cold_survivors` was handed and what it kept, a call."""
    seen = []
    real = t._cold_survivors

    def survivors(docs_raw, vals_raw, *rest):
        sel = real(docs_raw, vals_raw, *rest)
        seen.append((np.asarray(docs_raw).copy(), sel.copy(), rest))
        return sel

    t._cold_survivors = survivors
    return seen


def _assert_survivors_hold_the_top(t, qs, got, seen):
    """Every live doc of a query's cold lists whose exact total reaches
    the final k-th score is among the survivors (the picked rows' docs
    come by the other road and need not be)."""
    scores = np.asarray(got[0])
    cold_qs = [qi for qi, q in enumerate(qs) if any(
        t._term(term) is not None and term not in t._slot_of
        for term, _b in q)]
    assert len(seen) == len(cold_qs)
    for qi, (docs_raw, sel, _rest) in zip(cold_qs, seen):
        qterms = [(term, b, t._term(term)) for term, b in qs[qi]
                  if t._term(term) is not None]
        docs = np.unique(docs_raw).astype(np.int64)
        docs = docs[t._live_host[docs] > 0]
        totals = t._exact_scores(qterms, docs)
        n_hits = int(np.count_nonzero(scores[qi] > 0))
        kth = float(scores[qi][K - 1]) if n_hits >= K else 0.0
        must = docs[(totals >= kth) & (totals > 0)]
        kept = np.unique(docs_raw[sel])
        assert np.isin(must, kept).all(), f"query {qi} lost a top doc"
        # liveness was tested on the raw side
        assert (t._live_host[kept] > 0).all()
        # a surviving doc keeps every one of its cold postings
        assert np.isin(docs_raw, kept).sum() == len(sel)


@pytest.mark.parametrize("seed", [7, 11, 23])
def test_the_bound_answers_the_hosts_bits_and_keeps_the_top(seed):
    t = _turbo(_pcorpus(3000, 40, seed), 3000, cold_df=COLD_DF)
    qs = _shapes()
    seen = _spy(t)
    got = t.search_many([qs], k=K)[0]
    _assert_identical(got, t.search_many_host([qs], k=K)[0],
                      f"seed {seed} vs host")
    assert t.stats["sparse_fallbacks"] == 0 and t.stats["fallbacks"] == 0
    # slack > 0 on the gathered route
    assert all(rest[0] > 0 for _d, _s, rest in seen)
    _assert_survivors_hold_the_top(t, qs, got, seen)


@pytest.mark.parametrize("k", [3, 10, 25])
def test_ties_at_the_kth_score_are_kept_whole(k):
    """Four copies of 750 docs: every score comes four times, k = 3, 10
    and 25 all cut a group of equals, and the (score desc, doc asc) rank
    needs every one of the group."""
    t = _turbo(_pcorpus(750, 40, 5, reps=4), 3000, cold_df=COLD_DF)
    qs = _shapes()
    got = t.search_many([qs], k=k)[0]
    _assert_identical(got, t.search_many_host([qs], k=k)[0], f"ties k={k}")
    # the (k + 1)-th best doc, which is left out, scores what the k-th does
    wide = np.asarray(t.search_many_host([qs], k=k + 1)[0][0])
    assert np.count_nonzero((wide[:, k] > 0)
                            & (wide[:, k] == wide[:, k - 1])) > len(qs) // 2


def test_deleted_docs_among_the_best_cold_docs_do_not_raise_the_bound():
    """The best cold docs of every query are deleted: a bound ranked over
    dead postings would sit above the true k-th and cut live hits."""
    fp = _pcorpus(3000, 40, 7)
    qs = _shapes()
    first = _turbo(fp, 3000, cold_df=COLD_DF)
    live = np.ones(3000, bool)
    for s, d in zip(*map(np.asarray, first.search_many_host([qs], k=K)[0])):
        live[d[s > 0]] = False
    stacked = build_stacked_bm25([_Seg(3000, fp)], "body",
                                 live_masks=[live], serve_only=True)
    t = TurboBM25(stacked, hbm_budget_bytes=64 << 20, cold_df=COLD_DF)
    seen = _spy(t)
    got = t.search_many([qs], k=K)[0]
    _assert_identical(got, t.search_many_host([qs], k=K)[0], "deleted")
    assert not np.isin(np.asarray(got[1])[np.asarray(got[0]) > 0],
                       np.flatnonzero(~live)).any()
    _assert_survivors_hold_the_top(t, qs, got, seen)


def test_fewer_than_k_hits_keep_every_hit():
    """A term with fewer than k postings, alone and beside column terms:
    no rank to take a bound from, every live doc survives."""
    t = _turbo(_pcorpus(3000, 600, 3), 3000, cold_df=COLD_DF)
    rare = [f"t{i}" for i in range(600)
            if t._term(f"t{i}") is not None and t._term(f"t{i}").df < K]
    assert len(rare) >= 2
    qs = [[(rare[0], 1.0)], [(rare[0], 1.0), (rare[1], 1.0)],
          [("t0", 1.0), (rare[1], 1.0)]]
    seen = _spy(t)
    got = t.search_many([qs], k=K)[0]
    _assert_identical(got, t.search_many_host([qs], k=K)[0], "short")
    assert np.count_nonzero(np.asarray(got[0])[0]) == t._term(rare[0]).df
    for docs_raw, sel, _rest in seen[:2]:
        assert len(sel) == len(docs_raw)
    _assert_survivors_hold_the_top(t, qs, got, seen)


@pytest.mark.parametrize("route", ["host_walk", "lost_gather"])
def test_the_slack_0_routes_take_the_same_finish(route, monkeypatch):
    """ES_TPU_SPARSE=0 (the `_cold_contrib` walk) and a gather that was
    lost (`h.host`) hand `_cold_survivors` exact sums with slack 0."""
    fp = _pcorpus(3000, 40, 7)
    qs = _shapes()
    if route == "host_walk":
        monkeypatch.setenv("ES_TPU_SPARSE", "0")
    t = _turbo(fp, 3000, cold_df=COLD_DF)
    if route == "lost_gather":
        t._launch_gather = lambda g: None
    seen = _spy(t)
    got = t.search_many([qs], k=K)[0]
    _assert_identical(got, t.search_many_host([qs], k=K)[0], route)
    assert all(rest[0] == 0.0 for _d, _s, rest in seen)
    assert (t.stats["cold_queries"] > 0) == (route == "host_walk")
    assert (t.stats["sparse_fallbacks"] > 0) == (route == "lost_gather")
    assert 0 < t.stats["cold_survivor_docs"] < t.stats["cold_enum_docs"]
    _assert_survivors_hold_the_top(t, qs, got, seen)


def _boosted(lo, hi, seed):
    """`_shapes()` with every boost drawn from [lo, hi): totals in the
    hundreds and thousands, where one f32 rounding is 1e-5 to 1e-4."""
    rng = np.random.default_rng(seed)
    return [[(term, float(rng.uniform(lo, hi))) for term, _b in q]
            for q in _shapes()]


@pytest.mark.parametrize("route", ["gathered", "host_walk", "lost_gather"])
@pytest.mark.parametrize("lo,hi", [(50, 100), (300, 1000)])
def test_large_boosts_widen_the_margin_with_the_scores(route, lo, hi,
                                                       monkeypatch):
    """The bound's margin for the exact scorer's f32 arithmetic grows with
    the query's boosts (`_f32_err`): on the slack-0 routes it is the only
    cover between the f64 cold sums and the f32 exact totals, and a fixed
    1e-5 is passed by ONE rounding of a total over 170. One corpus has
    every score four times (750 docs four times over), the other 3,000
    docs of their own with many scores close by; the answers are the
    host's bits and no doc that reaches the final k-th is dropped."""
    if route == "host_walk":
        monkeypatch.setenv("ES_TPU_SPARSE", "0")
    for seed, reps in ((5, 4), (9, 1)):
        t = _turbo(_pcorpus(3000 // reps, 40, seed, reps=reps), 3000,
                   cold_df=COLD_DF)
        if route == "lost_gather":
            t._launch_gather = lambda g: None
        qs = _boosted(lo, hi, seed)
        seen = _spy(t)
        got = t.search_many([qs], k=K)[0]
        _assert_identical(got, t.search_many_host([qs], k=K)[0],
                          f"{route} boosts {lo}-{hi} seed {seed}")
        assert (np.asarray(got[0])[:, 0] > 2 * lo).any()
        # (slack, cold terms, col_const, col_floor, f32_err, kth_0, k)
        assert all(rest[4] > 1e-5 + 2.0 ** -23 * lo for _d, _s, rest in seen)
        assert all((rest[0] == 0.0) == (route != "gathered")
                   for _d, _s, rest in seen)
        _assert_survivors_hold_the_top(t, qs, got, seen)
        assert t.stats["cold_survivor_docs"] < t.stats["cold_enum_docs"]


@pytest.mark.parametrize("seed", range(4))
def test_f32_err_covers_the_exact_scorer_at_any_boost(seed):
    """`_f32_err` against the scorer itself: f32 totals accumulated term
    at a time (as `_exact_scores` does) against the same sum in f64, for
    boosts up to 1,000 and up to six terms."""
    rng = np.random.default_rng(seed)
    for n_terms in range(1, 7):
        w = rng.uniform(0.5, 8.0, n_terms) * rng.choice(
            [1.0, 50.0, 1000.0], n_terms) * rng.choice([1.0, -1.0], n_terms,
                                                       p=[0.8, 0.2])
        smax = rng.uniform(0.5, 2.2, n_terms)
        imp = (rng.random((n_terms, 4000)) * smax[:, None]).astype(np.float32)
        imp[:, :64] = smax.astype(np.float32)[:, None] * (
            rng.random((n_terms, 64)) > 0.5)
        total = np.zeros(4000, np.float32)
        for wt, row in zip(w, imp):
            total = total + np.float32(wt) * row
        real = (w[:, None] * imp.astype(np.float64)).sum(axis=0)
        most = float((np.abs(w) * smax.astype(np.float32)).sum())
        err = np.abs(total.astype(np.float64) - real).max()
        assert err <= turbo_mod._f32_err(n_terms, most) - 1e-5
        if most > 1000:
            assert err > 1e-5          # what a fixed margin does not cover


def test_a_fixed_margin_would_drop_a_tie_at_a_boost_of_1000():
    """The case a margin of 1e-5 loses, made by hand: one cold term of
    weight 1,000 over 200 docs whose impacts are consecutive f32 values
    under 2. Their real products lie 1.19e-4 apart; the f32 products have
    1.22e-4 between neighbours, so every few dozen docs two of them round
    to the SAME f32 total. Where such a pair straddles rank k, the (k +
    1)-th doc ties the k-th and must survive; its f64 value is 1.19e-4
    under the k-th's."""
    w, k = 1000.0, None
    imp = np.float32(2.0) - np.arange(1, 201, dtype=np.float32) * np.float32(
        2.0 ** -23)
    assert len(np.unique(imp)) == 200
    totals = np.float32(w) * imp                     # as `_exact_scores`
    vals = w * imp.astype(np.float64)                # as `_cold_contrib`
    k = 1 + int(np.flatnonzero(totals[1:] == totals[:-1])[0])
    assert 1 < k < 100 and totals[k] == totals[k - 1]
    assert vals[k - 1] - vals[k] > 1e-4
    docs_raw = np.arange(200, dtype=np.int32)
    live = _Live(np.ones(200, bool))

    def kept(f32_err):
        return TurboBM25._cold_survivors(
            live, docs_raw, vals, 0.0, 1, 0.0, 0.0, f32_err, 0.0, k)

    must = np.flatnonzero(totals >= totals[k - 1])
    assert len(must) == k + 1
    assert not np.isin(must, kept(1e-5)).all()       # the old margin
    sel = kept(turbo_mod._f32_err(1, w * float(imp[0])))
    assert np.isin(must, sel).all() and len(sel) <= k + 10


def test_a_negative_boost_lowers_the_floor_not_the_answers():
    """A column term with a negative boost takes from a doc's total: the
    cold side's lower bound counts the most it can take (`col_floor`;
    without it the first two queries lose hits), and the upper bound
    counts nothing of it (`col_const` took w * smax < 0 before this PR,
    and the third and fourth queries lost hits)."""
    t = _turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)
    qs = [[("t7", -1.0), ("t30", 1.0)], [("t7", -0.5), ("t9", 1.0)],
          [("t6", -2.0), ("t12", 1.0)], [("t5", -2.0), ("t35", 1.0)],
          [("t3", 1.0), ("t30", -1.0), ("t33", 1.0)]]
    seen = _spy(t)
    got = t.search_many([qs], k=K)[0]
    _assert_identical(got, t.search_many_host([qs], k=K)[0], "negative")
    # (slack, cold terms, col_const, col_floor, f32_err, kth_0, k) a call
    assert all(rest[2] == 0 and rest[3] < 0 for _d, _s, rest in seen[:4])
    assert seen[4][2][2] > 0 and seen[4][2][3] == 0
    _assert_survivors_hold_the_top(t, qs, got, seen)


def test_the_fused_path_takes_the_bound_a_partition():
    eng = _fused([(1500, _pcorpus(1500, 40, 1)),
                  (900, _pcorpus(900, 56, 2)),
                  (2100, _pcorpus(2100, 40, 3))], cold_df=300)
    qs = _shapes()
    want = eng._merge3([t.search_many_host([qs], k=K)[0]
                        for t in eng.turbos], len(qs), K)
    got = eng.search_many([qs], k=K)[0]
    for g, w, name in zip(got, want, ("scores", "parts", "ords")):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
    for t in eng.turbos:
        assert 0 < t.stats["cold_survivor_docs"] < t.stats["cold_enum_docs"]


def test_the_counters_count_each_pair_once():
    """`cold_enum_docs` rises by the RAW postings of each pair's cold
    terms, `cold_survivor_docs` by the distinct docs the bound kept, on
    the engine and on the node alike."""
    t = _turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)
    qs = _shapes()
    t.search_many([qs], k=K)                 # slices in place
    seen = _spy(t)
    node0 = turbo_mod.node_sparse_stats()
    e0 = {key: t.stats[key] for key in COLD_KEYS}
    t.search_many([qs], k=K)
    rise = {key: t.stats[key] - e0[key] for key in COLD_KEYS}
    node1 = turbo_mod.node_sparse_stats()
    raw = sum(t._term(term).df for q in qs for term, _b in q
              if t._term(term) is not None and term not in t._slot_of)
    assert rise["cold_enum_docs"] == raw == sum(
        len(d) for d, _s, _r in seen)
    assert rise["cold_survivor_docs"] == sum(
        len(np.unique(d[s])) for d, s, _r in seen)
    assert 0 < rise["cold_survivor_docs"] <= rise["cold_enum_docs"]
    assert {key: node1[key] - node0[key] for key in COLD_KEYS} == rise


def test_a_cold_only_query_keeps_fewer_than_every_live_doc():
    """The old rule kept every live doc of a cold-only query; the cold
    side's own k-th bound leaves a handful around the top k."""
    t = _turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)
    qs = [[("t30", 1.0), ("t35", 1.0)], [("t31", 2.0)]]
    seen = _spy(t)
    got = t.search_many([qs], k=K)[0]
    _assert_identical(got, t.search_many_host([qs], k=K)[0], "cold-only")
    for docs_raw, sel, _rest in seen:
        old_rule = len(np.unique(docs_raw))      # every doc is live here
        kept = len(np.unique(docs_raw[sel]))
        assert K <= kept < old_rule // 2, (kept, old_rule)
    assert t.stats["cold_survivor_docs"] == sum(
        len(np.unique(d[s])) for d, s, _r in seen)


class _Live:
    """What `_cold_survivors` reads of its engine."""

    def __init__(self, live):
        self._live_host = live.astype(np.float32)


@pytest.mark.parametrize("seed", range(6))
def test_cold_survivors_is_a_superset_for_any_totals_inside_the_bounds(seed):
    """The function alone against a brute force: docs with cold values,
    duplicates (a doc in up to three lists), deletions, exact totals drawn
    anywhere inside [vals - slack, vals + slack + col_const]: whatever
    the totals are, every live doc that reaches the true k-th survives,
    and every posting of a survivor with it."""
    rng = np.random.default_rng(seed)
    n_docs, n_terms, k = 400, 3, 10
    slack, col_const = float(rng.choice([0.0, 0.02])), float(
        rng.choice([0.0, 0.5, 3.0]))
    vals = np.round(rng.gamma(2.0, 1.0, n_docs), 1)       # ties among them
    times = rng.integers(1, n_terms + 1, n_docs)
    docs_raw = np.repeat(np.arange(n_docs, dtype=np.int32), times)
    order = rng.permutation(len(docs_raw))
    docs_raw = docs_raw[order]
    live = rng.random(n_docs) > 0.2
    live[np.argsort(-vals)[:5]] = False                   # the best are dead
    totals = (vals + rng.uniform(-slack, slack, n_docs)
              + col_const * rng.random(n_docs)).astype(np.float32)
    kth_true = np.sort(totals[live])[-k]
    kth_0 = float(rng.choice([0.0, kth_true * 0.5]))
    sel = TurboBM25._cold_survivors(
        _Live(live), docs_raw, vals[docs_raw].astype(np.float64), slack,
        n_terms, col_const, 0.0, 1e-5, kth_0, k)
    assert np.array_equal(sel, np.sort(sel))
    kept = np.unique(docs_raw[sel])
    assert live[kept].all()
    assert np.isin(np.flatnonzero(live & (totals >= kth_true)), kept).all()
    assert np.isin(docs_raw, kept).sum() == len(sel)
    if col_const < 1.0:
        assert len(kept) < np.count_nonzero(live) // 2


# ---------------------------------------------------------------------------
# the bool route shares `_collect_gather` and nothing else of this
# ---------------------------------------------------------------------------

BOOL_SPECS = [
    {"must": [("t1", 1.0)], "should": [("t30", 1.0), ("t35", 0.5)]},
    {"filter": ["t4"], "should": [("t38", 1.0)]},
    {"must": [("t2", 1.0)], "should": [("t8", 1.0), ("t31", 1.0)]},
    {"must": [("t2", 1.0)], "should": [("t12", 1.0), ("t21", 1.0)]},
    {"should": [("t28", 1.0), ("t36", 2.0)]},      # all-cold scoring
    {"must": [("t25", 1.0), ("t3", 1.0)], "must_not": ["t33"]},
]


def test_the_bool_route_keeps_its_answers_and_its_books():
    """The SHOULD side reads the collect through the old shape (distinct
    docs) and keeps the old bound: a cold contribution is no lower bound
    on the score of a hit that must also match the required clauses. Its
    counters read what they read before this PR; the match finish's two
    stay where they were."""
    t = _turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)
    got = t.search_bool(BOOL_SPECS, k=K)
    # (the parent commit's readings of the same six specs)
    books = {key: t.stats[key] for key in (
        "sparse_queries", "sparse_gather_launches", "sparse_fallbacks",
        "cold_queries", "fallbacks", "bool_device", "bool_cold_lead",
        "bool_host", "finish_bulk_pairs", "finish_pair_fallbacks")}
    assert books == {"sparse_queries": 5, "sparse_gather_launches": 5,
                     "sparse_fallbacks": 0, "cold_queries": 0,
                     "fallbacks": 0, "bool_device": 6, "bool_cold_lead": 1,
                     "bool_host": 0, "finish_bulk_pairs": 0,
                     "finish_pair_fallbacks": 0}
    assert t.stats["cold_enum_docs"] == t.stats["cold_survivor_docs"] == 0
    _assert_identical(got, t.search_bool_host(BOOL_SPECS, k=K), "bool")


def test_the_raw_collect_is_cold_contrib_spread_over_the_postings():
    """`_collect_gather` hands back the terms' lists laid end to end with
    the doc's whole contribution at every occurrence: made distinct it is
    `_cold_contrib`'s (docs, contrib) within the gather's slack."""
    t = _turbo(_pcorpus(3000, 40, 7), 3000, cold_df=COLD_DF)
    cold = [(term, b, t._term(term)) for term, b in
            (("t12", 1.0), ("t21", 0.5), ("t30", 1.0))]
    t.ensure_columns([term for term, _b, _i in cold])
    h = t._start_gathers([(0, cold)], False)[0]
    docs_raw, vals_raw, slack = t._collect_gather(h)
    assert not h.host and slack > 0
    assert len(docs_raw) == sum(i.df for _t, _b, i in cold)
    want_u, want, inv = t._cold_contrib(cold)
    assert np.array_equal(docs_raw, want_u[inv])
    u, first = np.unique(docs_raw, return_index=True)
    assert np.abs(vals_raw[first] - want).max() <= slack
    # every occurrence of a doc reads the same accumulator cell
    assert np.array_equal(vals_raw, vals_raw[first][inv])
    # the host walk hands back the same layout, exact
    docs_h, vals_h, slack_h = t._cold_raw(cold)
    assert slack_h == 0.0 and np.array_equal(docs_h, docs_raw)
    assert np.array_equal(vals_h, want[inv])
