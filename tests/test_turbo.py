"""TurboBM25 (int8 column cache + Pallas kernels) correctness tests.

Runs on the CPU mesh via pallas interpret mode (tests/conftest.py forces
JAX_PLATFORMS=cpu); differential-checked against a brute-force scorer with
the reference accumulation order.
"""

import numpy as np
import pytest

from elasticsearch_tpu.index.segment import build_field_postings
from elasticsearch_tpu.ops import bm25_idf
from elasticsearch_tpu.parallel.spmd import build_stacked_bm25
from elasticsearch_tpu.parallel.turbo import COLD_DF, TurboBM25


class _Seg:
    def __init__(self, n_docs, fp):
        self.n_docs = n_docs
        self.postings = {"body": fp}
        self.vectors = {}


def _corpus(n_docs=3000, vocab=300, seed=0):
    rng = np.random.default_rng(seed)
    probs = 1.0 / np.arange(1, vocab + 1) ** 1.1
    probs /= probs.sum()
    lens = rng.integers(4, 20, size=n_docs).astype(np.int64)
    tokens = rng.choice(vocab, size=int(lens.sum()), p=probs).astype(np.int64)
    names = [f"t{i}" for i in range(vocab)]
    tok_docs = np.repeat(np.arange(n_docs, dtype=np.int64), lens)
    fp = build_field_postings("body", lens, tok_docs, tokens, names)
    return fp, probs, rng


def _agg(q):
    agg = {}
    for t in q:
        agg[t] = agg.get(t, 0.0) + 1.0
    return list(agg.items())


def _brute(fp, avgdl, total_docs, terms, k=10, live=None):
    """Reference scorer: term-at-a-time f32 accumulation in query order."""
    from elasticsearch_tpu.parallel.blockmax import _host_block_scores

    bs = _host_block_scores(fp, avgdl)
    dense = np.zeros(total_docs, np.float32)
    for t, boost in terms:
        o = fp.ord(t)
        if o < 0:
            continue
        w = np.float32(bm25_idf(total_docs, int(fp.doc_freq[o])) * boost)
        lo, hi = int(fp.post_start[o]), int(fp.post_start[o + 1])
        docs = fp.post_doc[lo:hi]
        start, cnt = int(fp.block_start[o]), int(fp.block_count[o])
        vals = bs[start: start + cnt].ravel()[: hi - lo]
        dense[docs] = dense[docs] + w * vals
    if live is not None:
        dense = np.where(live, dense, 0.0)
    docs = np.nonzero(dense > 0)[0]
    sel = np.lexsort((docs, -dense[docs]))[:k]
    return dense[docs[sel]], docs[sel].astype(np.int32)


@pytest.fixture(scope="module")
def engine():
    fp, probs, rng = _corpus()
    stacked = build_stacked_bm25([_Seg(3000, fp)], "body", serve_only=True)
    turbo = TurboBM25(stacked, hbm_budget_bytes=64 << 20)
    return fp, stacked, turbo, probs, rng


def test_cold_only_queries_exact(engine):
    fp, stacked, turbo, probs, rng = engine
    # all terms are cold at this corpus size (df < COLD_DF)
    assert all(int(df) < COLD_DF for df in fp.doc_freq)
    queries = [[f"t{a}", f"t{b}"] for a, b in
               rng.integers(0, 200, size=(16, 2))]
    (scores, ords), = [turbo.search(queries, k=10)]
    for qi, q in enumerate(queries):
        bs, bd = _brute(fp, stacked.avgdl, stacked.total_docs,
                        _agg(q), k=10)
        n = len(bd)
        assert np.array_equal(ords[qi][:n], bd), f"query {qi} docs"
        np.testing.assert_allclose(scores[qi][:n], bs, rtol=1e-6)


def test_colized_path_exact():
    # small dense corpus with COLD_DF forced low so columns engage

    fp, probs, rng = _corpus(n_docs=2000, vocab=50, seed=1)
    stacked = build_stacked_bm25([_Seg(2000, fp)], "body", serve_only=True)
    turbo = TurboBM25(stacked, hbm_budget_bytes=64 << 20, cold_df=10)
    queries = [[f"t{a}", f"t{b}"] for a, b in
               rng.integers(0, 50, size=(12, 2))]
    scores, ords = turbo.search(queries, k=10)
    for qi, q in enumerate(queries):
        bs, bd = _brute(fp, stacked.avgdl, stacked.total_docs,
                        _agg(q), k=10)
        n = len(bd)
        assert np.array_equal(ords[qi][:n], bd), f"query {qi} docs"
        np.testing.assert_allclose(scores[qi][:n], bs, rtol=1e-6)
    assert turbo.stats["builds"] > 0


def test_live_mask_filters_deleted():

    fp, probs, rng = _corpus(n_docs=1500, vocab=40, seed=2)
    live = np.ones(1500, bool)
    live[::3] = False
    stacked = build_stacked_bm25([_Seg(1500, fp)], "body",
                                 live_masks=[live], serve_only=True)
    turbo = TurboBM25(stacked, hbm_budget_bytes=64 << 20, cold_df=10)
    queries = [[f"t{a}", f"t{b}"] for a, b in
               rng.integers(0, 40, size=(6, 2))]
    scores, ords = turbo.search(queries, k=10)
    for qi, q in enumerate(queries):
        bs, bd = _brute(fp, stacked.avgdl, stacked.total_docs,
                        _agg(q),
                        k=10, live=live)
        n = len(bd)
        assert np.array_equal(ords[qi][:n], bd)
        np.testing.assert_allclose(scores[qi][:n], bs, rtol=1e-6)


def test_mixed_and_boosted_queries():

    fp, probs, rng = _corpus(n_docs=2500, vocab=120, seed=3)
    stacked = build_stacked_bm25([_Seg(2500, fp)], "body", serve_only=True)
    # head terms colized, tail cold -> mixed
    turbo = TurboBM25(stacked, hbm_budget_bytes=64 << 20, cold_df=60)
    queries = [[("t0", 2.0), (f"t{100 + i}", 1.0)] for i in range(8)]
    scores, ords = turbo.search(queries, k=10)
    for qi, q in enumerate(queries):
        bs, bd = _brute(fp, stacked.avgdl, stacked.total_docs, q, k=10)
        n = len(bd)
        assert np.array_equal(ords[qi][:n], bd), f"query {qi}"
        np.testing.assert_allclose(scores[qi][:n], bs, rtol=1e-6)


def test_missing_terms_and_empty():
    fp, probs, rng = _corpus(n_docs=1000, vocab=30, seed=4)
    stacked = build_stacked_bm25([_Seg(1000, fp)], "body", serve_only=True)
    turbo = TurboBM25(stacked, hbm_budget_bytes=64 << 20)
    scores, ords = turbo.search([["zzz_missing"], ["t0", "zzz_missing"]],
                                k=5)
    assert float(scores[0].sum()) == 0.0
    bs, bd = _brute(fp, stacked.avgdl, stacked.total_docs,
                    [("t0", 1.0)], k=5)
    assert np.array_equal(ords[1][: len(bd)], bd)


def test_capacity_overflow_degrades_to_cold():
    """A batch whose colizable terms exceed cache capacity must degrade
    gracefully (ADVICE r4): overflow terms score host-exact, results stay
    identical to brute force."""
    fp, probs, rng = _corpus(n_docs=3000, vocab=80, seed=7)
    stacked = build_stacked_bm25([_Seg(3000, fp)], "body", serve_only=True)
    # hbm budget floor is 32 slots; make nearly every term colizable so one
    # batch demands more columns than capacity
    turbo = TurboBM25(stacked, hbm_budget_bytes=1, cold_df=5)
    assert turbo.Hp == 32
    queries = [[f"t{i}", f"t{(i + 37) % 80}"] for i in range(40)]
    scores, ords = turbo.search(queries, k=10)
    assert turbo.stats["degraded"] > 0
    for qi, q in enumerate(queries):
        bs, bd = _brute(fp, stacked.avgdl, stacked.total_docs, _agg(q), k=10)
        n = len(bd)
        assert np.array_equal(ords[qi][:n], bd), f"query {qi}"
        np.testing.assert_allclose(scores[qi][:n], bs, rtol=1e-6)


def test_qc_sizes_rounded_and_intermediate_used():
    fp, probs, rng = _corpus(n_docs=1200, vocab=30, seed=8)
    stacked = build_stacked_bm25([_Seg(1200, fp)], "body", serve_only=True)
    turbo = TurboBM25(stacked, hbm_budget_bytes=64 << 20, cold_df=10,
                      qc_sizes=(3, 20, 64))
    # rounded up to ROWS_PER_STEP multiples, deduped, ascending
    assert turbo.qc_sizes == (8, 24, 64)
    queries = [[f"t{i % 30}", f"t{(i + 11) % 30}"] for i in range(17)]
    scores, ords = turbo.search(queries, k=5)   # 17 -> qc 24 (intermediate)
    for qi, q in enumerate(queries):
        bs, bd = _brute(fp, stacked.avgdl, stacked.total_docs, _agg(q), k=5)
        n = len(bd)
        assert np.array_equal(ords[qi][:n], bd), f"query {qi}"


# ---------------------------------------------------------------------------
# PR 31: the finish runs once a (partition, chunk); its exact rescore reads
# a term's postings inside a picked row as one span of its list
# ---------------------------------------------------------------------------

def _tied_engine():
    """A partition whose second half repeats its first (every score ties
    with its twin's), every seventh doc deleted; head terms own a column,
    tail terms are cold."""
    rng = np.random.default_rng(31)
    vocab = 60
    probs = 1.0 / np.arange(1, vocab + 1) ** 1.1
    probs /= probs.sum()
    lens = rng.integers(4, 20, size=3000).astype(np.int64)
    toks = rng.choice(vocab, size=int(lens.sum()), p=probs).astype(np.int64)
    toks[[40, 9000, 20000]] = vocab          # t60: three docs and their twins
    lens, toks = np.concatenate([lens, lens]), np.concatenate([toks, toks])
    fp = build_field_postings(
        "body", lens, np.repeat(np.arange(6000, dtype=np.int64), lens), toks,
        [f"t{i}" for i in range(vocab + 1)])
    live = np.ones(6000, bool)
    live[::7] = False
    stacked = build_stacked_bm25([_Seg(6000, fp)], "body",
                                 live_masks=[live], serve_only=True)
    return TurboBM25(stacked, hbm_budget_bytes=64 << 20, cold_df=200)


# hot-only, cold-only, mixed, a term the partition lacks (beside others
# and alone), a repeated term, boosts, a rare term (fewer hits than k)
CHUNK_MIX = [
    ["t0", "t1"], ["t50", "t55"], ["t0", "t40", "t58"], ["t1", "zzz"],
    ["zzz"], ["t2", "t2", "t45"], [("t0", 2.0), ("t47", 0.5)],
    [("t3", 0.25), ("t4", 3.0), ("t52", 1.0), ("t30", 1.0)], ["t60"],
    ["t5", "t6", "t7", "t8", "t9", "t35", "t36", "t57"],
]


@pytest.fixture(scope="module")
def tied():
    return _tied_engine()


@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("width", [1, 3, 16, 40])
def test_chunk_finish_bit_identical_to_host(tied, width, k):
    """Widths 1 .. 40 (over SMALL_BATCH_MAX and over the narrow sweep) run
    ONE finish: scores and ords are `search_many_host`'s bits, and every
    pair is counted on the chunk-wide path."""
    qs = [CHUNK_MIX[(i + width) % len(CHUNK_MIX)] for i in range(width)]
    bulk0 = tied.stats["finish_bulk_pairs"]
    left0 = tied.stats["finish_pair_fallbacks"]
    fb0 = tied.stats["fallbacks"]
    got_s, got_d = tied.search_many([qs], k=k)[0]
    want_s, want_d = tied.search_many_host([qs], k=k)[0]
    assert np.array_equal(got_d, want_d)
    assert np.array_equal(got_s, want_s)          # bit for bit
    left = tied.stats["finish_pair_fallbacks"] - left0
    assert left == tied.stats["fallbacks"] - fb0
    assert tied.stats["finish_bulk_pairs"] - bulk0 == width - left
    for i, q in enumerate(qs):      # k above the hits there are: padded
        if q == ["t60"]:
            assert 0 < (got_s[i] > 0).sum() <= 6


def test_chunk_finish_orders_ties_by_doc(tied):
    s, d = tied.search([["t0", "t1"], ["t50", "t55"]], k=10)
    for qi in range(2):
        for j in range(9):
            assert s[qi, j] >= s[qi, j + 1] > 0
            if s[qi, j] == s[qi, j + 1]:
                assert d[qi, j] < d[qi, j + 1]
        assert len({float(x) for x in s[qi]}) < 10      # twins do tie


SPAN_DFS = {"one": 1, "row_less_one": 127, "row": 128, "row_and_one": 129,
            "cold_most": COLD_DF - 1, "cold_df": COLD_DF, "tail": 84}
_SPAN_DOCS = COLD_DF + 300          # 130 full rows and one of 44 docs


def _span_engine(cold_df):
    """One term per df of SPAN_DFS (`tail`'s postings are the last 84
    docs: they end inside the last, partial row) and a filler in every
    doc."""
    rng = np.random.default_rng(5)
    docs_of = {"f": np.arange(_SPAN_DOCS)}
    for name, df in SPAN_DFS.items():
        docs_of[name] = (np.arange(_SPAN_DOCS - df, _SPAN_DOCS)
                         if name == "tail" else
                         np.sort(rng.choice(_SPAN_DOCS, df, replace=False)))
    names = list(docs_of)
    doc = np.concatenate(list(docs_of.values())).astype(np.int64)
    tok = np.concatenate([np.full(len(d), i, np.int64)
                          for i, d in enumerate(docs_of.values())])
    order = np.lexsort((tok, doc))
    fp = build_field_postings(
        "body", np.bincount(doc, minlength=_SPAN_DOCS).astype(np.int64),
        doc[order], tok[order], names)
    stacked = build_stacked_bm25([_Seg(_SPAN_DOCS, fp)], "body",
                                 serve_only=True)
    return TurboBM25(stacked, hbm_budget_bytes=64 << 20, cold_df=cold_df)


@pytest.fixture(scope="module")
def span_engines():
    # the shipped threshold (only `cold_df` and the filler own a column),
    # and every term past df 1 owning one
    return {"list": _span_engine(COLD_DF), "column": _span_engine(1)}


@pytest.mark.parametrize("path", ["list", "column"])
@pytest.mark.parametrize("name", list(SPAN_DFS))
def test_row_span_impacts_are_impacts_at(span_engines, name, path):
    """One term's exact impacts at the docs of 33 picked rows, read by row
    span (`_rescore_rows`: from the column's host index where the term
    owns a column, by a search of the row edges where not), against
    `_impacts_at`'s needle search of the same docs: the same bits; an
    empty slot (-1) stays 0."""
    eng = span_engines[path]
    eng.ensure_columns([name])
    owns = name in eng._slot_of
    if path == "list":
        assert owns == (SPAN_DFS[name] >= COLD_DF)
    else:
        assert owns
    info = eng._term(name)
    assert info.df == SPAN_DFS[name]
    lo = int(eng.fp.post_start[info.ord])
    own_rows = np.unique(eng.fp.post_doc[lo: lo + info.df] >> 7)
    rng = np.random.default_rng(SPAN_DFS[name])
    rows = np.unique(np.concatenate(
        [rng.choice(own_rows, min(len(own_rows), 28), replace=False),
         [0, 1, eng.dp_rows - 1, (_SPAN_DOCS - 1) >> 7]]))[:32]
    rows_all = np.full((1, 33), -1, np.int64)
    rows_all[0, 1: 1 + len(rows)] = rng.permutation(rows)   # slot 0 empty
    boost = 1.5
    plan = eng._plan_chunk([[(name, boost)]], 8)
    plane = eng._rescore_rows(plan, rows_all).reshape(33, 128)
    w = np.float32(info.idf * boost)
    for slot, r in enumerate(rows_all[0]):
        want = np.zeros(128, np.float32)
        if r >= 0:
            want = want + w * eng._impacts_at(
                info, r * 128 + np.arange(128, dtype=np.int64))
        assert np.array_equal(plane[slot], want), (name, path, slot, r)
    assert (plane > 0).any()        # the rows held postings of the term
