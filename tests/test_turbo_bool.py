"""TurboBM25 conjunctive + slop-0 phrase differential suite.

Three routes through the SAME engine must agree bit-for-bit, because all
of them rescore through _exact_bool (f64 accumulation in spec clause
order, one f32 downcast):

  * device: presence-mask sweep over resident int8 columns (Pallas
    kernels in interpret mode on the CPU mesh — tests/conftest.py forces
    JAX_PLATFORMS=cpu),
  * forced certificate failure: device collection discarded, exact host
    fallback (turbo.force_cert_fail test hook),
  * all-cold: a fresh engine with cold_df above every df, so no term has
    a column: every required clause is a COLD row of the bitsets and the
    query is answered from its conjunction mask, on the device (PR 38;
    before it this was the host's sorted-array intersection),
  * host: `search_bool_host`, the sorted-array intersection a faulted
    partition falls back to.

Ground truth is an independent numpy scorer (tf lookups shared, formula
and phrase-position walk reimplemented here).
"""

import numpy as np
import pytest

from elasticsearch_tpu.index.segment import build_field_postings, tf_at
from elasticsearch_tpu.ops import bm25_idf
from elasticsearch_tpu.parallel.spmd import build_stacked_bm25
from elasticsearch_tpu.parallel.turbo import TurboBM25

K1, B = 1.2, 0.75


class _Seg:
    def __init__(self, n_docs, fp):
        self.n_docs = n_docs
        self.postings = {"body": fp}
        self.vectors = {}


def _pcorpus(n_docs=2000, vocab=60, seed=11):
    """Positional Zipf corpus: token_pos is the in-doc offset, so every
    adjacent token pair is a real slop-0 phrase occurrence."""
    rng = np.random.default_rng(seed)
    probs = 1.0 / np.arange(1, vocab + 1) ** 1.1
    probs /= probs.sum()
    lens = rng.integers(4, 24, size=n_docs).astype(np.int64)
    tokens = rng.choice(vocab, size=int(lens.sum()), p=probs).astype(np.int64)
    tok_docs = np.repeat(np.arange(n_docs, dtype=np.int64), lens)
    bounds = np.concatenate([[0], np.cumsum(lens)])
    tok_pos = (np.arange(len(tokens), dtype=np.int64)
               - np.repeat(bounds[:-1], lens))
    names = [f"t{i}" for i in range(vocab)]
    fp = build_field_postings("body", lens, tok_docs, tokens, names,
                              token_pos=tok_pos)
    return fp, lens, tokens, bounds, rng


def _engine(fp, n_docs, live=None, cold_df=5, hbm=64 << 20):
    stacked = build_stacked_bm25(
        [_Seg(n_docs, fp)], "body",
        live_masks=None if live is None else [live], serve_only=True)
    return TurboBM25(stacked, hbm_budget_bytes=hbm, cold_df=cold_df), stacked


def _phrase_pf_brute(fp, terms, doc):
    """Slop-0 phrase frequency by direct position walk."""
    pos = [set(fp.positions(t, doc).tolist()) for t in terms]
    return sum(1 for p0 in pos[0]
               if all(p0 + i in pos[i] for i in range(1, len(terms))))


def _brute_bool(fp, avgdl, total_docs, spec, k=10, live=None):
    """Independent reference: same clause order / f64 accumulation as
    _exact_bool, tf via postings lookup, phrase freq via position walk."""
    n = fp.doc_len.shape[0] if hasattr(fp.doc_len, "shape") else len(fp.doc_len)
    docs = np.arange(n, dtype=np.int64)
    dl = np.asarray(fp.doc_len)[docs]
    norm = K1 * (1.0 - B + B * dl / max(avgdl, 1e-9))
    scores = np.zeros(n, np.float64)
    match = np.ones(n, bool)
    for t, w in spec.get("must", ()):
        if fp.ord(t) < 0:
            return []
        idf = bm25_idf(total_docs, int(fp.doc_freq[fp.ord(t)]))
        tf, present = tf_at(fp, t, docs)
        match &= present
        scores += w * idf * tf * (K1 + 1.0) / (tf + norm)
    for t in spec.get("filter", ()):
        if fp.ord(t) < 0:
            return []
        _, present = tf_at(fp, t, docs)
        match &= present
    for t, w in spec.get("should", ()):
        if fp.ord(t) < 0:
            continue
        idf = bm25_idf(total_docs, int(fp.doc_freq[fp.ord(t)]))
        tf, present = tf_at(fp, t, docs)
        contrib = w * idf * tf * (K1 + 1.0) / np.maximum(tf + norm, 1e-9)
        scores += np.where(present, contrib, 0.0)
    for terms, slop, boost in spec.get("phrases", ()):
        assert slop == 0, "brute reference is slop-0 only"
        if any(fp.ord(t) < 0 for t in terms):
            return []
        idf_sum = sum(bm25_idf(total_docs, int(fp.doc_freq[fp.ord(t)]))
                      for t in terms)
        pf = np.zeros(n, np.float64)
        cand = match.nonzero()[0] if spec.get("must") or spec.get("filter") \
            else docs
        for d in cand:
            pf[d] = _phrase_pf_brute(fp, terms, int(d))
        match &= pf > 0
        if boost != 0.0:
            scores += boost * idf_sum * pf * (K1 + 1.0) / (pf + norm)
    for t in spec.get("must_not", ()):
        if fp.ord(t) < 0:
            continue
        _, present = tf_at(fp, t, docs)
        match &= ~present
    if live is not None:
        match &= live
    keep = match & (scores > 0)
    sel = docs[keep]
    s32 = scores[keep].astype(np.float32)
    order = np.lexsort((sel, -s32))[:k]
    return [(float(s32[j]), int(sel[j])) for j in order]


def _draw_specs(rng, vocab, n=24, bounds=None, tokens=None):
    """Mixed bool specs across all clause kinds; when the corpus arrays
    are given, half the phrase draws come from real adjacent pairs."""
    specs = []
    for i in range(n):
        t = rng.choice(vocab, size=6, replace=False)
        spec = {}
        if i % 3 != 2:
            spec["must"] = [(f"t{t[0]}", 1.0)]
            if i % 2:
                spec["must"].append((f"t{t[1]}", float(rng.choice([1.0, 2.0]))))
        spec["should"] = [(f"t{t[2]}", 1.0), (f"t{t[3]}", 0.5)]
        if i % 4 == 0:
            spec["filter"] = [f"t{t[4]}"]
        if i % 5 == 0:
            spec["must_not"] = [f"t{t[5]}"]
        if i % 3 == 2 and bounds is not None:
            d = int(rng.integers(0, len(bounds) - 1))
            lo, hi = int(bounds[d]), int(bounds[d + 1])
            j = int(rng.integers(lo, hi - 1))
            a, b = int(tokens[j]), int(tokens[j + 1])
            if a != b:
                spec["phrases"] = [([f"t{a}", f"t{b}"], 0, 1.0)]
        specs.append(spec)
    # hot-term and absent-term edges
    specs.append({"must": [("t0", 1.0), ("t1", 1.0)], "filter": ["t2"]})
    specs.append({"must": [("t0", 1.0)], "must_not": ["t1"]})
    specs.append({"must": [("absent", 1.0), ("t1", 1.0)]})
    specs.append({"should": [("t3", 1.0), ("t7", 2.0)]})
    return specs


@pytest.fixture(scope="module")
def corpus():
    return _pcorpus()


def _run_routes(fp, n_docs, specs, live=None, k=10):
    """(device, cert-fail fallback, all-cold device) results, each held
    to the host route's here."""
    dev, _ = _engine(fp, n_docs, live=live, cold_df=5)
    got_dev = dev.search_bool(specs, k=k)
    dev.force_cert_fail = True
    got_fb = dev.search_bool(specs, k=k)
    assert dev.stats["bool_host"] == 0, "a failed certificate went to host"
    cold, _ = _engine(fp, n_docs, live=live, cold_df=1 << 30)
    got_cold = cold.search_bool(specs, k=k)
    assert dev.stats["bool_device"] > 0, "device route never engaged"
    assert cold.stats["bool_cold_lead"] > 0, "cold lead never on the device"
    assert cold.stats["bool_host"] == 0, "a cold clause went to the host"
    got_host = dev.search_bool_host(specs, k=k)
    assert dev.stats["bool_host"] > 0, "host route never engaged"
    _assert_identical(got_dev, got_host, "device vs host route")
    return got_dev, got_fb, got_cold, dev, cold


def _assert_identical(a, b, label):
    (sa, da), (sb, db) = a, b
    assert np.array_equal(da, db), f"{label}: doc ids differ"
    assert np.array_equal(sa, sb), f"{label}: scores differ (not bit-identical)"


def test_bool_routes_bit_identical(corpus):
    fp, lens, tokens, bounds, rng = corpus
    specs = _draw_specs(rng, 60, bounds=bounds, tokens=tokens)
    got_dev, got_fb, got_cold, *_ = _run_routes(fp, len(lens), specs)
    _assert_identical(got_dev, got_fb, "device vs cert-fail fallback")
    _assert_identical(got_dev, got_cold, "device vs all-cold host")


def test_bool_matches_brute_force(corpus):
    fp, lens, tokens, bounds, rng = corpus
    specs = _draw_specs(rng, 60, n=16, bounds=bounds, tokens=tokens)
    turbo, stacked = _engine(fp, len(lens), cold_df=5)
    scores, ords = turbo.search_bool(specs, k=10)
    for qi, spec in enumerate(specs):
        want = _brute_bool(fp, stacked.avgdl, stacked.total_docs, spec, 10)
        got = [(float(scores[qi][j]), int(ords[qi][j]))
               for j in range(10) if scores[qi][j] > 0]
        assert len(got) == len(want), f"query {qi}: {spec}"
        for (es, eo), (gs, go) in zip(want, got):
            assert abs(es - gs) <= 1e-6 * abs(es) + 1e-7, f"query {qi}"
        ws = np.asarray([w[0] for w in want])
        gaps = np.abs(np.diff(ws)) > 1e-6 * np.abs(ws[:-1]) + 1e-7
        if gaps.all():
            assert [o for _, o in want] == [o for _, o in got], f"query {qi}"


def test_phrase_slop0_routes_bit_identical(corpus):
    fp, lens, tokens, bounds, rng = corpus
    phrases = []
    while len(phrases) < 12:
        d = int(rng.integers(0, len(lens)))
        lo, hi = int(bounds[d]), int(bounds[d + 1])
        j = int(rng.integers(lo, hi - 1))
        a, b = int(tokens[j]), int(tokens[j + 1])
        if a != b:
            phrases.append([f"t{a}", f"t{b}"])
    dev, _ = _engine(fp, len(lens), cold_df=5)
    s1, d1 = dev.search_phrase(phrases, k=10, slop=0)
    assert dev.stats["phrase_builds"] > 0, "adjacency columns never built"
    dev.force_cert_fail = True
    s2, d2 = dev.search_phrase(phrases, k=10, slop=0)
    cold, _ = _engine(fp, len(lens), cold_df=1 << 30)
    s3, d3 = cold.search_phrase(phrases, k=10, slop=0)
    _assert_identical((s1, d1), (s2, d2), "phrase device vs cert-fail")
    _assert_identical((s1, d1), (s3, d3), "phrase device vs all-cold")
    # each phrase was drawn from a real adjacency: it must match something
    assert (s1[:, 0] > 0).all()
    # ... and agree with the position-walk brute force
    stacked = build_stacked_bm25([_Seg(len(lens), fp)], "body",
                                 serve_only=True)
    for qi, p in enumerate(phrases[:4]):
        want = _brute_bool(fp, stacked.avgdl, stacked.total_docs,
                           {"phrases": [(p, 0, 1.0)]}, 10)
        got = [(float(s1[qi][j]), int(d1[qi][j]))
               for j in range(10) if s1[qi][j] > 0]
        assert [o for _, o in want] == [o for _, o in got], f"phrase {qi}"


def test_deleted_docs_excluded_on_all_routes(corpus):
    fp, lens, tokens, bounds, rng = corpus
    live = np.ones(len(lens), bool)
    live[::3] = False
    specs = _draw_specs(rng, 60, n=10, bounds=bounds, tokens=tokens)
    got_dev, got_fb, got_cold, *_ = _run_routes(fp, len(lens), specs,
                                                live=live)
    _assert_identical(got_dev, got_fb, "deleted: device vs cert-fail")
    _assert_identical(got_dev, got_cold, "deleted: device vs all-cold")
    scores, ords = got_dev
    hit = ords[scores > 0]
    assert live[hit].all(), "a deleted doc surfaced in the top-k"


def test_capacity_degradation_stays_exact(corpus):
    """Columns + phrases far beyond the slot budget: the engine degrades
    to host scoring for the overflow, twice in a row (the second call
    used to crash ensure_phrases on an empty build dispatch), and stays
    bit-identical to the uncached route throughout."""
    fp, lens, tokens, bounds, rng = corpus
    turbo, _ = _engine(fp, len(lens), cold_df=5, hbm=256 << 10)
    assert turbo.Hp < 40, "budget too generous for a degradation test"
    phrases = []
    while len(phrases) < 48:
        d = int(rng.integers(0, len(lens)))
        lo, hi = int(bounds[d]), int(bounds[d + 1])
        j = int(rng.integers(lo, hi - 1))
        a, b = int(tokens[j]), int(tokens[j + 1])
        if a != b and [f"t{a}", f"t{b}"] not in phrases:
            phrases.append([f"t{a}", f"t{b}"])
    s1, d1 = turbo.search_phrase(phrases, k=10, slop=0)
    s2, d2 = turbo.search_phrase(phrases, k=10, slop=0)   # warm/degraded
    _assert_identical((s1, d1), (s2, d2), "degraded warm vs cold call")
    cold, _ = _engine(fp, len(lens), cold_df=1 << 30)
    s3, d3 = cold.search_phrase(phrases, k=10, slop=0)
    _assert_identical((s1, d1), (s3, d3), "degraded vs all-cold host")
    assert turbo.stats["degraded"] > 0, "degradation never exercised"


def test_sloppy_phrase_takes_host_path(corpus):
    """slop > 0 must bypass the adjacency columns and still agree with
    the uncached engine."""
    fp, lens, tokens, bounds, rng = corpus
    phrases = [["t0", "t1"], ["t1", "t0"], ["t2", "t5"]]
    dev, _ = _engine(fp, len(lens), cold_df=5)
    s1, d1 = dev.search_phrase(phrases, k=10, slop=2)
    assert dev.stats["phrase_builds"] == 0, "slop>0 built adjacency columns"
    cold, _ = _engine(fp, len(lens), cold_df=1 << 30)
    s2, d2 = cold.search_phrase(phrases, k=10, slop=2)
    _assert_identical((s1, d1), (s2, d2), "slop-2 device-eng vs all-cold")
