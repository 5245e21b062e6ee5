"""The device bool route (PR 38): every shape of the benchmark's bool mix
(luceneutil's And / AndHighOr / Phrase categories, a filter, a must_not)
answered on the device, cold required clauses included, with its total.

Held here, on the CPU at tiny size (Pallas in interpret mode,
`ES_TPU_FORCE_TURBO` where the REST path is involved):

* the device route against `search_bool_host` (bit-identical) and against
  an independent numpy scorer, for every shape, on a solo engine and on
  the fused one;
* the hit count the device took (the conjunction mask's population count)
  against a brute-force count and against `_conj_total`'s host
  intersection, and that a device-routed request never calls the latter;
* bool requests through the dispatch scheduler: 32 at once with a cold
  SHOULD side, equal to the serial answers, mixed with disjunctions in one
  lane;
* the node-wide counters: one count a (partition, request) pair,
  `bool_host` and `bitset_gallop` unmoved.
"""

import threading

import numpy as np
import pytest

from elasticsearch_tpu.parallel import turbo as turbo_mod
from elasticsearch_tpu.parallel.spmd import build_stacked_bm25
from elasticsearch_tpu.parallel.turbo import TurboBM25
from elasticsearch_tpu.threadpool.scheduler import AdaptiveDispatchScheduler

from test_turbo_bool import _Seg, _brute_bool, _pcorpus
from test_turbo_sharded import _corpus_fp, _fused_engine

pytestmark = pytest.mark.multidevice

COLD_DF = 260      # of 2,000 docs: ranks 0-9 own a column, the rest are cold
K = 10


@pytest.fixture(scope="module")
def corpus():
    return _pcorpus(n_docs=2000, vocab=60, seed=23)


def _bands(fp):
    """Terms by document frequency, as the benchmark's bands go: High owns
    a column, Med is cold, Low is rare."""
    by_df = sorted(range(len(fp.doc_freq)), key=lambda o: -int(fp.doc_freq[o]))
    names = [fp.terms[o] for o in by_df]
    hot = [t for t in names if fp.doc_freq[fp.term_to_ord[t]] >= COLD_DF]
    cold = [t for t in names if 0 < fp.doc_freq[fp.term_to_ord[t]] < COLD_DF]
    assert len(hot) >= 6 and len(cold) >= 12
    return {"High": hot[2:], "Med": cold[:8], "Low": cold[-8:],
            "P": hot[:4]}


def _shapes(fp, tokens, bounds):
    """One spec per shape of benchmark/traffic/bool-open.json's cycle."""
    b = _bands(fp)
    H, M, L = b["High"], b["Med"], b["Low"]
    # phrases that occur: two and three adjacent tokens of one document
    # (a live one: the tests delete every seventh)
    j = next(int(bounds[d]) for d in range(1, len(bounds) - 1)
             if d % 7 and bounds[d + 1] - bounds[d] >= 4
             and len(set(tokens[bounds[d]: bounds[d] + 4])) == 4)
    p2 = [f"t{tokens[j]}", f"t{tokens[j + 1]}"]
    p3 = [f"t{tokens[j + 1]}", f"t{tokens[j + 2]}", f"t{tokens[j + 3]}"]
    return {
        "AndHighHigh": {"must": [(H[0], 1.0), (H[1], 1.0)]},
        "AndHighMed": {"must": [(H[0], 1.0), (M[0], 1.0)]},
        "AndHighLow": {"must": [(H[1], 1.0), (L[0], 1.0)]},
        "AndMedMed": {"must": [(M[1], 1.0), (M[2], 1.0)]},
        "And3": {"must": [(H[2], 1.0), (M[3], 1.0), (M[0], 1.0)]},
        "AndHighOrMedMed": {"must": [(H[3], 1.0)],
                            "should": [(M[4], 1.0), (M[5], 1.0)]},
        "Filter": {"must": [(M[6], 1.0)], "filter": [H[0]]},
        "MustNot": {"must": [(H[1], 1.0), (M[7], 1.0)],
                    "must_not": [H[2]]},
        "MustNotCold": {"must": [(H[0], 1.0), (M[0], 1.0)],
                        "must_not": [M[1]]},
        "Phrase2": {"phrases": [(p2, 0, 1.0)]},
        "Phrase3": {"phrases": [(p3, 0, 1.0)]},
    }


def _engine(fp, n_docs, live=None):
    stacked = build_stacked_bm25(
        [_Seg(n_docs, fp)], "body",
        live_masks=None if live is None else [live], serve_only=True)
    return TurboBM25(stacked, hbm_budget_bytes=64 << 20,
                     cold_df=COLD_DF), stacked


def _count(fp, spec, live=None):
    """Documents that satisfy the conjunction, by brute force."""
    n = len(fp.doc_len)
    want = _brute_bool(fp, 10.0, n, dict(spec, should=[]), k=n, live=live)
    return len(want)


SHAPES = ("AndHighHigh", "AndHighMed", "AndHighLow", "AndMedMed", "And3",
          "AndHighOrMedMed", "Filter", "MustNot", "MustNotCold", "Phrase2",
          "Phrase3")


@pytest.fixture(scope="module")
def solo(corpus):
    fp, lens, tokens, bounds, _ = corpus
    live = np.ones(len(lens), bool)
    live[::7] = False
    eng, stacked = _engine(fp, len(lens), live=live)
    specs = _shapes(fp, tokens, bounds)
    totals = np.zeros(len(SHAPES), np.int64)
    got = eng.search_bool([specs[s] for s in SHAPES], k=K, totals=totals)
    return eng, stacked, specs, live, got, totals


@pytest.mark.parametrize("shape", SHAPES)
def test_solo_device_route_is_the_host_route_and_the_brute_force(
        corpus, solo, shape):
    fp = corpus[0]
    eng, stacked, specs, live, (scores, ords), totals = solo
    qi = SHAPES.index(shape)
    hs, ho = eng.search_bool_host([specs[shape]], k=K)
    assert np.array_equal(scores[qi], hs[0]) and np.array_equal(
        ords[qi], ho[0]), "device and host routes differ"
    want = _brute_bool(fp, stacked.avgdl, stacked.total_docs, specs[shape],
                       K, live=live)
    got = [(float(scores[qi][j]), int(ords[qi][j]))
           for j in range(K) if scores[qi][j] > 0]
    assert len(got) == len(want) > 0, shape
    for (es, _), (gs, _) in zip(want, got):
        assert abs(es - gs) <= 1e-6 * abs(es) + 1e-7
    # the device's own count: the mask's population count among live docs
    assert totals[qi] == _count(fp, specs[shape], live=live) > 0, shape


def test_solo_counters_count_pairs_and_no_host_answer(corpus):
    fp, lens, tokens, bounds, _ = corpus
    eng, _ = _engine(fp, len(lens))
    specs = _shapes(fp, tokens, bounds)
    node0 = turbo_mod.node_bitset_stats()
    eng.search_bool([specs[s] for s in SHAPES], k=K)
    node1 = turbo_mod.node_bitset_stats()
    rose = {k: node1[k] - node0[k] for k in node1}
    assert rose["bool_device"] == eng.stats["bool_device"] == len(SHAPES)
    # the shapes with a cold required term: 7 of the 11 here
    assert rose["bool_cold_lead"] == eng.stats["bool_cold_lead"] == 7
    assert rose["bool_host"] == eng.stats["bool_host"] == 0
    assert rose["bitset_gallop"] == 0
    assert rose["phrase_builds"] == eng.stats["phrase_builds"] == 2
    assert rose["bitset_packs"] >= 1
    # a cold lead scores nothing on the device and builds no slice for
    # its required terms: only the cold SHOULD side rode a gather
    assert eng.stats["sparse_queries"] == 1


def test_a_failed_certificate_finishes_from_the_mask_not_on_the_host(corpus):
    fp, lens, tokens, bounds, _ = corpus
    eng, _ = _engine(fp, len(lens))
    specs = [_shapes(fp, tokens, bounds)[s] for s in SHAPES]
    want = eng.search_bool(specs, k=K)
    eng.force_cert_fail = True
    got = eng.search_bool(specs, k=K)
    assert eng.stats["fallbacks"] > 0 and eng.stats["bool_host"] == 0
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])


def test_more_cold_clauses_than_rows_still_answers_exactly(corpus,
                                                           monkeypatch):
    """A batch naming more cold required terms than there are cold rows:
    the queries left without a row take the host route, counted."""
    fp, lens, _tokens, _bounds, _ = corpus
    eng, _ = _engine(fp, len(lens))
    eng._crow_free = eng._crow_free[:3]
    b = _bands(fp)
    specs = [{"must": [(b["High"][0], 1.0), (t, 1.0)]}
             for t in b["Med"] + b["Low"]]
    got = eng.search_bool(specs, k=K)
    want = eng.search_bool_host(specs, k=K)
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])
    assert eng.stats["degraded"] > 0
    assert eng.stats["bool_device"] == 3
    # the rows turn over: the same terms again, three at a time, all on
    # the device
    for i in range(0, len(specs), 3):
        eng.search_bool(specs[i: i + 3], k=K)
    assert eng.stats["bool_device"] == 3 + len(specs)


# ---- fused -------------------------------------------------------------

@pytest.fixture(scope="module")
def fused(corpus):
    """The corpus as three partitions under the fused engine."""
    fp, lens, tokens, bounds, _ = corpus
    cuts = [0, 700, 1300, len(lens)]
    parts = []
    for a, b in zip(cuts, cuts[1:]):
        parts.append((b - a, _corpus_fp(
            lens[a:b], tokens[bounds[a]: bounds[b]], 60)))
    eng = _fused_engine(parts, cold_df=COLD_DF // 3)
    specs = _shapes(fp, tokens, bounds)
    totals = np.zeros(len(SHAPES), np.int64)
    got = eng.search_bool([specs[s] for s in SHAPES], k=K, totals=totals)
    eng.after_shapes = dict(eng.stats)
    return eng, specs, got, totals


@pytest.mark.parametrize("shape", SHAPES)
def test_fused_device_route_is_the_host_route_with_its_total(
        corpus, fused, shape):
    fp = corpus[0]
    eng, specs, (scores, parts, ords), totals = fused
    qi = SHAPES.index(shape)
    per = [t.search_bool_host([specs[shape]], k=K) for t in eng.turbos]
    hs, hp, ho = eng._merge3(per, 1, K)
    assert np.array_equal(scores[qi], hs[0]), shape
    assert np.array_equal(parts[qi], hp[0]) and np.array_equal(
        ords[qi], ho[0]), shape
    # one index cut in three: the partitions' counts add up to the whole's
    assert totals[qi] == _count(fp, specs[shape]) > 0, shape


def test_fused_counts_no_host_answer(fused):
    st = fused[0].after_shapes
    # (a phrase of one document resolves to nothing on the partitions
    # that do not hold it: such a pair takes no route)
    assert 3 * (len(SHAPES) - 2) + 2 <= st["bool_device"] <= 3 * len(SHAPES)
    assert st["bool_cold_lead"] >= 3 * 7
    assert st["bool_host"] == 0 and st["bitset_gallop"] == 0
    assert st["fused_dispatches"] >= 1


# ---- through the scheduler ---------------------------------------------

def test_32_concurrent_bool_requests_with_a_cold_should_side(corpus, fused):
    """What the HTTP pool's 32 threads do to one engine: before PR 38 each
    ran `search_bool` on its own thread, and two of them met inside
    `_ensure_sparse`, which donates the pool buffer."""
    fp = corpus[0]
    eng = fused[0]
    b = _bands(fp)
    specs = [{"must": [(b["High"][i % len(b["High"])], 1.0)],
              "should": [(b["Med"][i % 8], 1.0), (b["Low"][(i * 3) % 8], 2.0)]}
             for i in range(32)]
    serial = [eng.search_bool([s], k=K) for s in specs]
    before = dict(eng.stats)
    sched = AdaptiveDispatchScheduler(buckets=(1, 4, 16))
    out, errs = [None] * 32, []
    totals = [np.zeros(1, np.int64) for _ in specs]
    gate = threading.Barrier(32)

    def one(i):
        try:
            gate.wait(timeout=30)
            out[i] = sched.dispatch(eng, [specs[i]], K, totals=totals[i])
        except BaseException as e:  # noqa: BLE001 — asserted below
            errs.append(e)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    for i, (got, want) in enumerate(zip(out, serial)):
        for g, w in zip(got, want):
            assert np.array_equal(g, w), i
        assert totals[i][0] == _count(fp, specs[i])
    st = sched.stats()
    assert st["sched_queries"] == 32 and st["largest_batch"] > 1
    after = eng.stats
    assert after["sparse_fallbacks"] == 0
    assert after["bool_host"] == before["bool_host"]
    assert after["bool_device"] == before["bool_device"] + 3 * 32


def test_a_lane_batches_bool_specs_and_disjunctions_together(corpus, fused):
    fp = corpus[0]
    eng = fused[0]
    b = _bands(fp)
    spec = {"must": [(b["High"][0], 1.0), (b["Med"][0], 1.0)]}
    disj = [(b["High"][1], 1.0), (b["Low"][0], 1.0)]
    batch = [disj, spec, disj, spec]
    s, p, o, totals = eng.search_many([batch], k=K)[0]
    want_b = eng.search_bool([spec], k=K)
    want_d = eng.search_many([[disj]], k=K)[0]
    for row, want in ((0, want_d), (1, want_b), (2, want_d), (3, want_b)):
        for got, w in zip((s, p, o), want):
            assert np.array_equal(got[row], w[0]), row
    assert list(totals) == [-1, _count(fp, spec), -1, _count(fp, spec)]
    # and a lane hands each caller its own rows of such a batch
    sched = AdaptiveDispatchScheduler(buckets=(1, 4), interactive_us=2e5)
    res = {}

    def call(name, q, tot):
        res[name] = sched.dispatch(eng, [q], K, totals=tot)

    tb, td = np.zeros(1, np.int64), np.zeros(1, np.int64)
    ts = [threading.Thread(target=call, args=("b", spec, tb)),
          threading.Thread(target=call, args=("d", disj, td))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    for got, w in zip(res["b"], want_b):
        assert np.array_equal(got, w)
    for got, w in zip(res["d"], want_d):
        assert np.array_equal(got, w)
    # (-1 where the two met in one batch: a disjunction's row of a mixed
    # batch; 0, untouched, where the disjunction was dispatched alone)
    assert tb[0] == _count(fp, spec) and td[0] in (0, -1)
    assert len(res["b"]) == len(res["d"]) == 3


# ---- the REST path -----------------------------------------------------

@pytest.fixture(scope="module")
def bool_svc():
    """An index whose conjunctions route through TurboEngine (two
    segments and deletions: the fused path), with hot and cold terms."""
    from elasticsearch_tpu.cluster.state import IndexMetadata
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.index_service import IndexService

    mp = pytest.MonkeyPatch()
    mp.setenv("ES_TPU_FORCE_TURBO", "1")
    mp.setenv("ES_TPU_TURBO_COLD_DF", "60")
    meta = IndexMetadata(
        index="bool_t", uuid="u_bool", settings=Settings({}),
        mappings={"properties": {"body": {"type": "text"}}})
    svc = IndexService(meta)
    rng = np.random.default_rng(5)
    probs = 1.0 / np.arange(1, 41) ** 1.1
    probs /= probs.sum()
    for i in range(600):
        words = rng.choice(40, size=int(rng.integers(4, 20)), p=probs)
        svc.index_doc(str(i), {"body": " ".join(f"w{w}" for w in words)})
        if i == 280:
            svc.refresh()
    for i in range(0, 90, 11):
        svc.delete_doc(str(i))
    svc.refresh()
    yield svc
    svc.close()
    mp.undo()


def _rest_bodies():
    m = lambda t: {"match": {"body": t}}          # noqa: E731
    return {
        "AndHighHigh": {"bool": {"must": [m("w0"), m("w1")]}},
        "AndHighMed": {"bool": {"must": [m("w1"), m("w12")]}},
        "AndHighLow": {"bool": {"must": [m("w0"), m("w37")]}},
        "AndMedMed": {"bool": {"must": [m("w11"), m("w14")]}},
        "And3": {"bool": {"must": [m("w2"), m("w10"), m("w13")]}},
        "AndHighOrMedMed": {"bool": {"must": [m("w3")],
                                     "should": [m("w15"), m("w20")]}},
        "Filter": {"bool": {"must": [m("w12")],
                            "filter": [{"term": {"body": "w1"}}]}},
        "MustNot": {"bool": {"must": [m("w0"), m("w16")],
                             "must_not": [{"term": {"body": "w2"}}]}},
        "Phrase2": {"match_phrase": {"body": {"query": "w0 w1", "slop": 0}}},
        "Phrase3": {"match_phrase": {"body": {"query": "w1 w0 w2",
                                              "slop": 0}}},
    }


@pytest.mark.parametrize("shape", sorted(_rest_bodies()))
def test_rest_shape_is_served_by_the_device_with_its_own_total(
        bool_svc, monkeypatch, shape):
    """Every shape of the cell over HTTP's entry point: the dense
    executor's hits and total, from the device route alone — the host's
    conjunction (`_conjunctive_candidates`, what `_conj_total` ran beside
    every device answer before PR 38) is not called once."""
    from elasticsearch_tpu.search import serving

    svc = bool_svc
    body = {"query": _rest_bodies()[shape], "size": 10}
    want = svc._search_dense(body)
    plan = serving.extract_plan(body, svc.mapper)
    snap = svc.serving.snapshot()
    host_total = svc.serving._conj_total(plan, snap, body)
    assert host_total == (want["hits"]["total"]["value"], "eq")

    def never(*a, **k):
        raise AssertionError("the host intersected a device-routed request")

    monkeypatch.setattr(serving, "_conjunctive_candidates", never)
    eng = snap.engine("body")
    before = dict(eng.stats)
    for fast in (svc.serving.try_search(body, "query_then_fetch"),
                 svc.serving.try_msearch([body, body],
                                         "query_then_fetch")[1]):
        assert fast is not None
        assert [h["_id"] for h in fast["hits"]["hits"]] == [
            h["_id"] for h in want["hits"]["hits"]], shape
        assert fast["hits"]["total"] == want["hits"]["total"], shape
        assert want["hits"]["total"]["value"] > 0
    after = eng.stats
    assert after["bool_host"] == before["bool_host"]
    assert after["bool_device"] > before["bool_device"]
    # through the scheduler: the `device` span's batch-size histogram saw
    # the dispatches (one of one request, one of the msearch's two)
    assert after["fused_dispatches"] >= before["fused_dispatches"] + 2


def test_rest_query_phase_adapter_counts_on_the_device_too(bool_svc,
                                                           monkeypatch):
    from elasticsearch_tpu.search import serving

    svc = bool_svc
    body = {"query": _rest_bodies()["AndHighMed"], "size": 10}
    want = svc._search_dense(body)
    monkeypatch.setattr(
        serving, "_conjunctive_candidates",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError("host")))
    res = svc.serving.try_query_phase(body)
    assert res is not None and res.total == want["hits"]["total"]["value"]
    assert res.relation == "eq" and len(res.hits) == len(
        want["hits"]["hits"])


# ---- spans -------------------------------------------------------------

def test_a_bool_dispatch_takes_one_observation_a_step(corpus, fused):
    """`dispatch.prep`'s new children (`bool_resolve`, `phrase_build`,
    `bitset_pack`) and the four top-level steps: one histogram
    observation an ENGINE call each, 0.0 when the step did not run,
    children summing under their parent."""
    from elasticsearch_tpu.common import metrics

    fp = corpus[0]
    eng = fused[0]
    b = _bands(fp)
    names = ("dispatch.prep", "dispatch.launch", "dispatch.device_wait",
             "dispatch.finish", "dispatch.bool_resolve",
             "dispatch.phrase_build", "dispatch.bitset_pack",
             "dispatch.slice_build", "dispatch.rescore")

    def read():
        out = {}
        for n in names:
            s = metrics.summary(n) or {"count": 0, "mean": 0.0}
            out[n] = (s["count"], s["count"] * s["mean"])
        return out

    spec = {"must": [(b["High"][0], 1.0), (b["Low"][1], 1.0)]}
    phrase = {"phrases": [([b["P"][0], b["P"][1]], 0, 1.0)]}
    for batch in ([spec], [spec, phrase, [(b["High"][1], 1.0)]]):
        before = read()
        eng.search_many([batch], k=K)
        after = read()
        took = {n: after[n][1] - before[n][1] for n in names}
        for n in names:
            assert after[n][0] - before[n][0] == 1, n
        kids = (took["dispatch.bool_resolve"] + took["dispatch.phrase_build"]
                + took["dispatch.bitset_pack"] + took["dispatch.slice_build"])
        assert kids <= took["dispatch.prep"] * 1.001 + 1e-3
        assert took["dispatch.rescore"] <= took["dispatch.finish"] + 1e-3
        assert took["dispatch.bool_resolve"] > 0


def test_scanned_phrases_outlive_their_columns_within_a_bound(corpus,
                                                              monkeypatch):
    """A phrase's positions scan is what it costs: its (docs, pf) stay on
    the host when its column is evicted, up to `_PHRASE_HOST_BYTES`."""
    fp, lens, tokens, bounds, _ = corpus
    eng, _ = _engine(fp, len(lens))
    pairs = []
    for d in range(1, 400):
        a, b = tokens[bounds[d]], tokens[bounds[d] + 1]
        if a != b and [f"t{a}", f"t{b}"] not in pairs:
            pairs.append([f"t{a}", f"t{b}"])
    pairs = pairs[:40]
    want = eng.search_phrase(pairs, k=K)
    scans = []
    scan = turbo_mod.phrase_freqs
    monkeypatch.setattr(turbo_mod, "phrase_freqs",
                        lambda *a, **k: (scans.append(1), scan(*a, **k))[1])
    for key in [k for k in eng._slot_of if k.startswith("\x00p:")]:
        eng._evict(key)
    got = eng.search_phrase(pairs, k=K)
    assert not scans, "an evicted column's phrase was scanned again"
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])
    # under a bound of nothing only resident phrases are kept
    monkeypatch.setattr(turbo_mod, "_PHRASE_HOST_BYTES", 0)
    eng.search_phrase([["t0", "t59"], ["t59", "t0"]], k=K)
    assert len(eng._phrases) < len(pairs)
    for key in [k for k in eng._slot_of if k.startswith("\x00p:")]:
        eng._evict(key)
    got = eng.search_phrase(pairs, k=K)
    assert scans, "nothing was dropped under a bound of nothing"
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])
