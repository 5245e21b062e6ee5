"""The hybrid route (PR 45): a body with `query` AND a top-level `knn`
section, served by both engines of one snapshot (`TurboBM25` +
`KnnEngine`) and joined exactly (`serving.hybrid_join`), held equal to
the dense executor's answer (`query_phase.execute_query_phase` through
`IndexService._search_dense`): ids, order, scores (f32, to the ulp the
two BM25 and kNN paths already agree to), `hits.total` and its relation.

Tiny seeded corpus on the CPU; the engines are forced as in
tests/bench_harness/bench_tiny.steer_engines."""

import numpy as np
import pytest

from elasticsearch_tpu.cluster.state import IndexMetadata
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.search import serving

WORDS = ["alpha", "beta", "gamma", "delta", "eps", "zeta", "eta", "theta",
         "iota", "kappa"]
DIMS = 8
N_DOCS = 320
RARE = "omega"          # in three documents only
DELETED = tuple(str(i) for i in range(0, 40, 9))


def _docs():
    rng = np.random.default_rng(59)
    out = []
    for i in range(N_DOCS):
        words = list(rng.choice(WORDS, size=int(rng.integers(2, 6))))
        if i in (7, 150, 301):
            words.append(RARE)
        out.append({"body": " ".join(words),
                    "tag": str(rng.choice(["red", "green"])),
                    "vec": [float(x) for x in rng.standard_normal(DIMS)]})
    return out


DOCS = _docs()


@pytest.fixture(scope="module")
def svc():
    mp = pytest.MonkeyPatch()
    mp.setenv("ES_TPU_FORCE_TURBO", "1")
    mp.setenv("ES_TPU_FORCE_KNN", "1")
    mp.setenv("ES_TPU_TURBO_COLD_DF", "8")
    meta = IndexMetadata(
        index="hy", uuid="u_hy", settings=Settings({}),
        mappings={"properties": {
            "body": {"type": "text"}, "tag": {"type": "keyword"},
            "vec": {"type": "dense_vector", "dims": DIMS}}})
    svc = IndexService(meta)
    for i, d in enumerate(DOCS):
        svc.index_doc(str(i), d)
        if i == 140:
            svc.refresh()       # two segments: two partitions, merged
    for i in DELETED:
        svc.delete_doc(i)
    svc.refresh()
    yield svc
    svc.close()
    mp.undo()


def near(doc: int, noise: float = 0.05, seed: int = 3):
    """A query vector beside document `doc`'s."""
    v = np.asarray(DOCS[doc]["vec"]) + noise * np.random.default_rng(
        seed).standard_normal(DIMS)
    return [float(x) for x in v]


def knn(doc: int, k: int = 5, **more):
    return dict({"field": "vec", "query_vector": near(doc), "k": k}, **more)


def assert_same(fast: dict, dense: dict, body) -> None:
    fh, dh = fast["hits"]["hits"], dense["hits"]["hits"]
    assert [h["_id"] for h in fh] == [h["_id"] for h in dh], body
    for a, b in zip(fh, dh):
        assert abs(a["_score"] - b["_score"]) <= 4 * np.spacing(
            np.float32(b["_score"])), (body, a, b)
    assert fast["hits"].get("total") == dense["hits"].get("total"), body
    got, want = fast["hits"]["max_score"], dense["hits"]["max_score"]
    assert (got is None) == (want is None)
    if want is not None:
        assert abs(got - want) <= 4 * np.spacing(np.float32(want))


def both(svc, body):
    """(the device route's answer, the dense executor's), with the
    route's counters held to what happened."""
    before = serving.hybrid_node_stats()
    fast = svc.serving.try_search(body, "query_then_fetch")
    after = serving.hybrid_node_stats()
    assert fast is not None, f"the hybrid route did not engage: {body}"
    assert after["hybrid_device"] == before["hybrid_device"] + 1
    assert after["hybrid_host"] == before["hybrid_host"]
    return fast, svc._search_dense(body)


# doc 200 holds some of WORDS; its own vector's nearest is itself
CASES = {
    "a nearest document that also matches: the sum": {
        "query": {"match": {"body": DOCS[200]["body"]}},
        "knn": knn(200)},
    "a nearest document that matches nothing: knn only, in the total": {
        "query": {"match": {"body": RARE}}, "knn": knn(200, k=4)},
    "bm25-only hits below the nearest": {
        "query": {"match": {"body": "alpha kappa"}}, "knn": knn(100, k=2),
        "size": 20},
    "k above size": {
        "query": {"match": {"body": "beta gamma"}}, "knn": knn(120, k=25),
        "size": 5},
    "k below size": {
        "query": {"match": {"body": "beta gamma"}}, "knn": knn(120, k=3),
        "size": 30},
    "a knn filter": {
        "query": {"match": {"body": "delta eps"}},
        "knn": knn(210, k=8, filter={"term": {"tag": DOCS[210]["tag"]}})},
    "from above zero": {
        "query": {"match": {"body": "zeta eta theta"}}, "knn": knn(90, k=6),
        "from": 4, "size": 7},
    "track_total_hits false": {
        "query": {"match": {"body": "alpha"}}, "knn": knn(33),
        "track_total_hits": False},
    "track_total_hits true": {
        "query": {"match": {"body": "alpha beta gamma delta"}},
        "knn": knn(33), "track_total_hits": True},
    "track_total_hits 10: the cap and its relation": {
        "query": {"match": {"body": "alpha"}}, "knn": knn(33),
        "track_total_hits": 10},
    "track_total_hits 5 with a total under it but for the knn-only": {
        "query": {"match": {"body": RARE}}, "knn": knn(33, k=6),
        "track_total_hits": 5},
    "a deleted document beside the query vector": {
        "query": {"match": {"body": DOCS[9]["body"]}}, "knn": knn(9, k=4)},
    "a bool.should of terms with a boost": {
        "query": {"bool": {"should": [
            {"term": {"body": {"value": "iota", "boost": 2.0}}},
            {"term": {"body": "kappa"}}]}},
        "knn": knn(250, k=7)},
    "a term no document holds": {
        "query": {"match": {"body": "zzz_missing"}}, "knn": knn(60, k=3)},
    "knn given as a list of one": {
        "query": {"match": {"body": "gamma"}}, "knn": [knn(61, k=3)]},
}


@pytest.mark.parametrize("name", list(CASES))
def test_hybrid_body_equals_the_dense_executor(svc, name):
    fast, dense = both(svc, CASES[name])
    assert_same(fast, dense, name)


def test_the_sum_is_worked_and_knn_only_documents_are_counted(svc):
    """What the cases above rest on, seen directly: the nearest document
    that matches carries BM25 + vector score, one that matches nothing
    carries its vector score alone and is in the total."""
    body = CASES["a nearest document that also matches: the sum"]
    fast, _ = both(svc, body)
    bm = svc.serving.try_search({"query": body["query"], "size": 50},
                                "query_then_fetch")
    nn = svc.serving.try_search({"knn": body["knn"]}, "query_then_fetch")
    bm_of = {h["_id"]: h["_score"] for h in bm["hits"]["hits"]}
    nn_of = {h["_id"]: h["_score"] for h in nn["hits"]["hits"]}
    assert "200" in nn_of and "200" in bm_of
    top = fast["hits"]["hits"][0]
    assert top["_id"] == "200"
    assert top["_score"] == float(np.float32(bm_of["200"])
                                  + np.float32(nn_of["200"]))
    before = serving.hybrid_node_stats()
    fast, dense = both(svc, CASES[
        "a nearest document that matches nothing: knn only, in the total"])
    after = serving.hybrid_node_stats()
    assert fast["hits"]["total"] == {"value": 3 + 4, "relation": "eq"}
    assert after["knn_only_hits"] - before["knn_only_hits"] == 4
    assert after["point_scored_docs"] - before["point_scored_docs"] == 4
    assert after["wall_us"] > before["wall_us"]
    assert after["bm25_us"] > before["bm25_us"]
    assert after["knn_us"] > before["knn_us"]


def test_a_mixed_msearch_puts_every_body_in_its_own_group_and_slot(svc):
    bodies = [
        CASES["a nearest document that also matches: the sum"],
        {"query": {"match": {"body": "alpha beta"}}},
        {"knn": knn(77, k=6)},
        CASES["k above size"],
        {"query": {"match": {"body": "theta"}}, "size": 3},
        {"knn": knn(12, k=4, filter={"term": {"tag": "red"}})},
        CASES["a knn filter"],
    ]
    routed = svc.serving._route(bodies, "query_then_fetch")
    groups = routed[-1]
    assert groups["hybrid"] == {("body", "vec"): [0, 3, 6]}
    assert groups["disj"] == {"body": [1, 4]}
    assert groups["knn"] == {"vec": [2, 5]}
    before = serving.hybrid_node_stats()
    out = svc.serving.try_msearch(bodies, "query_then_fetch")
    after = serving.hybrid_node_stats()
    assert after["hybrid_device"] - before["hybrid_device"] == 3
    assert after["hybrid_host"] == before["hybrid_host"]
    for body, fast in zip(bodies, out):
        assert fast is not None, body
        assert_same(fast, svc._search_dense(body), body)


DECLINED = {
    "a conjunctive query": {
        "query": {"bool": {"must": [{"term": {"body": "alpha"}},
                                    {"term": {"body": "beta"}}]}},
        "knn": knn(40)},
    "a sort": {
        "query": {"match": {"body": "alpha"}}, "knn": knn(40),
        "sort": [{"_score": "desc"}]},
    "a boost on the knn section": {
        "query": {"match": {"body": "alpha"}}, "knn": knn(40, boost=2.0)},
    "two knn sections": {
        "query": {"match": {"body": "alpha"}},
        "knn": [knn(40), knn(41)]},
}


@pytest.mark.parametrize("name", list(DECLINED))
def test_a_declined_body_is_answered_by_the_host_and_counted(svc, name):
    body = DECLINED[name]
    assert serving.extract_hybrid_plan(body, svc.mapper) is None
    assert svc.serving.try_search(body, "query_then_fetch") is None
    before = serving.hybrid_node_stats()
    resp = svc.search(body)
    after = serving.hybrid_node_stats()
    assert after["hybrid_host"] == before["hybrid_host"] + 1
    assert after["hybrid_device"] == before["hybrid_device"]
    assert after["hybrid_queries"] == before["hybrid_queries"] + 1
    dense = svc._search_dense(body)
    assert [h["_id"] for h in resp["hits"]["hits"]] == \
        [h["_id"] for h in dense["hits"]["hits"]]


def test_a_declined_body_in_an_msearch_counts_once(svc):
    bodies = [DECLINED["a conjunctive query"],
              CASES["from above zero"]]
    before = serving.hybrid_node_stats()
    out = svc.msearch(bodies)
    after = serving.hybrid_node_stats()
    assert after["hybrid_host"] - before["hybrid_host"] == 1
    assert after["hybrid_device"] - before["hybrid_device"] == 1
    assert all(isinstance(r, dict) for r in out)


def test_an_engine_without_a_point_score_leaves_the_body_to_the_host(
        svc, monkeypatch):
    """Where the text field's engine offers no `point_scores` (BlockMax)
    the route is not taken."""
    from elasticsearch_tpu.search.serving import TurboEngine

    monkeypatch.delattr(TurboEngine, "point_scores")
    body = CASES["k below size"]
    assert svc.serving.try_search(body, "query_then_fetch") is None


def test_point_scores_read_what_the_sweep_would_have_returned(svc):
    """`TurboBM25.point_scores`: the exact score of GIVEN documents, the
    same bits the sweep returns for them; 0 for a document that holds no
    term and for a deleted one."""
    snap = svc.serving.snapshot()
    eng = snap.engine("body")
    terms = [("alpha", 1.0), ("kappa", 2.0)]
    scores, parts, ords = eng.search_many([[terms]], k=40)[0]
    for part in range(len(eng.turbos)):
        at = np.flatnonzero((parts[0] == part) & (scores[0] > 0))
        assert len(at)
        got = eng.point_scores(terms, part, ords[0][at])
        assert np.array_equal(got, scores[0][at])
    p0 = snap.partitions[0]
    dead = np.flatnonzero(~p0.live)[:3].astype(np.int32)
    assert len(dead) and not eng.point_scores(terms, 0, dead).any()
    assert not eng.point_scores([("zzz_missing", 1.0)], 0,
                                np.arange(5, dtype=np.int32)).any()
    assert eng.point_scores(terms, 0, np.empty(0, np.int32)).shape == (0,)


def test_the_two_sides_run_side_by_side(svc, monkeypatch):
    """The kNN side has a thread of its own beside the caller's BM25
    side, and `tpu_hybrid` counts each side's time and the batch's: no
    side outlasts the wall."""
    import threading

    ran_on = {}
    dispatch = serving.serving_dispatch

    def noted(eng, *a, **kw):
        ran_on[type(eng).__name__] = threading.current_thread().name
        return dispatch(eng, *a, **kw)

    monkeypatch.setattr(serving, "serving_dispatch", noted)
    body = CASES["bm25-only hits below the nearest"]
    before = serving.hybrid_node_stats()
    fast, dense = both(svc, body)
    after = serving.hybrid_node_stats()
    assert_same(fast, dense, body)
    assert ran_on == {"KnnEngine": "es-hybrid-side",
                      "TurboEngine": threading.current_thread().name}
    wall = after["wall_us"] - before["wall_us"]
    for side in ("bm25_us", "knn_us"):
        assert 0 < after[side] - before[side] <= wall


def test_an_error_on_the_knn_side_is_the_batchs(svc, monkeypatch):
    """The side on its own thread raises in the caller: an unexpected
    error is a counted reject (the dense executor answers), as on every
    route."""
    from elasticsearch_tpu.parallel.knn import KnnEngine

    def boom(self, *a, **kw):
        raise RuntimeError("knn side")

    monkeypatch.setattr(KnnEngine, "search_many", boom)
    before = serving.serving_fault_stats()["fastpath_reject_error"]
    assert svc.serving.try_search(CASES["k above size"],
                                  "query_then_fetch") is None
    assert serving.serving_fault_stats()["fastpath_reject_error"] == \
        before + 1


def test_the_spans_and_the_stats_section(svc):
    from elasticsearch_tpu.common import metrics, tracing

    tc = tracing.TraceContext()
    with tracing.activate(tc):
        svc.serving.try_search(CASES["k below size"], "query_then_fetch")
    names = [s["name"] for s in tc.span_dicts()]
    for name in ("route", "dispatch.hybrid_bm25", "dispatch.hybrid_knn",
                 "dispatch.hybrid_join", "device", "demux", "fetch"):
        assert name in names, names
    assert names.count("device") == 2           # one an engine
    route = next(s for s in tc.span_dicts() if s["name"] == "route")
    assert route["meta"]["hybrid"] == 1
    hist = metrics.search_latency_stats()
    for name in ("dispatch.hybrid_bm25", "dispatch.hybrid_knn",
                 "dispatch.hybrid_join"):
        assert hist[name]["count"] >= 1
    assert set(serving.hybrid_node_stats()) == {
        "hybrid_queries", "hybrid_device", "hybrid_host",
        "point_scored_docs", "knn_only_hits", "bm25_us", "knn_us",
        "wall_us"}


def test_a_fresh_nodes_stats_hold_the_section():
    """`tpu_hybrid` of GET /_nodes/stats is declared where `tpu_knn` is:
    every counter is there (as a number) before any hybrid body came."""
    from elasticsearch_tpu.rest import handlers

    section = handlers._tpu_hybrid_stats()
    assert set(section) == set(serving.hybrid_node_stats())
    assert all(isinstance(v, int) for v in section.values())
