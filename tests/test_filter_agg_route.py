"""The filter + bucket route of the aggregation engine (PR 40), at tiny size
on the CPU: Rally's `http_logs` shapes (a time range, optionally a status,
one `date_histogram`, `size` 0) over the benchmark's own seeded log lines,
installed as the benchmark installs them.

Three sides have to agree to the last count: the device route
(`search/serving.py` -> ONE scheduler dispatch a request ->
`AggDeviceEngine._run_filter_works` -> `kernels.agg_filter_counts`), the
host aggregators (`_search_dense`, the path every other request takes)
and the plain numpy reference of the benchmark
(benchmark/kinds/request/filter_agg.py), which imports nothing of the
program. The device route is steered onto tiny segments by shrinking
AGG_DEVICE_MIN_DOCS, as tests/test_agg_device.py does.

Since PR 41 a range runs only the pair chunks it can touch and a segment it
misses launches no program (the kernel and the planner alone:
tests/test_agg_chunk_pruning.py); the last section holds the route to that,
on a corpus of twelve chunks a segment.
"""

import json
import threading

import numpy as np
import pytest

import elasticsearch_tpu.search.aggregations as agg_mod
from benchmark.manifest import ROOT, Manifest
from elasticsearch_tpu.cluster.state import IndexMetadata
from elasticsearch_tpu.common import integrity, metrics
from elasticsearch_tpu.common.faults import clear as clear_faults, inject
from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.index.segment_io import segment_to_blob
from elasticsearch_tpu.search import agg_device
from elasticsearch_tpu.search.serving import extract_filter_agg_plan

CELL = Manifest(ROOT).cell("http-logs.filter-agg-open")
KIND = CELL.corpus_kind
REQ = CELL.request_kind
SEED = 1556403449
SHAPES = tuple(CELL.traffic["request"]["shapes"])
DAY = 86_400_000


def _config(docs=None):
    cfg = json.loads(json.dumps(CELL.config))
    cfg["corpus"].update(KIND.TINY if docs is None else {"docs": docs})
    return cfg


@pytest.fixture(autouse=True)
def _device_route_on_tiny_segments(monkeypatch):
    clear_faults()
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1)
    # the module's default: a test of `search.max_buckets` that ran before
    # in this worker leaves its own value behind (88 days are 2,112 hours)
    monkeypatch.setattr(agg_mod, "MAX_BUCKETS", 65536)
    yield
    clear_faults()


@pytest.fixture(scope="module")
def logs():
    """(config, parts, service): the tiny corpus in three segments."""
    cfg = _config()
    parts = KIND.make_parts(cfg, SEED)
    svc = IndexService(IndexMetadata(
        index="http_logs", uuid="u", settings=Settings({}),
        mappings=cfg["index"]["mappings"]))
    for i, part in enumerate(parts):
        svc.shards[0].install_segment(
            segment_to_blob(KIND.segment(cfg, part, i)),
            np.ones(part.n, bool))
    svc.shards[0].fill_seqno_gaps(sum(p.n for p in parts) - 1)
    yield cfg, parts, svc
    svc.close()


def _requests(cfg, n=64):
    return REQ.Requests(CELL.traffic["request"], cfg, CELL.traffic, n,
                        np.random.default_rng(0), None)


def _counts():
    with agg_device._COUNTS_LOCK:
        return dict(agg_device._COUNTS)


def _moved(before):
    """The counters that moved (`agg_bytes` aside: it moves when a layout
    or a column is first built, whoever asks first)."""
    return {k: v - before[k] for k, v in _counts().items()
            if v != before[k] and k != "agg_bytes"}


PRUNING_COUNTERS = ("agg_chunks_total", "agg_chunks_run",
                    "agg_reductions_pruned")


def _strip(resp):
    return json.dumps({k: v for k, v in resp.items() if k != "took"})


def _body(query, interval="1h", **params):
    body = {"size": 0, "track_total_hits": True, "aggs": {"by_time": {
        "date_histogram": dict(field="@timestamp", fixed_interval=interval,
                               **params)}}}
    if query is not None:
        body["query"] = query
    return body


def _range(lo, hi, **more):
    return {"bool": {"filter": [
        {"range": {"@timestamp": {"gte": lo, "lt": hi}}},
        *({"term": {k: v}} for k, v in more.items())]}}


def _agrees_with_reference(resp, ref):
    checked = REQ.numbers([(resp, ref)], {}, 0)
    assert all(v["ok"] for v in checked.values()), checked


# ---------------------------------------------------------------------------
# the mix's five shapes: device route = host aggregators = numpy reference
# ---------------------------------------------------------------------------


def test_the_corpus_is_three_time_ordered_segments(logs):
    cfg, parts, svc = logs
    assert len(parts) == 3 and sum(p.n for p in parts) == KIND.TINY["docs"]
    ts = np.concatenate([p.ts for p in parts])
    assert np.all(np.diff(ts) >= 0) and np.all(ts % 1000 == 0)
    t0 = KIND.start_ms(cfg["corpus"])
    assert t0 <= ts[0] and ts[-1] < t0 + cfg["corpus"]["days"] * DAY
    status = np.concatenate([p.status for p in parts])
    assert 0.74 < np.mean(status == 200) < 0.82
    assert np.all(np.concatenate([p.size for p in parts])[status == 304] == 0)
    # the same seed draws the same lines, whatever the thread count
    again = KIND.make_parts(cfg, SEED)
    assert all(np.array_equal(a.ts, b.ts) and np.array_equal(a.size, b.size)
               for a, b in zip(parts, again))
    assert svc.shards[0].segment_count() == 3


@pytest.mark.parametrize("shape", SHAPES)
def test_every_shape_three_ways(logs, shape):
    cfg, parts, svc = logs
    reqs = _requests(cfg)
    ref = REQ.reference(cfg, parts)
    cycle = CELL.traffic["request"]["cycle"]
    mine = [reqs.request(j) for j in range(64)
            if cycle[j % len(cycle)] == shape][:6]
    assert mine and all(r.shape == shape for r in mine)
    for r in mine:
        assert extract_filter_agg_plan(r.body, svc.mapper) is not None
        before = _counts()
        fast = svc.serving.try_search(r.body, "query_then_fetch")
        moved = _moved(before)
        assert fast is not None
        # ONE dispatch a request, its three segments in it, every match
        # set made on the device, no host mask, no fallback
        chunks = {k: moved.pop(k, 0) for k in PRUNING_COUNTERS}
        assert moved == {"agg_device_dispatches": 1, "agg_reductions": 3,
                         "agg_queries": 3, "filter_device": 3}, moved
        # the chunks of all three layouts met, those in the range run: all
        # of them without a range, and no segment answered with no program
        total = sum(-(-p.n // agg_device.AGG_PAIR_GRAN) for p in parts)
        assert chunks["agg_chunks_total"] == total
        if r.lo is None:
            assert chunks == {"agg_chunks_total": total,
                              "agg_chunks_run": total,
                              "agg_reductions_pruned": 0}
        else:
            assert 0 < chunks["agg_chunks_run"] < total
            assert chunks["agg_reductions_pruned"] >= 1
        dense = svc._search_dense(r.body)
        assert _strip(fast) == _strip(dense)
        _agrees_with_reference(fast, ref.answer(r))
        if r.lo is not None:        # a range's start is to the millisecond
            assert r.hi - r.lo == 1000 * CELL.traffic["request"][
                "shapes"][shape]["range_s"]


def test_the_mix_is_the_cycle_the_issue_wrote(logs):
    cfg, _parts, _svc = logs
    req = CELL.traffic["request"]
    assert req["cycle"] == [
        "RangeHourly", "Status200sInRange", "RangeTenMinute", "HourlyAgg",
        "RangeHourly", "Status400sInRange", "RangeTenMinute",
        "Status200sInRange"]
    reqs = _requests(cfg, 32)
    assert [reqs.request(j).shape for j in range(8)] == req["cycle"]
    # what is another set of programs on the device: the layout's
    # granularity and the number of filter columns
    assert reqs.variants() == [("1h", False), ("1h", True), ("10m", False)]
    t0 = KIND.start_ms(cfg["corpus"])
    end = t0 + cfg["corpus"]["days"] * DAY
    off_second = 0
    for j in range(32):
        r = reqs.request(j)
        assert r.body["size"] == 0 and r.body["track_total_hits"] is True
        if r.lo is not None:
            assert t0 <= r.lo and r.hi <= end
            off_second += r.lo % 1000 != 0
    assert off_second >= 20
    assert json.dumps(reqs.request(3).body) == json.dumps(
        reqs.request(11).body)      # HourlyAgg is the same body every time


BOUNDS = {
    "on_second_boundaries": (5 * DAY, 12 * DAY),
    "off_second_boundaries": (5 * DAY + 1, 12 * DAY + 999),
    "one_millisecond_before_a_line": None,      # filled from the data
    "empty_range": (9 * DAY, 9 * DAY),
    "wider_than_the_data": (-400 * DAY, 400 * DAY),
    "before_the_data": (-9 * DAY, -2 * DAY),
    "misses_the_first_and_last_segment": None,  # filled from the data
}


@pytest.mark.parametrize("case", sorted(BOUNDS))
def test_bounds_are_exact_to_the_millisecond(logs, case):
    cfg, parts, svc = logs
    t0 = KIND.start_ms(cfg["corpus"])
    if case == "one_millisecond_before_a_line":
        # gte just above a line's own second keeps it out; lt on the
        # second after the last line of the range keeps that one in
        lo, hi = int(parts[1].ts[10]) + 1, int(parts[1].ts[-10]) + 1
    elif case == "misses_the_first_and_last_segment":
        lo, hi = int(parts[1].ts[0]), int(parts[1].ts[-1]) + 1
    else:
        lo, hi = (t0 + b for b in BOUNDS[case])
    ref = REQ.reference(cfg, parts)
    for interval, status in (("1h", None), ("10m", None), ("1h", 200)):
        more = {} if status is None else {"status": status}
        body = _body(_range(lo, hi, **more), interval)
        fast = svc.serving.try_search(body, "query_then_fetch")
        assert fast is not None
        assert _strip(fast) == _strip(svc._search_dense(body))
        _agrees_with_reference(fast, ref.answer(REQ.FilterAggRequest(
            body=body, lo=lo, hi=hi, status=status,
            interval=REQ.interval_ms(interval))))
    total = fast["hits"]["total"]["value"]
    if case in ("empty_range", "before_the_data"):
        assert total == 0 and fast["aggregations"]["by_time"]["buckets"] == []
    if case == "wider_than_the_data":
        assert svc.serving.try_search(
            _body(_range(lo, hi)), "query_then_fetch")["hits"]["total"][
                "value"] == KIND.TINY["docs"]
    if case == "misses_the_first_and_last_segment":
        assert svc.serving.try_search(
            _body(_range(lo, hi)), "query_then_fetch")["hits"]["total"][
                "value"] == parts[1].n


def test_the_other_forms_of_the_envelope(logs):
    """`gt` / `lte`, a date as a string, an `offset`, `aggregations` for
    `aggs`, a `term` alone, no query at all: all recognised, all equal to
    the host path."""
    cfg, parts, svc = logs
    t0 = KIND.start_ms(cfg["corpus"])
    lo = int(parts[0].ts[40])
    bodies = [
        _body(None),
        _body({"match_all": {}}, "12h"),
        _body({"bool": {"filter": {"range": {"@timestamp": {
            "gt": lo, "lte": lo + 3 * DAY}}}}}),
        _body({"bool": {"filter": [{"range": {"@timestamp": {
            "gte": "1998-05-05T00:00:00Z",
            "lt": "1998-05-09T06:00:00.500Z"}}}]}}, "1h", offset=1_800_000),
        _body({"bool": {"filter": [{"term": {"status": 404}}]}}, "1d"),
        _body({"bool": {"filter": [
            {"range": {"size": {"gte": 1000, "lt": 20000}}},
            {"range": {"@timestamp": {"gte": t0 + DAY}}}]}}, "6h",
            min_doc_count=0),
    ]
    swapped = dict(bodies[2])
    swapped["aggregations"] = swapped.pop("aggs")
    for body in bodies + [swapped]:
        before = _counts()
        fast = svc.serving.try_search(body, "query_then_fetch")
        assert fast is not None, body
        assert _moved(before)["filter_device"] == 3
        assert _strip(fast) == _strip(svc._search_dense(body))
        assert fast["hits"]["total"]["value"] > 0


UNRECOGNISED = {
    "a_sub_aggregation": lambda b: b["aggs"]["by_time"].update(
        aggs={"bytes": {"sum": {"field": "size"}}}),
    "a_terms_bucket": lambda b: b.update(
        aggs={"by_status": {"terms": {"field": "status"}}}),
    "size_10": lambda b: b.update(size=10),
    "two_aggregations": lambda b: b["aggs"].update(
        other={"date_histogram": {"field": "@timestamp",
                                  "fixed_interval": "1d"}}),
    "a_calendar_interval": lambda b: b["aggs"]["by_time"].update(
        date_histogram={"field": "@timestamp", "calendar_interval": "day"}),
    "min_doc_count_1": lambda b: b["aggs"]["by_time"][
        "date_histogram"].update(min_doc_count=1),
    "a_must_clause": lambda b: b["query"]["bool"].update(
        must=[{"term": {"status": 200}}]),
    "no_exact_total": lambda b: b.pop("track_total_hits"),
    "a_sort": lambda b: b.update(sort=[{"@timestamp": "desc"}]),
}


@pytest.mark.parametrize("case", sorted(UNRECOGNISED))
def test_an_unrecognised_envelope_keeps_the_old_path(logs, case):
    cfg, parts, svc = logs
    lo = int(parts[0].ts[40])
    body = _body(_range(lo, lo + 7 * DAY))
    UNRECOGNISED[case](body)
    assert extract_filter_agg_plan(body, svc.mapper) is None
    before = _counts()
    assert svc.serving.try_search(body, "query_then_fetch") is None
    resp = svc.search(body)
    moved = _moved(before)
    assert "filter_device" not in moved, moved
    assert resp["hits"]["total"]["value"] > 0


# ---------------------------------------------------------------------------
# batching: concurrent requests share one padded batch a (segment, layout)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 4, 16])
def test_concurrent_requests_merge_into_one_dispatch(logs, n):
    """n requests in one `_msearch` are one dispatch (at most
    SMALL_BATCH_MAX ride the lane, more go direct): three reductions when
    all share a granularity, six when hour and minute layouts mix; every
    answer is its solo answer."""
    cfg, parts, svc = logs
    reqs = _requests(cfg)
    bodies = [reqs.request(j).body for j in range(n)]
    solo = [_strip(svc._search_dense(b)) for b in bodies]
    before = _counts()
    out = svc.serving.try_msearch(bodies, "query_then_fetch")
    moved = _moved(before)
    assert [_strip(r) for r in out] == solo
    assert moved["agg_device_dispatches"] == 1
    assert moved["agg_reductions"] == (3 if n == 1 else 6)
    assert moved["filter_device"] == 3 * n and "filter_host" not in moved


def test_requests_from_many_threads_share_dispatches(logs):
    cfg, parts, svc = logs
    reqs = _requests(cfg)
    bodies = [reqs.request(j).body for j in range(16)]
    want = [_strip(svc._search_dense(b)) for b in bodies]
    got = [None] * len(bodies)
    before = _counts()

    def one(i):
        got[i] = _strip(svc.serving.try_search(bodies[i],
                                               "query_then_fetch"))

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == want
    moved = _moved(before)
    assert moved["filter_device"] == 48
    assert moved["agg_device_dispatches"] <= 16


def test_the_shard_query_phase_returns_the_partial(logs):
    """`try_query_phase` (the distributed shard executor's adapter): the
    shard's reduced partial and exact total, finalized by the coordinator
    to the same response."""
    from elasticsearch_tpu.search.aggregations import finalize_shard_aggs

    cfg, parts, svc = logs
    body = _requests(cfg).request(1).body
    before = _counts()
    qr = svc.serving.try_query_phase(body)
    assert qr is not None and _moved(before)["filter_device"] == 3
    dense = svc._search_dense(body)
    assert (qr.total, qr.relation, qr.hits) == (
        dense["hits"]["total"]["value"], "eq", [])
    assert finalize_shard_aggs(body, [qr.aggregations]) == \
        dense["aggregations"]


# ---------------------------------------------------------------------------
# nothing is weakened
# ---------------------------------------------------------------------------


def test_agg_flag_off_answers_every_shape_from_the_host(logs, monkeypatch):
    cfg, parts, svc = logs
    reqs = _requests(cfg)
    bodies = [reqs.request(j).body for j in range(8)]
    on = [_strip(svc.search(b, request_cache=False)) for b in bodies]
    monkeypatch.setenv("ES_TPU_AGG", "0")
    before = _counts()
    off = [_strip(svc.search(b, request_cache=False)) for b in bodies]
    assert off == on and _counts() == before


def test_an_agg_reduce_fault_is_contained_and_counted(logs):
    """The fault site `agg_reduce` on one layout: the requests of that
    reduction are answered the old way, with the same bytes, and counted
    as host fallbacks; the next request runs on the device again."""
    cfg, parts, svc = logs
    body = _requests(cfg).request(0).body
    want = _strip(svc.search(body, request_cache=False))
    # the hour layout of the segment the range starts in
    lo = _requests(cfg).request(0).lo
    seg = next(v.segment for v, p in zip(
        svc.shards[0].acquire_searcher().views, parts) if p.ts[-1] >= lo)
    serial = seg._device["aggdev:uniq:@timestamp:3600000"].serial
    before = _counts()
    with inject(f"agg_reduce#{serial}:raise@1"):
        got = _strip(svc.search(body, request_cache=False))
    moved = _moved(before)
    assert got == want and moved["agg_host_fallbacks"] >= 1
    before = _counts()
    assert _strip(svc.search(body, request_cache=False)) == want
    assert "agg_host_fallbacks" not in _moved(before)


def test_filter_columns_are_ledgered_scrubbed_and_budgeted(monkeypatch):
    cfg = _config(1500)
    parts = KIND.make_parts(cfg, 7)
    monkeypatch.setattr(agg_device, "_ENGINE", None)
    integrity.reset_scrub_for_tests()
    svc = IndexService(IndexMetadata(
        index="http_logs", uuid="u2", settings=Settings({}),
        mappings=cfg["index"]["mappings"]))
    for i, part in enumerate(parts):
        svc.shards[0].install_segment(
            segment_to_blob(KIND.segment(cfg, part, i)),
            np.ones(part.n, bool))
    body = _body(_range(int(parts[0].ts[5]), int(parts[2].ts[-5]),
                        status=200))
    want = _strip(svc._search_dense(body))
    assert _strip(svc.serving.try_search(body, "query_then_fetch")) == want
    eng = agg_device.default_engine()
    regions = eng.layout_serials()
    # one region a (segment, field) beside one a (segment, layout)
    assert sum(n.startswith("aggflt") for n in regions) == 6
    assert eng.hbm_bytes() == eng.ledger_bytes() > 0
    region = next(n for n in regions if n.endswith("_status"))
    base = integrity.integrity_stats()["scrub_repairs"]
    with inject(f"hbm_region#{region}:raise@1x1"):
        results = [integrity.scrub_once()
                   for _ in range(integrity.scrub_registry_size())]
    assert [r["region"].endswith(region) for r in results
            if r and r["result"] == "mismatch"] == [True]
    assert integrity.integrity_stats()["scrub_repairs"] == base + 1
    assert _strip(svc.serving.try_search(body, "query_then_fetch")) == want
    svc.close()

    # a budget that holds nothing: the request is answered as before,
    # from the host, and counted
    monkeypatch.setenv("ES_TPU_AGG_HBM_FRAC", "0.0")
    monkeypatch.setattr(agg_device, "_ENGINE", None)
    svc = IndexService(IndexMetadata(
        index="http_logs", uuid="u3", settings=Settings({}),
        mappings=cfg["index"]["mappings"]))
    svc.shards[0].install_segment(
        segment_to_blob(KIND.segment(cfg, parts[0], 0)),
        np.ones(parts[0].n, bool))
    before = _counts()
    assert svc.serving.try_search(body, "query_then_fetch") is None
    moved = _moved(before)
    assert moved["agg_host_fallbacks"] == 1 and "filter_device" not in moved
    svc.close()


def test_a_deleted_document_or_a_multi_valued_field_declines():
    svc = IndexService(IndexMetadata(
        index="l", uuid="u4", settings=Settings({}), mappings={
            "properties": {"@timestamp": {"type": "date"},
                           "status": {"type": "integer"}}}))
    t0 = 893894400000
    for i in range(300):
        svc.index_doc(str(i), {"@timestamp": t0 + 60_000 * i,
                               "status": [200, 304] if i == 7 else 200})
    svc.refresh()
    ranged = _body(_range(t0, t0 + DAY))
    with_status = _body(_range(t0, t0 + DAY, status=304))
    assert svc.serving.try_search(ranged, "query_then_fetch") is not None
    # doc 7 holds two statuses: one rank a doc cannot say "any value"
    before = _counts()
    assert svc.serving.try_search(with_status, "query_then_fetch") is None
    assert _moved(before)["agg_host_fallbacks"] == 1
    assert svc.search(with_status)["hits"]["total"]["value"] == 1
    svc.delete_doc("3")
    svc.refresh()
    before = _counts()
    assert svc.serving.try_search(ranged, "query_then_fetch") is None
    assert _moved(before) == {}
    assert svc.search(ranged)["hits"]["total"]["value"] == 299
    svc.close()


def test_request_cache_false_bypasses_the_shard_request_cache(logs):
    cfg, parts, svc = logs
    body = _requests(cfg).request(3).body       # HourlyAgg: one body
    svc.search(body)
    before = dict(svc.request_cache_stats)
    c0 = _counts()
    svc.search(body)
    assert svc.request_cache_stats["hits"] == before["hits"] + 1
    assert _moved(c0) == {}
    svc.search(body, request_cache=False)
    assert svc.request_cache_stats["hits"] == before["hits"] + 1
    assert _moved(c0)["filter_device"] == 3


def test_spans_and_counters_are_declared_and_observed_once_a_dispatch(logs):
    cfg, parts, svc = logs
    names = ("dispatch.prep", "dispatch.launch", "dispatch.device_wait",
             "dispatch.finish", "dispatch.agg_plan", "dispatch.agg_fold",
             "dispatch.rescore", "device")

    def seen():
        return {n: metrics.summary(n)["count"] for n in names}

    before = seen()
    svc.serving.try_msearch([_requests(cfg).request(j).body
                             for j in range(5)], "query_then_fetch")
    assert {n: seen()[n] - before[n] for n in names} == dict.fromkeys(
        names, 1)
    for name in ("agg_reductions", "filter_device", "filter_host",
                 *PRUNING_COUNTERS):
        assert name in metrics.counter_values()
        assert name in agg_device.agg_stats()
    # the pruning's counters move once a (segment, layout) reduction of the
    # dispatch, in the engine's stats and in the metrics registry alike
    c0, m0 = _counts(), metrics.counter_values()
    svc.serving.try_msearch([_requests(cfg).request(j).body
                             for j in range(5)], "query_then_fetch")
    moved = _moved(c0)
    assert moved["agg_device_dispatches"] == 1 and moved["agg_reductions"] == 6
    chunks = sum(-(-p.n // agg_device.AGG_PAIR_GRAN) for p in parts)
    # four requests on the hour layouts, one on the minute layouts
    assert moved["agg_chunks_total"] == 5 * chunks
    assert 0 < moved["agg_chunks_run"] < moved["agg_chunks_total"]
    for name in PRUNING_COUNTERS:
        assert metrics.counter_values()[name] - m0[name] == moved.get(name, 0)


# ---------------------------------------------------------------------------
# a range runs the chunks it can touch (PR 41): twelve chunks a segment
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def wide_logs():
    """(config, parts, service): 36,000 log lines, three segments of
    twelve 1024-pair chunks each (the tiny corpus has two a segment: a
    week is one of them or both)."""
    cfg = _config(36_000)
    parts = KIND.make_parts(cfg, SEED)
    svc = IndexService(IndexMetadata(
        index="http_logs", uuid="u5", settings=Settings({}),
        mappings=cfg["index"]["mappings"]))
    for i, part in enumerate(parts):
        svc.shards[0].install_segment(
            segment_to_blob(KIND.segment(cfg, part, i)),
            np.ones(part.n, bool))
    svc.shards[0].fill_seqno_gaps(sum(p.n for p in parts) - 1)
    yield cfg, parts, svc
    svc.close()


@pytest.fixture
def launches(monkeypatch):
    """The calls of `kernels.agg_filter_counts`: the programs launched."""
    from elasticsearch_tpu.parallel import kernels

    calls, real = [], kernels.agg_filter_counts

    def spy(bounds, crange, *a, **kw):
        calls.append(np.asarray(crange))
        return real(bounds, crange, *a, **kw)

    monkeypatch.setattr(kernels, "agg_filter_counts", spy)
    return calls


def test_a_range_that_misses_two_segments_launches_one_program(wide_logs,
                                                               launches):
    cfg, parts, svc = wide_logs
    ref = REQ.reference(cfg, parts)
    lo, hi = int(parts[1].ts[2_000]), int(parts[1].ts[9_000])
    for interval, status in (("1h", None), ("10m", None), ("1h", 200)):
        more = {} if status is None else {"status": status}
        body = _body(_range(lo, hi, **more), interval)
        del launches[:]
        before = _counts()
        fast = svc.serving.try_search(body, "query_then_fetch")
        moved = _moved(before)
        # three reductions ANSWERED, two of them with no program; the
        # requests' match sets are still the device route's (none is a
        # host mask), the requests' segments all answered
        assert moved["agg_reductions"] == 3
        assert moved["agg_reductions_pruned"] == 2
        assert moved["agg_device_dispatches"] == 1
        assert moved["filter_device"] == 3 and moved["agg_queries"] == 3
        assert "filter_host" not in moved
        assert "agg_host_fallbacks" not in moved
        assert len(launches) == 1
        c0, c1 = launches[0][0]
        assert 1 <= c0 < c1 <= 10 and moved["agg_chunks_run"] == c1 - c0
        assert moved["agg_chunks_total"] == 36
        assert _strip(fast) == _strip(svc._search_dense(body))
        _agrees_with_reference(fast, ref.answer(REQ.FilterAggRequest(
            body=body, lo=lo, hi=hi, status=status,
            interval=REQ.interval_ms(interval))))
        assert fast["hits"]["total"]["value"] > 0
    # a range before the data: three reductions, no program at all, the
    # empty answer the host gives
    t0 = KIND.start_ms(cfg["corpus"])
    body = _body(_range(t0 - 9 * DAY, t0 - 2 * DAY))
    del launches[:]
    before = _counts()
    fast = svc.serving.try_search(body, "query_then_fetch")
    moved = _moved(before)
    assert launches == [] and moved["agg_reductions_pruned"] == 3
    assert moved["agg_reductions"] == 3 and "agg_chunks_run" not in moved
    assert _strip(fast) == _strip(svc._search_dense(body))
    assert fast["hits"]["total"]["value"] == 0


def test_the_share_of_chunks_run_by_shape(wide_logs, launches):
    """`agg_chunks_run` over `agg_chunks_total`: 1.0 for `HourlyAgg` (no
    range: every chunk of every segment, three programs), under 0.3 for
    every `RangeHourly` of the mix (7 of 88 days: at most two segments'
    programs), less again for a day."""
    cfg, parts, svc = wide_logs
    reqs = _requests(cfg)
    shares = {}
    for j in range(32):
        r = reqs.request(j)
        del launches[:]
        before = _counts()
        fast = svc.serving.try_search(r.body, "query_then_fetch")
        moved = _moved(before)
        assert _strip(fast) == _strip(svc._search_dense(r.body))
        assert moved["agg_chunks_total"] == 36
        share = moved.get("agg_chunks_run", 0) / moved["agg_chunks_total"]
        shares.setdefault(r.shape, []).append(share)
        assert len(launches) == 3 - moved.get("agg_reductions_pruned", 0)
        if r.shape == "HourlyAgg":
            assert len(launches) == 3
        else:
            assert len(launches) <= 2
    assert set(shares) == set(SHAPES)
    assert shares["HourlyAgg"] == [1.0] * 4
    for shape in ("RangeHourly", "Status200sInRange", "Status400sInRange"):
        assert max(shares[shape]) < 0.3, shares
    assert max(shares["RangeTenMinute"]) <= 2 / 36
    # over the cycle: an eighth of the requests run everything, the rest
    # a tenth or less
    mean = np.mean([x for v in shares.values() for x in v])
    assert 0.125 < mean < 0.25


def test_after_the_warm_up_no_request_of_the_cycle_builds_a_program(
        wide_logs):
    """Once round the cycle one at a time and one burst at each width
    builds every program the route has: a request's range picks no program
    of its own (the chunk axis' length is an operand, not a shape), so the
    cycle's later requests, other ranges every one, build none."""
    from elasticsearch_tpu.common import hbm_ledger
    from elasticsearch_tpu.parallel import kernels

    cfg, parts, svc = wide_logs
    hbm_ledger.install_jit_listener()
    reqs = _requests(cfg)
    bodies = [reqs.request(j).body for j in range(64)]
    for b in bodies[:8]:
        svc.serving.try_search(b, "query_then_fetch")
    for width in (4, 16):
        svc.serving.try_msearch(bodies[:width], "query_then_fetch")
    # the host's answers first: its collects build programs of their own
    want = [_strip(svc._search_dense(b)) for b in bodies[40:64]]
    built = hbm_ledger.compile_stats()["jit_builds"]
    programs = kernels.agg_filter_counts._cache_size()
    assert programs >= 1
    for b in bodies[8:40]:
        assert svc.serving.try_search(b, "query_then_fetch") is not None
    for width, at in ((4, 40), (16, 48)):
        out = svc.serving.try_msearch(bodies[at:at + width],
                                      "query_then_fetch")
        assert [_strip(r) for r in out] == want[at - 40:at - 40 + width]
    assert kernels.agg_filter_counts._cache_size() == programs
    assert hbm_ledger.compile_stats()["jit_builds"] == built
