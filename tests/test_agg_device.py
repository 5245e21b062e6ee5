"""Differential suite for the device analytics tier (PR 18).

The host aggregators are the exact reference: every device-served
response must match the host path BIT-FOR-BIT — including rendered
float metrics, under injected `agg_reduce` faults (containment → host
fallback), with `ES_TPU_AGG=0` (verbatim host path, zero device
counters), and after an `hbm_region` scrub repair of a flipped agg
column. Device routing is forced by shrinking AGG_DEVICE_MIN_DOCS, the
same seam the old terms-count kernel test used.
"""

import numpy as np
import pytest

import elasticsearch_tpu.search.aggregations as agg_mod
from elasticsearch_tpu.cluster.state import IndexMetadata
from elasticsearch_tpu.common import integrity, metrics
from elasticsearch_tpu.common.faults import clear as clear_faults, inject
from elasticsearch_tpu.common.settings import Settings, knob
from elasticsearch_tpu.index.index_service import IndexService
from elasticsearch_tpu.search import agg_device

BASE_MS = 1_600_000_000_000        # 2020-09-13T12:26:40Z


@pytest.fixture(autouse=True)
def _clean_faults():
    clear_faults()
    yield
    clear_faults()


def _make_service(n=2500, seed=7):
    meta = IndexMetadata(
        index="agg", uuid="u", settings=Settings({}), mappings={
            "properties": {"tag": {"type": "keyword"},
                           "body": {"type": "text"},
                           "price": {"type": "float"},
                           "ts": {"type": "long"}}})
    svc = IndexService(meta)
    rng = np.random.default_rng(seed)
    for i in range(n):
        tags = [f"t{rng.integers(0, 40)}"]
        if i % 3 == 0:
            tags.append(f"t{rng.integers(0, 40)}")   # multi-valued docs
        doc = {"tag": tags, "body": "w" + str(i % 7),
               "ts": BASE_MS + int(rng.integers(0, 90 * 86_400_000))}
        if i % 5 != 0:                               # price gaps: exists
            doc["price"] = float(np.round(rng.normal(40, 12), 2))
        svc.index_doc(str(i), doc)
    svc.refresh()
    return svc


def _ab(svc, body, monkeypatch):
    """(device response, host response) for one search body."""
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1)
    dev = svc._search_dense(body)["aggregations"]
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1 << 60)
    host = svc._search_dense(body)["aggregations"]
    return dev, host


def _counts():
    with agg_device._COUNTS_LOCK:
        return dict(agg_device._COUNTS)


# ---------------------------------------------------------------------------
# bit-identity across agg shapes
# ---------------------------------------------------------------------------


def test_terms_device_matches_host(monkeypatch):
    svc = _make_service()
    before = _counts()
    body = {"query": {"match": {"body": "w3"}}, "size": 0,
            "aggs": {"tags": {"terms": {"field": "tag", "size": 50}}}}
    dev, host = _ab(svc, body, monkeypatch)
    assert dev == host
    assert sum(b["doc_count"] for b in dev["tags"]["buckets"]) > 0
    after = _counts()
    assert after["agg_queries"] == before["agg_queries"] + 1
    assert after["agg_device_dispatches"] > before["agg_device_dispatches"]
    svc.close()


def test_date_histogram_offset_format_and_calendar(monkeypatch):
    svc = _make_service()
    for body in [
        {"size": 0, "aggs": {"d": {"date_histogram": {
            "field": "ts", "fixed_interval": "7d",
            "offset": 10_800_000}}}},                 # +3h offset
        {"size": 0, "aggs": {"d": {"date_histogram": {
            "field": "ts", "calendar_interval": "month"}}}},
        {"size": 0, "aggs": {"d": {"date_histogram": {
            "field": "ts", "fixed_interval": "12h"}}}},
    ]:
        dev, host = _ab(svc, body, monkeypatch)
        assert dev == host                  # includes key_as_string render
        assert len(dev["d"]["buckets"]) > 1
    svc.close()


def test_stats_under_terms_subagg_bit_identical(monkeypatch):
    svc = _make_service()
    body = {"query": {"match": {"body": "w1"}}, "size": 0,
            "aggs": {"tags": {
                "terms": {"field": "tag", "size": 50},
                "aggs": {"p": {"stats": {"field": "price"}},
                         "a": {"avg": {"field": "price"}},
                         "lo": {"min": {"field": "price"}},
                         "nv": {"value_count": {"field": "price"}}}}}}
    dev, host = _ab(svc, body, monkeypatch)
    assert dev == host        # float sums reduced in host order: bitwise
    svc.close()


def test_histogram_and_date_histogram_subaggs(monkeypatch):
    svc = _make_service()
    for body in [
        {"size": 0, "aggs": {"h": {
            "histogram": {"field": "price", "interval": 7.5},
            "aggs": {"s": {"stats": {"field": "price"}}}}}},
        {"size": 0, "aggs": {"d": {
            "date_histogram": {"field": "ts", "calendar_interval": "month"},
            "aggs": {"s": {"extended_stats": {"field": "price"}}}}}},
    ]:
        dev, host = _ab(svc, body, monkeypatch)
        assert dev == host
    svc.close()


def test_empty_mask_matches_host(monkeypatch):
    svc = _make_service(n=1200)
    body = {"query": {"match": {"body": "nosuchtoken"}}, "size": 0,
            "aggs": {"tags": {"terms": {"field": "tag"}},
                     "h": {"histogram": {"field": "price", "interval": 5}}}}
    dev, host = _ab(svc, body, monkeypatch)
    assert dev == host
    assert dev["tags"]["buckets"] == []
    svc.close()


def test_primed_batch_rungs_retrace_nothing(monkeypatch):
    """Once the scheduler's rungs are primed through `extend_qc_sizes` and
    one collect has built the layouts, device aggregations over other
    query masks trace no program (`tpu_compile.retraces` stands still)."""
    from elasticsearch_tpu.common import hbm_ledger

    svc = _make_service(n=1200)
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1)
    agg_device.default_engine().extend_qc_sizes([1, 4, 16])
    aggs = {"tags": {"terms": {"field": "tag"},
                     "aggs": {"p": {"stats": {"field": "price"}}}},
            "days": {"date_histogram": {"field": "ts",
                                        "fixed_interval": "7d"}}}

    def run(word):
        return svc._search_dense({"query": {"match": {"body": word}},
                                  "size": 0, "aggs": aggs})["aggregations"]

    run("w0")                                # warm: layouts + first traces
    before, r0 = _counts(), hbm_ledger.compile_stats()["retraces"]
    dev = [run(w) for w in ("w1", "w2", "w3", "nosuchtoken")]
    assert hbm_ledger.compile_stats()["retraces"] == r0
    after = _counts()
    assert after["agg_device_dispatches"] > before["agg_device_dispatches"]
    assert after["agg_host_fallbacks"] == before["agg_host_fallbacks"]
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1 << 60)
    assert dev == [run(w) for w in ("w1", "w2", "w3", "nosuchtoken")]
    svc.close()


# ---------------------------------------------------------------------------
# fallback + A/B + faults
# ---------------------------------------------------------------------------


def test_over_budget_layouts_fall_back_to_host(monkeypatch):
    """ES_TPU_AGG_HBM_FRAC=0 refuses every layout: the collect is served
    by the host aggregators (identical response), counted as fallback."""
    monkeypatch.setenv("ES_TPU_AGG_HBM_FRAC", "0.0")
    svc = _make_service(n=1200, seed=11)
    before = _counts()
    body = {"size": 0, "aggs": {"tags": {"terms": {"field": "tag"}}}}
    dev, host = _ab(svc, body, monkeypatch)
    assert dev == host
    after = _counts()
    assert after["agg_host_fallbacks"] > before["agg_host_fallbacks"]
    assert after["agg_device_dispatches"] == before["agg_device_dispatches"]
    assert after["agg_bytes"] == before["agg_bytes"]
    svc.close()


def test_agg_flag_off_restores_host_path_verbatim(monkeypatch):
    svc = _make_service(n=1500, seed=3)
    body = {"size": 0, "aggs": {
        "tags": {"terms": {"field": "tag", "size": 50},
                 "aggs": {"s": {"stats": {"field": "price"}}}}}}
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1)
    on = svc._search_dense(body)["aggregations"]

    monkeypatch.setenv("ES_TPU_AGG", "0")
    assert not knob("ES_TPU_AGG")
    before = _counts()
    off = svc._search_dense(body)["aggregations"]
    after = _counts()
    assert off == on
    # knob off = the host path verbatim: no device counters move at all
    assert after == before

    monkeypatch.delenv("ES_TPU_AGG")
    before = _counts()
    on2 = svc._search_dense(body)["aggregations"]
    assert on2 == on
    assert _counts()["agg_queries"] == before["agg_queries"] + 1
    svc.close()


def test_agg_reduce_fault_contained_with_host_fallback(monkeypatch):
    """An injected agg_reduce fault poisons only that dispatch: the
    collect falls back to the host aggregator and the response stays
    bit-identical; the next dispatch runs on device again."""
    svc = _make_service(n=1500, seed=5)
    body = {"size": 0, "aggs": {"tags": {"terms": {"field": "tag"}}}}
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1)
    want = svc._search_dense(body)["aggregations"]       # builds the layout

    eng = agg_device.default_engine()
    serials = [s for n, s in eng.layout_serials().items()
               if n.endswith("_terms")]
    assert serials
    before = _counts()
    with inject(f"agg_reduce#{max(serials)}:raise@1"):
        got = svc._search_dense(body)["aggregations"]
    assert got == want
    after = _counts()
    assert after["agg_host_fallbacks"] == before["agg_host_fallbacks"] + 1

    # containment: the fault did not poison the engine or the layout
    before = _counts()
    again = svc._search_dense(body)["aggregations"]
    assert again == want
    assert _counts()["agg_queries"] == before["agg_queries"] + 1
    svc.close()


def test_hbm_scrub_repairs_flipped_agg_column(monkeypatch):
    """A bitflipped device agg column is detected by the PR-15 scrubber,
    repaired from the host copy, and the repaired column serves
    bit-identical results."""
    integrity.reset_scrub_for_tests()
    svc = _make_service(n=1500, seed=13)
    body = {"size": 0, "aggs": {"tags": {"terms": {"field": "tag"}}}}
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1)
    want = svc._search_dense(body)["aggregations"]

    eng = agg_device.default_engine()
    # newest terms layout = the one this service just built (older tests'
    # layouts may still be alive but were dropped from the scrub registry
    # by the reset above)
    region = max((n for n in eng.layout_serials() if n.endswith("_terms")),
                 key=lambda n: eng.layout_serials()[n])
    base = integrity.integrity_stats()["scrub_repairs"]
    with inject(f"hbm_region#{region}:raise@1x1"):
        results = [integrity.scrub_once()
                   for _ in range(integrity.scrub_registry_size())]
    hit = [r for r in results if r and r["result"] == "mismatch"]
    assert len(hit) == 1 and hit[0]["region"].endswith(region)
    assert integrity.integrity_stats()["scrub_repairs"] == base + 1

    got = svc._search_dense(body)["aggregations"]
    assert got == want
    svc.close()


# ---------------------------------------------------------------------------
# scheduler tiering + accounting
# ---------------------------------------------------------------------------


def test_agg_collects_ride_bulk_tier(monkeypatch):
    """Agg dispatches are bulk-tier scheduler work: the bulk counter
    moves, the interactive counter does not."""
    from elasticsearch_tpu.threadpool.scheduler import scheduler_stats

    svc = _make_service(n=1200, seed=17)
    body = {"size": 0, "aggs": {"tags": {"terms": {"field": "tag"}}}}
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1)

    def tiers():
        t = scheduler_stats().get("tiers", {})
        return (t.get("bulk", {}).get("dispatches", 0),
                t.get("interactive", {}).get("dispatches", 0))

    svc._search_dense(body)                  # warm: layout build + trace
    b0, i0 = tiers()
    svc._search_dense(body)
    b1, i1 = tiers()
    assert b1 > b0
    assert i1 == i0
    svc.close()


def test_ledger_reconciles_and_knobs_declared(monkeypatch):
    """tpu_hbm's agg engine bytes == the engine's own accounting == the
    tpu_agg stats section; knobs come from the typed registry."""
    assert knob("ES_TPU_AGG") is True
    assert knob("ES_TPU_AGG_HBM_FRAC") == 0.25

    # a fresh process-default engine: another test file of this worker may
    # have reset the ledger (tests/test_telemetry.py does) and orphaned the
    # old one's handle
    monkeypatch.setattr(agg_device, "_ENGINE", None)
    svc = _make_service(n=1200, seed=19)
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1)
    svc._search_dense(
        {"size": 0, "aggs": {"tags": {"terms": {"field": "tag"}},
                             "h": {"histogram": {"field": "price",
                                                 "interval": 4}}}})
    eng = agg_device.default_engine()
    assert eng.hbm_bytes() > 0
    assert eng.hbm_bytes() == eng.ledger_bytes()
    assert agg_device.agg_stats()["hbm_bytes"] == eng.hbm_bytes()

    # counters are declared (TPU005): Prometheus sees them even at zero
    vals = metrics.counter_values()
    for name in ("agg_queries", "agg_device_dispatches",
                 "agg_host_fallbacks", "agg_bytes"):
        assert name in vals

    from elasticsearch_tpu.rest.handlers import _tpu_agg_stats
    section = _tpu_agg_stats()
    for key in ("agg_queries", "agg_device_dispatches",
                "agg_host_fallbacks", "agg_bytes", "hbm_bytes",
                "enabled", "layouts"):
        assert key in section
    svc.close()


# ---------------------------------------------------------------------------
# the filter + bucket route (PR 40): one dispatch a request, the match set
# made on the device; tests/test_filter_agg_route.py holds it to the
# benchmark's reference on the benchmark's own corpus
# ---------------------------------------------------------------------------

_WEEK = [BASE_MS + 20 * 86_400_000 + 123, BASE_MS + 27 * 86_400_000 + 123]
_DAY = [BASE_MS + 40 * 86_400_000 + 1, BASE_MS + 41 * 86_400_000 + 1]


def _envelope(filters, interval):
    query = {"bool": {"filter": filters}} if filters else {"match_all": {}}
    return {"size": 0, "track_total_hits": True, "query": query,
            "aggs": {"by_time": {"date_histogram": {
                "field": "ts", "fixed_interval": interval}}}}


def _ts_range(lo, hi):
    return {"range": {"ts": {"gte": lo, "lt": hi}}}


# the benchmark mix's five shapes (Rally http_logs' operations) over this
# file's own index: `ts` for @timestamp, the `long` field `n` for status
FILTER_AGG_SHAPES = {
    "HourlyAgg": _envelope([], "1h"),
    "RangeHourly": _envelope([_ts_range(*_WEEK)], "1h"),
    "RangeTenMinute": _envelope([_ts_range(*_DAY)], "10m"),
    "Status200sInRange": _envelope(
        [_ts_range(*_WEEK), {"term": {"n": 200}}], "1h"),
    "Status400sInRange": _envelope(
        [_ts_range(*_WEEK), {"term": {"n": 404}}], "1h"),
}
NOT_THE_ENVELOPE = {
    "a_sub_aggregation": lambda b: b["aggs"]["by_time"].update(
        aggs={"p": {"sum": {"field": "price"}}}),
    "a_terms_bucket": lambda b: b.update(
        aggs={"tags": {"terms": {"field": "tag"}}}),
    "size_10": lambda b: b.update(size=10),
}


def _logs_service():
    meta = IndexMetadata(
        index="agg", uuid="u", settings=Settings({}), mappings={
            "properties": {"tag": {"type": "keyword"},
                           "price": {"type": "float"},
                           "n": {"type": "long"}, "ts": {"type": "long"}}})
    svc = IndexService(meta)
    rng = np.random.default_rng(23)
    for seg in range(3):                       # three segments, in time order
        for i in range(800):
            svc.index_doc(f"{seg}-{i}", {
                "tag": f"t{i % 9}", "price": float(i % 50),
                "n": int(rng.choice([200, 200, 200, 304, 404])),
                "ts": BASE_MS + seg * 30 * 86_400_000
                + int(rng.integers(0, 30 * 86_400)) * 1000})
        svc.refresh()
    return svc


@pytest.fixture(scope="module")
def logs_service():
    svc = _logs_service()
    yield svc
    svc.close()


@pytest.mark.parametrize("shape", sorted(FILTER_AGG_SHAPES))
def test_recognised_envelope_and_host_path_give_the_same_bytes(
        logs_service, monkeypatch, shape):
    import json

    svc, body = logs_service, FILTER_AGG_SHAPES[shape]
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1)
    monkeypatch.setattr(agg_mod, "MAX_BUCKETS", 65536)   # the default
    before = _counts()
    fast = svc.serving.try_search(body, "query_then_fetch")
    after = _counts()
    assert fast is not None
    assert after["filter_device"] == before["filter_device"] + 3
    assert after["agg_device_dispatches"] == \
        before["agg_device_dispatches"] + 1          # ONE dispatch a request
    assert after["filter_host"] == before["filter_host"]
    assert after["agg_host_fallbacks"] == before["agg_host_fallbacks"]
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1 << 60)
    assert svc.serving.try_search(body, "query_then_fetch") is None
    host = svc._search_dense(body)                   # the host aggregators
    assert _counts()["filter_device"] == after["filter_device"]
    fast.pop("took"), host.pop("took")
    assert json.dumps(fast) == json.dumps(host)
    assert fast["hits"]["total"]["value"] > 0
    assert len(fast["aggregations"]["by_time"]["buckets"]) > 100


@pytest.mark.parametrize("case", sorted(NOT_THE_ENVELOPE))
def test_unrecognised_envelope_takes_the_old_path(logs_service, monkeypatch,
                                                  case):
    import copy

    svc = logs_service
    body = copy.deepcopy(FILTER_AGG_SHAPES["Status200sInRange"])
    NOT_THE_ENVELOPE[case](body)
    monkeypatch.setattr(agg_mod, "AGG_DEVICE_MIN_DOCS", 1)
    before = _counts()
    assert svc.serving.try_search(body, "query_then_fetch") is None
    resp = svc.search(body)
    after = _counts()
    assert after["filter_device"] == before["filter_device"]
    # the old device route still serves a histogram's collect, a host
    # mask a (segment, aggregation), one dispatch each
    if case != "a_terms_bucket":
        assert after["filter_host"] == before["filter_host"] + 3
        assert after["agg_device_dispatches"] == \
            before["agg_device_dispatches"] + 3
    assert resp["hits"]["total"]["value"] > 0
