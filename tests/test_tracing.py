"""Search flight recorder (PR 9): log-bucketed latency histograms, trace
propagation coordinator -> shard RPC -> back into `profile.tpu`, and the
slowlog ring.

The histogram units pin the mergeability contract (fixed per-kind bucket
boundaries, element-wise sum across nodes); the cluster tests ride the same
in-process harness as test_distributed/test_disruption and assert one trace
id spans the coordinator and every data-node shard context — including
across a PR 6 failover retry, where the failed and the successful rpc_query
attempt land in the SAME trace. The differential test is the acceptance
gate for "zero cost when disabled": sampled vs unsampled responses must be
bit-identical.
"""

import json

import pytest

from elasticsearch_tpu.action.search_action import _COORD_COUNTERS
from elasticsearch_tpu.cluster_node import form_local_cluster
from elasticsearch_tpu.common import faults, metrics, tracing
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest import RestController, register_handlers

MAPPINGS = {"properties": {"n": {"type": "integer"},
                           "body": {"type": "text"}}}

BODY = {"query": {"match": {"body": "common"}}, "size": 10,
        "track_total_hits": True}


@pytest.fixture(autouse=True)
def _fresh_recorder():
    """Rings and live histograms are module-global (shared by every node of
    an in-process cluster) — isolate each test from its neighbors."""
    metrics.reset_for_tests()
    tracing.reset_for_tests()
    yield
    metrics.reset_for_tests()
    tracing.reset_for_tests()


def make_cluster(n_data=3):
    names = ["m0"] + [f"d{i}" for i in range(n_data)]
    return form_local_cluster(names, roles={"m0": ("master",)})


def index_body(shards=2, replicas=1):
    return {"settings": {"number_of_shards": shards,
                         "number_of_replicas": replicas},
            "mappings": MAPPINGS}


def bulk_ops(start, count):
    return [{"op": "index", "id": str(i),
             "source": {"n": i, "body": f"word{i % 7} common text"}}
            for i in range(start, start + count)]


def ranked_first(coordinator, store, index="docs", sid=0):
    copies = [r for r in store.current().shard_copies(index, sid)
              if r.state == "STARTED"]
    return coordinator.search_action._rank_copies(copies)[0]


def normalized(resp):
    out = dict(resp)
    out.pop("took", None)
    return out


def has_key(obj, key):
    if isinstance(obj, dict):
        return key in obj or any(has_key(v, key) for v in obj.values())
    if isinstance(obj, list):
        return any(has_key(v, key) for v in obj)
    return False


# --------------------------------------------------------------------------
# histogram units
# --------------------------------------------------------------------------


def test_histogram_bucket_boundaries():
    h = metrics.Histogram("x", "ms")
    # a value exactly on a bound lands in that bound's bucket (bisect_left);
    # just above spills into the next one
    h.record(h.bounds[10])
    h.record(h.bounds[10] * 1.01)
    counts = h.raw()["counts"]
    assert counts[10] == 1 and counts[11] == 1
    # negatives clamp to the first bucket, overflow goes to the final slot
    h.record(-3.0)
    h.record(1e9)
    counts = h.raw()["counts"]
    assert counts[0] == 1 and counts[-1] == 1
    assert h.raw()["max"] == 1e9


def test_histogram_percentiles():
    h = metrics.Histogram("x", "ms")
    for _ in range(90):
        h.record(1.0)
    for _ in range(10):
        h.record(100.0)
    st = h.stats()
    assert st["count"] == 100
    assert st["mean"] == pytest.approx(10.9)
    # bucket upper bound of the quantile observation: p50/p90 in the ~1ms
    # bucket, p99 in the ~100ms bucket (sqrt-2 grid => <=41% quantization)
    assert 1.0 <= st["p50"] <= 1.5
    assert 1.0 <= st["p90"] <= 1.5
    assert 100.0 <= st["p99"] <= 150.0
    assert st["max"] == 100.0
    # overflow observations report the true max, not a bucket bound
    h2 = metrics.Histogram("y", "ms")
    h2.record(5e8)
    assert h2.stats()["p99"] == 5e8


def test_histogram_merge_across_nodes():
    a = metrics.Histogram("a", "ms")
    b = metrics.Histogram("b", "ms")
    for v in range(10):
        a.record(float(v))
    for v in range(100, 110):
        b.record(float(v))
    merged = metrics.merge_summaries([a.raw(), b.raw()])
    assert merged["count"] == 20
    assert merged["max"] == 109.0
    # merged median sits between the two nodes' medians
    assert a.stats()["p50"] <= merged["p50"] <= b.stats()["p50"]
    # merging is exactly element-wise: counts of the merged raw equal sums
    summed = [x + y for x, y in zip(a.raw()["counts"], b.raw()["counts"])]
    assert sum(summed) == 20
    # one-node merge is the identity on the summary
    assert metrics.merge_summaries([a.raw()]) == a.stats()
    # kinds with different boundaries refuse to merge
    c = metrics.Histogram("c", "count")
    with pytest.raises(ValueError):
        metrics.merge_summaries([a.raw(), c.raw()])
    # empty merge yields the zero summary
    assert metrics.merge_summaries([])["count"] == 0


def test_registry_strict_and_lenient():
    with pytest.raises(metrics.UndeclaredHistogramError):
        metrics.observe("not_a_histogram", 1.0)
    # dynamically composed names degrade to a no-op instead of raising
    metrics.observe_if_declared("queue_wait.adhoc_test_pool", 1.0)
    assert metrics.summary("not_a_histogram") is None
    metrics.observe("device", 3.0)
    assert metrics.summary("device")["count"] == 1
    stats = metrics.search_latency_stats()
    for name in ("queue_wait.search", "device", "demux",
                 "fetch", "query", "merge", "rest_total",
                 "coalesce_batch_size", "coalesce_pad_ratio"):
        assert name in stats and "p99" in stats[name]


# --------------------------------------------------------------------------
# trace context units
# --------------------------------------------------------------------------


def test_trace_context_spans_and_totals():
    tc = tracing.TraceContext(node="n1", kind="rest")
    tc.add_span("device", 2.0)
    tc.add_span("device", 3.0, engine="turbo")
    tc.add_span("fetch", 1.5)
    tc.add_span("rest_total", 10.0)
    totals = tc.phase_totals()
    assert totals["device"] == 5.0 and totals["fetch"] == 1.5
    # rest_total envelopes everything else; phase_totals excludes it
    assert "rest_total" not in totals
    with tc.span("merge", shards=2):
        pass
    assert any(s["name"] == "merge" and s["meta"] == {"shards": 2}
               for s in tc.span_dicts())


def test_trace_wire_roundtrip_and_activation():
    tc = tracing.TraceContext(opaque_id="client-7", node="coord")
    child = tracing.child_from_wire(tc.wire(), node="data-1", kind="shard_query")
    assert child.trace_id == tc.trace_id
    assert child.opaque_id == "client-7"
    assert child.node == "data-1" and child.kind == "shard_query"
    assert tracing.child_from_wire(None) is None
    assert tracing.child_from_wire({}) is None
    # activate(None) is a pass-through, real activation nests and restores
    assert tracing.current() is None
    with tracing.activate(None):
        assert tracing.current() is None
    with tracing.activate(tc):
        assert tracing.current() is tc
        with tracing.activate(child):
            assert tracing.current() is child
        assert tracing.current() is tc
    assert tracing.current() is None


def test_slowlog_threshold_parsing():
    class _S:
        def __init__(self, d):
            self._d = d

        def raw(self, key):
            return self._d.get(key)

    key = "index.search.slowlog.threshold.{}.{}"
    th = tracing.slowlog_thresholds(_S({
        key.format("query", "warn"): "500ms",
        key.format("query", "info"): "-1",
        key.format("fetch", "warn"): "1s",
        key.format("fetch", "info"): 250,
    }))
    assert th["query"] == {"warn": 500.0, "info": None}
    assert th["fetch"] == {"warn": 1000.0, "info": 250.0}
    # unparseable values disable rather than blow up the search path
    junk = tracing.slowlog_thresholds(
        _S({key.format("query", "warn"): "soon-ish"}))
    assert junk["query"]["warn"] is None
    assert not tracing.slowlog_configured(_S({}))
    assert tracing.slowlog_configured(
        _S({key.format("query", "warn"): "0ms"}))
    # warn outranks info when both match
    per = {"warn": 100.0, "info": 10.0}
    assert tracing.slowlog_check("query", 150.0, per) == "warn"
    assert tracing.slowlog_check("query", 50.0, per) == "info"
    assert tracing.slowlog_check("query", 5.0, per) is None


# --------------------------------------------------------------------------
# cross-node propagation (the tentpole)
# --------------------------------------------------------------------------


def _seeded_cluster():
    nodes, store, channels = make_cluster()
    master, a, b, c = nodes
    a.create_index("docs", index_body(2, 1))
    a.bulk("docs", bulk_ops(0, 40))
    a.refresh("docs")
    return nodes, store, channels


def test_trace_propagates_coordinator_to_shards():
    nodes, store, channels = _seeded_cluster()
    master = nodes[0]
    r = master.search("docs", dict(BODY, profile=True))
    assert r["_shards"]["failed"] == 0

    tpu = r["profile"]["tpu"]
    tid = tpu["trace_id"]
    assert tid and tpu["node"] == "m0"
    assert "rpc_query" in tpu["phases"] and "merge" in tpu["phases"]
    # span sum stays consistent with took: no phase can exceed the request
    assert max(tpu["phases"].values()) <= r["took"] + 250

    same = [t for t in tracing.recent_traces() if t["trace_id"] == tid]
    kinds = {t["kind"] for t in same}
    assert "coordinator" in kinds and "shard_query" in kinds
    # shard contexts ran on data nodes, never on the dedicated master
    shard_nodes = {t["node"] for t in same if t["kind"] == "shard_query"}
    assert shard_nodes and "m0" not in shard_nodes
    # both shards surface a per-shard tpu breakdown in the profile
    assert len(r["profile"]["shards"]) == 2
    for entry in r["profile"]["shards"]:
        assert entry["tpu"]["phases"]["query"] > 0
        assert entry["tpu"]["node"] in shard_nodes
    # internal span transport never leaks into the client response
    assert not has_key(r, "_trace_spans")
    # the shard query phase fed the node-wide histogram too
    assert metrics.summary("query")["count"] >= 2
    assert metrics.summary("merge")["count"] >= 1


def test_failover_retry_shares_one_trace():
    """PR 6 + PR 9: a faulted first attempt and its successful replica
    retry are two rpc_query spans in the SAME trace, the failed one
    carrying the error type and the node it died on."""
    nodes, store, channels = _seeded_cluster()
    master = nodes[0]
    victim = ranked_first(master, store)
    before = dict(_COORD_COUNTERS)
    with faults.inject(f"rpc_query#{victim}:raisexinf"):
        r = master.search("docs", dict(BODY, profile=True))
    assert r["_shards"]["failed"] == 0
    assert _COORD_COUNTERS["shard_retries"] - before["shard_retries"] >= 1

    tid = r["profile"]["tpu"]["trace_id"]
    coord = [t for t in tracing.recent_traces()
             if t["trace_id"] == tid and t["kind"] == "coordinator"]
    assert len(coord) == 1
    rpc = [s for s in coord[0]["spans"] if s["name"] == "rpc_query"]
    failed = [s for s in rpc if "error" in s["meta"]]
    ok = [s for s in rpc if "error" not in s["meta"]]
    assert failed and ok
    assert all(s["meta"]["node"] == victim for s in failed)
    # the shard that failed over still completed — on a different node
    for f in failed:
        retried = [s for s in ok if s["meta"]["shard"] == f["meta"]["shard"]]
        assert retried and all(s["meta"]["node"] != victim for s in retried)
        assert all(s["meta"]["attempt"] > f["meta"]["attempt"]
                   for s in retried)


def test_sampling_differential_bit_identity(monkeypatch):
    """The disabled-by-default acceptance gate: turning the flight recorder
    on (every-request sampling) must not change a single response byte."""
    nodes, store, channels = _seeded_cluster()
    master = nodes[0]
    r_off = master.search("docs", BODY)
    assert tracing.recent_traces() == []      # untraced by default
    # with no context (and no profiler) the one primitive still feeds the
    # standing histograms: that half of it is always on
    assert metrics.summary("query")["count"] >= 2
    assert metrics.summary("merge")["count"] >= 1

    monkeypatch.setenv("ES_TPU_TRACE_SAMPLE", "1")
    r_on = master.search("docs", BODY)
    assert normalized(r_on) == normalized(r_off)
    assert not has_key(r_on, "_trace_spans")
    traces = tracing.recent_traces()
    assert any(t["kind"] == "coordinator" for t in traces)
    # shard children joined the sampled trace id
    tid = next(t["trace_id"] for t in traces if t["kind"] == "coordinator")
    assert any(t["kind"] == "shard_query" and t["trace_id"] == tid
               for t in traces)


# --------------------------------------------------------------------------
# slowlog end-to-end through REST
# --------------------------------------------------------------------------


@pytest.fixture()
def env():
    node = Node()
    rc = RestController()
    register_handlers(node, rc)

    def call(method, path, body=None, params=None, headers=None):
        data = json.dumps(body).encode() if body is not None else None
        resp = rc.dispatch(method, path, params or {}, data, headers=headers)
        return resp.status, json.loads(resp.encode() or b"{}")

    yield node, call
    node.close()


def test_slowlog_end_to_end(env):
    node, call = env
    st, _ = call("PUT", "/s", {"mappings": {"properties": {
        "body": {"type": "text"}}}})
    assert st == 200
    # _disj_servable needs from+size <= the largest partition's doc count,
    # or the fast path declines and no device/demux phases are recorded
    for i in range(32):
        call("PUT", f"/s/_doc/{i}", {"body": f"w{i % 4} common"})
    call("POST", "/s/_refresh")

    # no thresholds configured -> searches never reach the slowlog
    st, r = call("POST", "/s/_search", {"query": {"match": {"body": "common"}}})
    assert st == 200
    st, slow = call("GET", "/_tpu/slowlog")
    assert slow["slowlog"] == [] and slow["query_warn"] == 0

    # thresholds arrive dynamically via _settings (the PR's bugfix: they
    # live on index settings and IndexService parses them effectively)
    st, _ = call("PUT", "/s/_settings", {"index": {"search": {"slowlog": {
        "threshold": {"query": {"warn": "0ms"}}}}}})
    assert st == 200
    svc = node.indices.get("s")
    th = svc.effective_slowlog_thresholds()
    assert th["query"]["warn"] == 0.0 and th["query"]["info"] is None

    st, r = call("POST", "/s/_search",
                 {"query": {"match": {"body": "common"}}},
                 headers={"X-Opaque-Id": "slowlog-e2e"})
    assert st == 200

    st, slow = call("GET", "/_tpu/slowlog")
    assert slow["query_warn"] >= 1
    entry = slow["slowlog"][-1]
    assert entry["phase"] == "query" and entry["level"] == "warn"
    assert entry["index"] == "s" and entry["took_ms"] >= 0
    assert entry["source"] == {"match": {"body": "common"}}
    # slowlog-configured index => the request was traced: the record has a
    # trace id, the client correlation header, and a phase breakdown
    assert entry["trace_id"] and entry["opaque_id"] == "slowlog-e2e"
    assert "device" in entry["phases"] and "fetch" in entry["phases"]
    # the same trace landed in the flight-recorder ring
    st, tr = call("GET", "/_tpu/trace")
    assert any(t["trace_id"] == entry["trace_id"] for t in tr["traces"])

    # and node stats expose both the histograms and the slowlog counters
    st, stats = call("GET", "/_nodes/stats")
    lat = stats["nodes"][node.node_id]["tpu_search_latency"]
    assert lat["rest_total"]["count"] >= 2
    assert lat["device"]["count"] >= 1
    assert lat["fetch"]["count"] >= 1
    assert lat["slowlog"]["query_warn"] >= 1
    assert lat["slowlog"]["ring_entries"] == len(slow["slowlog"])


def test_profile_response_carries_rest_trace(env):
    """Single-node profiled search: the REST layer owns the trace, so
    profile.tpu names the rest context and phases include the fast-path
    device/demux/fetch decomposition."""
    node, call = env
    call("PUT", "/s", {"mappings": {"properties": {
        "body": {"type": "text"}}}})
    for i in range(32):
        call("PUT", f"/s/_doc/{i}", {"body": f"w{i % 4} common"})
    call("POST", "/s/_refresh")

    st, r = call("POST", "/s/_search",
                 {"query": {"match": {"body": "common"}}, "profile": True,
                  "size": 10},
                 headers={"X-Opaque-Id": "prof-1"})
    assert st == 200
    tpu = r["profile"]["tpu"]
    assert tpu["trace_id"] and tpu["opaque_id"] == "prof-1"
    assert {"device", "demux", "fetch"} <= set(tpu["phases"])
    # the profile query tree is still the classic shape next to the
    # tpu section
    assert r["profile"]["shards"][0]["searches"][0]["query"]



# --------------------------------------------------------------------------
# the one span primitive (PR 27): ids, parents, self time, batches, builds
# --------------------------------------------------------------------------


def _by_name(tc):
    out = {}
    for s in tc.span_dicts():
        out.setdefault(s["name"], []).append(s)
    return out


def test_phase_spans_have_ids_parents_and_one_clock():
    tc = tracing.TraceContext(node="n1", kind="rest")
    assert tracing.current_span() == 0 and tracing.open_phase() is None
    with tracing.activate(tc):
        with tracing.phase("query", shard=0) as outer:
            assert tracing.open_phase() == "query"
            assert tracing.current_span() == outer.span_id != 0
            with tracing.phase("device", batch=1) as mid:
                with tracing.phase("dispatch.prep"):
                    pass
                with tracing.phase("dispatch.finish"):
                    with tracing.phase("dispatch.rescore"):
                        pass
            tc.add_span("rpc_query", 0.5, node="d0")   # measured by the caller
    assert outer.ms >= mid.ms > 0
    spans = _by_name(tc)
    q, dev = spans["query"][0], spans["device"][0]
    assert q["parent"] == 0 and q["meta"] == {"shard": 0}
    assert dev["parent"] == q["id"]
    assert spans["dispatch.prep"][0]["parent"] == dev["id"]
    assert spans["dispatch.rescore"][0]["parent"] == \
        spans["dispatch.finish"][0]["id"]
    assert spans["rpc_query"][0]["parent"] == q["id"]
    ids = [s["id"] for s in tc.span_dicts()]
    assert len(set(ids)) == len(ids) and all(ids)
    for s in tc.span_dicts():
        assert s["trace_id"] == tc.trace_id
        assert s["start_ns"] <= s["end_ns"]
        assert s["duration_ms"] == pytest.approx(
            (s["end_ns"] - s["start_ns"]) / 1e6, abs=2e-3)
        assert s["start_ms"] >= 0
    # children lie inside parents on the one process-wide clock
    assert q["start_ns"] <= dev["start_ns"] and dev["end_ns"] <= q["end_ns"]
    # the histograms took the same durations, with no context needed
    for name in ("query", "device", "dispatch.prep", "dispatch.finish",
                 "dispatch.rescore"):
        assert metrics.summary(name)["count"] == 1
    with tracing.phase("dispatch.prep"):
        pass
    assert metrics.summary("dispatch.prep")["count"] == 2
    assert len(tc.span_dicts()) == 6            # ... and no span without one
    with pytest.raises(metrics.UndeclaredHistogramError):
        with tracing.phase("not_a_histogram"):
            pass


def test_phase_totals_are_self_time_and_sum_to_the_root():
    import time as _time

    tc = tracing.TraceContext(kind="shard_query")
    with tracing.activate(tc):
        with tracing.phase("query") as root:
            _time.sleep(0.002)
            with tracing.phase("device") as dev:
                with tracing.phase("dispatch.prep") as prep:
                    _time.sleep(0.002)
                with tracing.phase("dispatch.finish") as fin:
                    _time.sleep(0.001)
                    with tracing.phase("dispatch.rescore") as r1:
                        _time.sleep(0.002)
                    with tracing.phase("dispatch.rescore") as r2:
                        _time.sleep(0.001)
            with tracing.phase("fetch") as fetch:
                _time.sleep(0.001)
    totals = tc.phase_totals()
    assert set(totals) == {"query", "device", "dispatch.prep",
                           "dispatch.finish", "dispatch.rescore", "fetch"}
    assert sum(totals.values()) == pytest.approx(root.ms, abs=0.02)
    # self time: what the children cover is theirs, not the parent's (set
    # against the phases' own readings, not against the wall clock: a
    # sleep under six test workers overshoots by as much as it likes)
    rescore = r1.ms + r2.ms
    assert totals["dispatch.rescore"] == pytest.approx(rescore, abs=0.01)
    assert totals["dispatch.finish"] == pytest.approx(fin.ms - rescore,
                                                      abs=0.01)
    assert totals["device"] == pytest.approx(dev.ms - prep.ms - fin.ms,
                                             abs=0.01)
    assert totals["query"] == pytest.approx(root.ms - dev.ms - fetch.ms,
                                            abs=0.01)
    # a sleep never returns early
    assert totals["dispatch.rescore"] >= 3.0
    assert totals["dispatch.finish"] >= 1.0
    assert totals["query"] >= 2.0
    assert tracing.self_times(tc.span_dicts()) == totals


def test_spans_cross_a_pool_hop_with_their_parent():
    from elasticsearch_tpu.threadpool.pool import FixedExecutor

    pool = FixedExecutor("search", size=1, queue_size=8)
    tc = tracing.TraceContext()
    seen = {}

    def work():
        seen["tc"] = tracing.current()
        with tracing.phase("fetch"):
            pass

    try:
        with tracing.activate(tc), tracing.phase("query") as q:
            pool.submit(work).get(timeout=10)
    finally:
        pool.shutdown()
    assert seen["tc"] is tc
    spans = _by_name(tc)
    assert spans["queue_wait.search"][0]["parent"] == q.span_id
    assert spans["fetch"][0]["parent"] == q.span_id
    assert metrics.summary("queue_wait.search")["count"] == 1


class _SteppedEngine:
    """search_many stub that records the steps a real engine does."""

    kind = "stub"
    STEPS = metrics.DISPATCH_TOP_STEPS + ("dispatch.rescore",)

    def search_many(self, batches, k=10, check=None):
        import numpy as np

        qs = batches[0]
        with tracing.steps(self.STEPS):
            with tracing.phase("dispatch.prep", queries=len(qs)):
                pass
            with tracing.phase("dispatch.launch"):
                pass
            with tracing.phase("dispatch.device_wait"):
                pass
            with tracing.phase("dispatch.finish"):
                for _ in qs:
                    with tracing.phase("dispatch.rescore"):
                        pass
        z = np.zeros((len(qs), k), np.int32)
        s = np.zeros((len(qs), k), np.float32)
        s[:, 0] = 1.0
        return [(s, z, z.copy())]


def test_a_batch_of_traced_waiters_each_see_its_device_span():
    import threading

    from elasticsearch_tpu.threadpool.scheduler import (
        AdaptiveDispatchScheduler,
    )

    n = 4
    sched = AdaptiveDispatchScheduler(buckets=(n,), interactive_us=2e6,
                                      inflight=1)
    eng = _SteppedEngine()
    tcs = [tracing.TraceContext(node=f"w{i}") for i in range(n)]
    errors = []

    def waiter(i):
        try:
            # the fourth waiter rides untraced: it gets its rows, no spans
            with tracing.activate(tcs[i] if i < n - 1 else None), \
                    tracing.phase("query", shard=i):
                sched.dispatch(eng, [[f"q{i}"]], 10)
        except BaseException as e:  # noqa: BLE001 — asserted below
            errors.append(e)

    threads = [threading.Thread(target=waiter, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    assert sched.stats()["sched_dispatches"] == 1        # ONE batch of 4
    device_ids = set()
    for tc in tcs[:n - 1]:
        spans = _by_name(tc)
        wait = spans["sched_tier_wait.interactive"][0]
        assert wait["parent"] == spans["query"][0]["id"]
        assert wait["meta"]["batch"] == n and wait["meta"]["bucket"] == n
        dev = spans["device"][0]
        # the batch's span hangs off the span THIS waiter submitted from
        assert dev["parent"] == wait["id"]
        assert dev["meta"] == {"engine": "stub", "batch": n}
        assert wait["start_ns"] <= dev["start_ns"] <= dev["end_ns"] \
            <= wait["end_ns"]
        device_ids.add(dev["id"])
        for name in metrics.DISPATCH_TOP_STEPS:
            assert [s["parent"] for s in spans[name]] == [dev["id"]]
        assert len(spans["dispatch.rescore"]) == n
        assert {s["parent"] for s in spans["dispatch.rescore"]} == \
            {spans["dispatch.finish"][0]["id"]}
        # a partition of the waiter's request: its queueing is the wait's
        # own time, the dispatch is the device's and its children's
        totals = tc.phase_totals()
        assert sum(totals.values()) == pytest.approx(
            spans["query"][0]["duration_ms"], abs=0.05)
    assert len(device_ids) == 1           # recorded once, linked thrice
    assert tcs[n - 1].span_dicts() == []
    # one observation per ENGINE CALL for every step, whatever ran inside
    assert metrics.summary("device")["count"] == 1
    for name in _SteppedEngine.STEPS:
        assert metrics.summary(name)["count"] == 1, name


def test_step_histograms_take_one_observation_per_call_and_zeros():
    names = ("dispatch.prep", "dispatch.finish", "dispatch.rescore",
             "dispatch.cert_fallback")
    with tracing.steps(names):
        with tracing.phase("dispatch.prep"):
            pass
        with tracing.steps(names):              # a partition's engine inside
            with tracing.phase("dispatch.prep"):
                pass
        with tracing.phase("dispatch.finish"):
            for _ in range(3):
                with tracing.phase("dispatch.rescore"):
                    pass
            with tracing.phase("demux"):        # not a step: its own record
                pass
    for name in names:
        assert metrics.summary(name)["count"] == 1, name
    assert metrics.summary("dispatch.cert_fallback")["max"] == 0.0
    assert metrics.summary("demux")["count"] == 1
    assert metrics.raw_dump("dispatch.finish")["total"] >= \
        metrics.raw_dump("dispatch.rescore")["total"]


def test_untraced_responses_are_bit_identical_and_the_steps_still_move(
        env, monkeypatch):
    """The served Turbo path with no context and no profiler: the same
    bytes as with every request traced, and the `dispatch.*` histograms
    move either way."""
    node, call = env
    monkeypatch.setenv("ES_TPU_FORCE_TURBO", "1")
    call("PUT", "/s", {"mappings": {"properties": {"body": {"type": "text"}}}})
    for i in range(48):
        call("PUT", f"/s/_doc/{i}", {"body": f"w{i % 4} w{i % 7} common"})
    call("POST", "/s/_refresh")
    body = {"query": {"match": {"body": "common w3"}}, "size": 10}
    st, r_off = call("POST", "/s/_search", body)
    assert st == 200 and r_off["hits"]["hits"]
    assert tracing.recent_traces() == []
    st, stats = call("GET", "/_nodes/stats")
    lat = stats["nodes"][node.node_id]["tpu_search_latency"]
    assert lat["device"]["count"] == 1
    for name in metrics.DISPATCH_TOP_STEPS + ("dispatch.rescore",):
        assert lat[name]["count"] == 1, name
    steps = sum(lat[n]["mean"] for n in metrics.DISPATCH_TOP_STEPS)
    assert steps <= lat["device"]["mean"] * 1.001 + 0.01

    monkeypatch.setenv("ES_TPU_TRACE_SAMPLE", "1")
    st, r_on = call("POST", "/s/_search", body)
    assert normalized(r_on) == normalized(r_off)
    traced = tracing.recent_traces()[-1]
    names = {s["name"] for s in traced["spans"]}
    assert {"rest_total", "device", "demux", "fetch"} <= names
    assert set(metrics.DISPATCH_TOP_STEPS) <= names
    assert "device.fused_chunk" not in names


def test_the_cold_gather_goes_out_before_the_wait_and_the_steps_still_sum(
        env, monkeypatch):
    """PR 28: the cold side's gather is planned and launched under
    `dispatch.launch`, behind the sweep and before `dispatch.device_wait`;
    `dispatch.sparse_gather` is its collect, inside `dispatch.finish`;
    the four steps are still what `device` is made of."""
    node, call = env
    monkeypatch.setenv("ES_TPU_FORCE_TURBO", "1")
    monkeypatch.setenv("ES_TPU_TRACE_SAMPLE", "1")
    call("PUT", "/s", {"mappings": {"properties": {"body": {"type": "text"}}}})
    for i in range(48):
        call("PUT", f"/s/_doc/{i}", {"body": f"w{i % 4} w{i % 7} common"})
    call("POST", "/s/_refresh")
    body = {"query": {"match": {"body": "common w3"}}, "size": 10}
    call("POST", "/s/_search", body)           # engine build, first traces
    st, s0 = call("GET", "/_nodes/stats")
    st, r = call("POST", "/s/_search", body)
    assert st == 200 and r["hits"]["hits"]
    st, s1 = call("GET", "/_nodes/stats")
    t0 = s0["nodes"][node.node_id]["tpu_turbo"]
    t1 = s1["nodes"][node.node_id]["tpu_turbo"]
    rise = {key: t1[key] - t0[key] for key in (
        "sparse_queries", "sparse_gather_launches",
        "sparse_gather_overlapped", "sparse_fallbacks")}
    assert rise == {"sparse_queries": 1, "sparse_gather_launches": 1,
                    "sparse_gather_overlapped": 1, "sparse_fallbacks": 0}

    spans = {}
    for sp in tracing.recent_traces()[-1]["spans"]:
        spans.setdefault(sp["name"], []).append(sp)
    (dev,), (wait,) = spans["device"], spans["dispatch.device_wait"]
    (gather,) = [sp for sp in spans["dispatch.launch"]
                 if sp.get("meta", {}).get("gathers")]
    (collect,) = spans["dispatch.sparse_gather"]
    assert gather["parent"] == dev["id"]
    assert gather["end_ns"] <= wait["start_ns"]
    assert collect["parent"] in {sp["id"] for sp in spans["dispatch.finish"]}
    assert collect["start_ns"] >= wait["end_ns"]
    top = [sp for name in metrics.DISPATCH_TOP_STEPS for sp in spans[name]]
    assert {sp["parent"] for sp in top} == {dev["id"]}
    steps = sum(sp["duration_ms"] for sp in top)
    assert 0.8 * dev["duration_ms"] <= steps <= dev["duration_ms"] + 0.01


def test_a_program_built_inside_a_span_is_counted_and_named():
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.common import hbm_ledger

    def pr27_fresh_program(x):
        return x * 3 + 1

    # (the ledger's own reset would also drop the engines other test files
    # of this worker hold registered: stop and start the listener only)
    hbm_ledger.stop_jit_listener()
    n0 = hbm_ledger.compile_stats()["jit_builds"]
    jax.jit(pr27_fresh_program)(jnp.ones(5))   # not listening: not counted
    assert hbm_ledger.compile_stats()["jit_builds"] == n0
    hbm_ledger.install_jit_listener()
    hbm_ledger.install_jit_listener()          # idempotent
    try:
        before = hbm_ledger.compile_stats()
        with tracing.phase("dispatch.finish"):
            jax.jit(pr27_fresh_program)(jnp.ones(7))      # a new shape
        after = hbm_ledger.compile_stats()
        assert after["jit_builds"] - before["jit_builds"] >= 1
        assert after["jit_build_ms"] > before["jit_build_ms"]
        mine = [e for e in after["events"]
                if "pr27_fresh_program" in e.get("fun_name", "")]
        assert len(mine) == 1
        assert mine[0]["span"] == "dispatch.finish"
        assert mine[0]["backend_s"] > 0 and mine[0]["trace_s"] > 0
        assert mine[0]["wall_ms"] == pytest.approx(
            1e3 * (mine[0]["trace_s"] + mine[0]["lower_s"]
                   + mine[0]["backend_s"]), abs=0.5)
        assert metrics.counter_values()["tpu_compile.jit_builds"] >= 1
        # the same shape again is no build
        n = after["jit_builds"]
        jax.jit(pr27_fresh_program)(jnp.ones(7))
        with tracing.phase("dispatch.finish"):
            pass
        outside = [e for e in hbm_ledger.compile_stats()["events"]
                   if "fun_name" in e and e["span"] is None]
        assert hbm_ledger.compile_stats()["jit_builds"] >= n
        assert all("pr27_fresh_program" not in e["fun_name"]
                   for e in outside)
    finally:
        hbm_ledger.stop_jit_listener()


def test_the_collector_is_counted_by_generation():
    import gc

    tracing.install_gc_hook()
    tracing.install_gc_hook()                  # idempotent
    assert gc.callbacks.count(tracing._on_gc) == 1
    before = tracing.gc_stats()
    gc.collect(0)
    gc.collect()                               # generation 2
    after = tracing.gc_stats()
    assert after["young"]["collection_count"] \
        == before["young"]["collection_count"] + 1
    assert after["old"]["collection_count"] \
        == before["old"]["collection_count"] + 1
    for gen in ("young", "old"):
        assert after[gen]["collection_time_in_millis"] \
            >= before[gen]["collection_time_in_millis"]


def test_node_stats_carry_the_new_sections(env):
    node, call = env
    st, stats = call("GET", "/_nodes/stats")
    sec = stats["nodes"][node.node_id]
    assert {"young", "old"} == set(sec["jvm"]["gc"]["collectors"])
    assert "collection_time_in_millis" in sec["jvm"]["gc"]["collectors"]["old"]
    assert sec["tpu_scheduler"]["lane_idle_ms"] >= 0
    assert {"jit_builds", "jit_build_ms"} <= set(sec["tpu_compile"])
    for name in ("dispatch.prep", "dispatch.launch", "dispatch.device_wait",
                 "dispatch.finish", "dispatch.slice_build", "dispatch.mask",
                 "dispatch.sparse_gather", "dispatch.rescore",
                 "dispatch.cert_fallback", "dispatch.dense_rerun",
                 "engine_build.columns", "engine_build.kmeans"):
        assert name in sec["tpu_search_latency"]
