"""PR 42: the spans where the chips wait. The bm25 host finish by named
steps (one histogram observation an engine call each, children summing
under parents, no `tracing.phase` a query), the REST path around a
dispatch (`rest_total` > `rest.parse`, `route`, for `_search` and
`_msearch` alike; `rest.respond` after it on the HTTP thread) and the
lane's fill wait (`sched_fill`).

No wall-clock window here is tighter than an order of magnitude: what is
held is counts, nesting and sums."""

import http.client
import json
import threading
import time

import pytest

from elasticsearch_tpu.common import metrics, tracing
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest import RestController, register_handlers
from elasticsearch_tpu.rest.http_server import HttpServer

FINISH_CHILDREN = ("dispatch.rescore_rows", "dispatch.rescore_survivors",
                   "dispatch.survivor_bound", "dispatch.merge_cert")
LOOP_STEPS = ("dispatch.sparse_gather", "dispatch.survivor_bound",
              "dispatch.rescore_survivors", "dispatch.merge_cert")
REST_CHILDREN = ("rest.parse", "route", "rest.respond")


@pytest.fixture(autouse=True)
def _fresh_recorder():
    metrics.reset_for_tests()
    tracing.reset_for_tests()
    yield
    metrics.reset_for_tests()
    tracing.reset_for_tests()


@pytest.fixture()
def env(monkeypatch):
    """A node whose `body` field is served by the Turbo engine: `common`
    owns a column, the `r*` terms (df 7-8 of 96) ride the cold side, so a
    `common r*` match runs every step of the finish."""
    monkeypatch.setenv("ES_TPU_FORCE_TURBO", "1")
    monkeypatch.setenv("ES_TPU_TURBO_COLD_DF", "16")
    node = Node()
    rc = RestController()
    register_handlers(node, rc)

    def call(method, path, body=None, params=None, raw=None):
        data = raw if raw is not None else (
            json.dumps(body).encode() if body is not None else None)
        resp = rc.dispatch(method, path, params or {}, data)
        return resp.status, json.loads(resp.encode() or b"{}")

    call("PUT", "/s", {"mappings": {"properties": {"body": {"type": "text"}}}})
    for i in range(96):
        call("PUT", f"/s/_doc/{i}",
             {"body": f"common w{i % 3} r{i % 13} filler{i}"})
    call("POST", "/s/_refresh")
    # the engine's build and the programs' first traces stay out of the
    # counts below
    call("POST", "/s/_search", {"query": {"match": {"body": "common r1"}}})
    call.controller = rc
    yield node, call
    node.close()


@pytest.fixture()
def over_http(env):
    """The same node behind its HTTP server: `call` as a client makes it."""
    node, call = env
    server = HttpServer(call.controller, port=0)
    server.start()

    def request(method, path, body=None, raw=None):
        data = raw if raw is not None else (
            json.dumps(body).encode() if body is not None else None)
        c = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)
        try:
            c.request(method, path, body=data,
                      headers={"Content-Type": "application/json"})
            resp = c.getresponse()
            return resp.status, json.loads(resp.read() or b"{}")
        finally:
            c.close()

    yield node, request
    server.stop()


def msearch_raw(n):
    lines = []
    for j in range(n):
        lines.append(json.dumps({"index": "s"}))
        lines.append(json.dumps({"query": {"match": {
            "body": f"common r{j % 13} w{j % 3}"}}, "size": 5}))
    return ("\n".join(lines) + "\n").encode()


def latency(node, call):
    _, stats = call("GET", "/_nodes/stats")
    return stats["nodes"][node.node_id]["tpu_search_latency"]


def total_ms(name):
    return metrics.raw_dump(name)["total"]


def responded(count, timeout=10.0):
    """The HTTP thread observes `rest.respond` when the socket write has
    returned, which the client may see first: wait for it."""
    end = time.monotonic() + timeout
    while (metrics.summary("rest.respond")["count"] < count
           and time.monotonic() < end):
        time.sleep(0.005)
    return metrics.summary("rest.respond")["count"]


def test_an_msearch_observes_each_finish_step_once_an_engine_call(env):
    node, call = env
    before = latency(node, call)
    st, r = call("POST", "/_msearch", raw=msearch_raw(6))
    assert st == 200 and all(x["hits"]["hits"] for x in r["responses"])
    after = latency(node, call)
    calls = after["device"]["count"] - before["device"]["count"]
    assert calls == 1
    for name in FINISH_CHILDREN + ("dispatch.rescore", "dispatch.finish",
                                   "dispatch.sparse_gather",
                                   "dispatch.cert_fallback"):
        assert after[name]["count"] - before[name]["count"] == calls, name


def test_rescore_is_its_two_children_and_the_steps_fit_in_finish(env):
    node, call = env
    base = {n: total_ms(n) for n in metrics.DECLARED if n.startswith(
        "dispatch.")}
    call("POST", "/_msearch", raw=msearch_raw(6))
    ms = {n: total_ms(n) - base[n] for n in base}
    # every step of the per-query loop ran, and so did the chunk-wide one
    for name in FINISH_CHILDREN + ("dispatch.sparse_gather",):
        assert ms[name] > 0, name
    assert ms["dispatch.rescore_rows"] + ms["dispatch.rescore_survivors"] \
        == pytest.approx(ms["dispatch.rescore"], abs=1e-6)
    named = (ms["dispatch.rescore"] + ms["dispatch.sparse_gather"]
             + ms["dispatch.survivor_bound"] + ms["dispatch.merge_cert"]
             + ms["dispatch.cert_fallback"])
    assert named <= ms["dispatch.finish"] + 1e-6
    # what the loop leaves unnamed is its own bookkeeping: the steps hold
    # most of the finish even at this size (on the chip 95 % and more)
    assert named >= 0.5 * ms["dispatch.finish"]


def spans_by_name(trace):
    out = {}
    for sp in trace["spans"]:
        out.setdefault(sp["name"], []).append(sp)
    return out


@pytest.mark.parametrize("path", ["_msearch", "_search"])
def test_a_traced_request_nests_parse_and_route_under_rest_total(
        env, monkeypatch, path):
    node, call = env
    monkeypatch.setenv("ES_TPU_TRACE_SAMPLE", "1")
    if path == "_msearch":
        st, _ = call("POST", "/_msearch", raw=msearch_raw(4))
        want = ("rest.parse", "route")
    else:
        st, _ = call("POST", "/s/_search",
                     {"query": {"match": {"body": "common r2"}}})
        # a _search's body is parsed by the controller, before the
        # handler opens rest_total: histogram and annotation, no span
        want = ("route",)
    assert st == 200
    trace = tracing.recent_traces()[-1]
    spans = spans_by_name(trace)
    (root,) = spans["rest_total"]
    assert root["meta"]["path"].endswith(path)
    for name in want:
        (sp,) = spans[name]
        assert sp["parent"] == root["id"], name
        assert root["start_ns"] <= sp["start_ns"] <= sp["end_ns"] \
            <= root["end_ns"], name
    # the encode and the write come after rest_total, on the HTTP thread,
    # which carries no context: a histogram and an annotation, no span
    assert "rest.respond" not in spans
    assert spans["route"][0]["meta"]["disj"] == (4 if path == "_msearch"
                                                 else 1)
    if path == "_msearch":
        assert spans["rest.parse"][0]["meta"]["bodies"] == 4
    # route comes before the dispatch it decides
    (dev,) = spans["device"]
    assert spans["route"][0]["end_ns"] <= dev["start_ns"]
    # still a partition of the root: nothing is counted twice
    own = tracing.self_times(trace["spans"])
    assert sum(own.values()) <= root["duration_ms"] * 1.001 + 0.01
    assert sum(own.values()) >= 0.5 * root["duration_ms"]


def test_the_finish_steps_are_one_span_a_chunk_laid_end_to_end(
        env, monkeypatch):
    """No `phase` a query inside `_finish_chunk`: eight bodies, one span
    of each step, the loop's four inside `dispatch.finish` one after the
    other, each carrying what sized it."""
    node, call = env
    monkeypatch.setenv("ES_TPU_TRACE_SAMPLE", "1")
    st, _ = call("POST", "/_msearch", raw=msearch_raw(8))
    assert st == 200
    spans = spans_by_name(tracing.recent_traces()[-1])
    finishes = [sp for sp in spans["dispatch.finish"]
                if sp.get("meta", {}).get("queries")]
    assert len(finishes) == 1
    finish = finishes[0]
    (rows,) = spans["dispatch.rescore_rows"]
    assert rows["parent"] == finish["id"]
    assert rows["meta"]["queries"] == 8 and rows["meta"]["rows"] > 0
    assert rows["meta"]["candidates"] > 0
    # the parent step keeps its histogram; its children carry the spans
    assert "dispatch.rescore" not in spans
    at = rows["end_ns"]
    for name in LOOP_STEPS:
        (sp,) = spans[name]
        assert sp["parent"] == finish["id"], name
        assert at <= sp["start_ns"] <= sp["end_ns"] <= finish["end_ns"], name
        at = sp["end_ns"]
    assert spans["dispatch.sparse_gather"][0]["meta"]["pairs"] == 8
    assert spans["dispatch.rescore_survivors"][0]["meta"]["docs"] > 0
    assert spans["dispatch.merge_cert"][0]["meta"]["docs"] > 0


def test_the_spans_docs_are_what_the_cold_counters_count(env, monkeypatch):
    """PR 43: the bound runs before the enumeration. `sparse_gather` and
    `survivor_bound` carry the RAW cold postings the pairs laid out,
    `rescore_survivors` the distinct docs the bound kept (their
    `np.unique` is booked there, with their impacts and exact scores);
    `tpu_turbo.cold_enum_docs` / `cold_survivor_docs` of
    `GET /_nodes/stats` rise by the same two sums."""
    node, call = env

    def cold():
        _, stats = call("GET", "/_nodes/stats")
        turbo = stats["nodes"][node.node_id]["tpu_turbo"]
        return turbo["cold_enum_docs"], turbo["cold_survivor_docs"]

    monkeypatch.setenv("ES_TPU_TRACE_SAMPLE", "1")
    enum0, surv0 = cold()
    st, _ = call("POST", "/_msearch", raw=msearch_raw(8))
    assert st == 200
    spans = spans_by_name(next(
        t for t in reversed(tracing.recent_traces())
        if any(sp["name"] == "dispatch.survivor_bound"
               for sp in t["spans"])))
    enum1, surv1 = cold()
    (bound,) = spans["dispatch.survivor_bound"]
    (surv,) = spans["dispatch.rescore_survivors"]
    assert bound["meta"]["docs"] == enum1 - enum0 \
        == spans["dispatch.sparse_gather"][0]["meta"]["docs"]
    assert surv["meta"]["docs"] == surv1 - surv0
    # 8 pairs of one cold term each (df 7-8 of 96): all enumerated
    assert 8 * 7 <= enum1 - enum0 <= 8 * 8
    assert 0 < surv1 - surv0 <= enum1 - enum0


@pytest.mark.parametrize("path", ["_search", "_msearch", "_doc"])
def test_every_search_call_observes_each_rest_step_once(over_http, path):
    """(An `_msearch`'s ndjson is parsed by its handler alone: the
    controller's doomed attempt at it is not a second `rest.parse`. Any
    other endpoint's body and response are under no search step.)"""
    node, request = over_http
    before = latency(node, request)
    seen = metrics.summary("rest.respond")["count"]
    if path == "_msearch":
        st, _ = request("POST", "/_msearch", raw=msearch_raw(3))
    elif path == "_search":
        st, _ = request("POST", "/s/_search",
                        {"query": {"match": {"body": "common"}}})
    else:
        st, _ = request("PUT", "/s/_doc/900", {"body": "common late"})
    assert st in (200, 201)
    responded(seen + (path != "_doc"))
    after = latency(node, request)
    rise = {n: after[n]["count"] - before[n]["count"]
            for n in REST_CHILDREN + ("rest_total",)}
    # (the two GET /_nodes/stats around it are not searches either)
    assert rise == dict.fromkeys(REST_CHILDREN + ("rest_total",),
                                 0 if path == "_doc" else 1)


def test_rest_respond_is_the_http_threads_step(over_http, env):
    """The response is encoded and written where it always was: a call of
    the controller alone observes no `rest.respond`, a client's does, and
    reads what the handler returned."""
    node, request = over_http
    _, call = env
    body = {"query": {"match": {"body": "common r3"}}}
    resp = call.controller.dispatch("POST", "/s/_search", {},
                                    json.dumps(body).encode())
    assert metrics.summary("rest.respond")["count"] == 0
    st, got = request("POST", "/s/_search", body)
    assert responded(1) == 1
    assert st == 200 and got["hits"] == json.loads(resp.encode())["hits"]


class _Engine:
    """search_many stub, slow enough that a wait in front of it shows."""

    kind = "stub"

    def search_many(self, batches, k=10, check=None):
        import numpy as np

        qs = batches[0]
        z = np.zeros((len(qs), k), np.int32)
        s = np.zeros((len(qs), k), np.float32)
        s[:, 0] = 1.0
        return [(s, z, z.copy())]


@pytest.mark.parametrize("waiters", [1, 3])
def test_a_lane_flush_observes_sched_fill_once(waiters):
    from elasticsearch_tpu.threadpool.scheduler import (
        AdaptiveDispatchScheduler,
    )

    sched = AdaptiveDispatchScheduler(buckets=(4,), interactive_us=20_000,
                                      inflight=1)
    eng = _Engine()
    errors = []

    def waiter(i):
        try:
            sched.dispatch(eng, [[f"q{i}"]], 10)
        except BaseException as e:  # noqa: BLE001 — asserted below
            errors.append(e)

    threads = [threading.Thread(target=waiter, args=(i,))
               for i in range(waiters)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    flushes = sched.stats()["sched_dispatches"]
    fill = metrics.raw_dump("sched_fill")
    wait = metrics.raw_dump("sched_tier_wait.interactive")
    assert fill["count"] == flushes >= 1
    assert wait["count"] == waiters
    # a waiter's wait = its batch's fill + the dispatch + its wake-up: the
    # longest wait holds the longest fill
    assert wait["max"] >= fill["max"]
    if waiters == 1:
        # alone under the top rung it waited its tier's budget (20 ms) out
        assert fill["total"] >= 2.0
        assert wait["total"] >= fill["total"]


def test_steps_add_joins_the_calls_accumulator_and_lays_one_span():
    names = ("dispatch.finish", "dispatch.merge_cert", "dispatch.rescore")
    tc = tracing.TraceContext()
    with tracing.activate(tc), tracing.steps(names):
        with tracing.phase("dispatch.finish") as finish:
            t0 = finish._t0
            tracing.steps.add("dispatch.merge_cert", 0.25, t0, docs=7)
            tracing.steps.add("dispatch.merge_cert", 0.5, t0 + 250_000)
            tracing.steps.add("dispatch.rescore", 1.5)     # no span
    for name in names:
        assert metrics.summary(name)["count"] == 1, name
    assert total_ms("dispatch.merge_cert") == pytest.approx(0.75)
    assert total_ms("dispatch.rescore") == pytest.approx(1.5)
    spans = {}
    for sp in tc.span_dicts():
        spans.setdefault(sp["name"], []).append(sp)
    assert "dispatch.rescore" not in spans
    a, b = spans["dispatch.merge_cert"]
    assert a["parent"] == b["parent"] == spans["dispatch.finish"][0]["id"]
    assert (a["start_ns"], a["end_ns"]) == (t0, t0 + 250_000)
    assert b["start_ns"] == a["end_ns"] and a["meta"] == {"docs": 7}


def test_steps_add_outside_a_call_observes_the_histogram():
    tracing.steps.add("dispatch.merge_cert", 2.0)
    tracing.steps.add("dispatch.merge_cert", 4.0)
    assert metrics.summary("dispatch.merge_cert")["count"] == 2
    with tracing.steps(("dispatch.finish",)):        # not this call's step
        tracing.steps.add("dispatch.merge_cert", 1.0)
    assert metrics.summary("dispatch.merge_cert")["count"] == 3
    assert tracing.recent_traces() == []


@pytest.mark.parametrize("name", ["knn_candidates_per_query",
                                  "knn_nprobe_ratio", "sched_lanes"])
def test_what_nothing_read_is_gone(env, name):
    node, call = env
    assert name not in metrics.DECLARED
    assert name not in metrics.DECLARED_GAUGES
    _, stats = call("GET", "/_nodes/stats")
    assert name not in stats["nodes"][node.node_id]["tpu_search_latency"]
    assert name not in json.dumps(metrics.scrape_payload())


@pytest.mark.parametrize("name", FINISH_CHILDREN + REST_CHILDREN + (
    "sched_fill", "rest_total", "dispatch.cert_fallback"))
def test_a_fresh_nodes_stats_hold_every_histogram_a_new_metric_reads(
        env, name):
    node, call = env
    h = latency(node, call)[name]
    assert set(h) >= {"count", "mean", "max"}
