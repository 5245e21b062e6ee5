"""Named bounded executors + the dispatch scheduler's lanes (threadpool/).

Admission control: saturating one named pool rejects with 429
`es_rejected_execution_exception` (pool name in the reason) without
affecting the other pools. Lane keying: searches never share a device
dispatch across top-k depths or across the engine swap of a snapshot
refresh, and each reads rows BIT-identical to solo execution (the merged
rows' own suite is tests/test_scheduler.py).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from elasticsearch_tpu.threadpool import (
    AdaptiveDispatchScheduler, EsRejectedExecutionError, ThreadPool,
    pool_for_request,
)

from test_scheduler import _assert_rows_equal, _build_index


def tiny_pool(**overrides):
    sizes = {"search": 1, "write": 1, "get": 1, "management": 1,
             "snapshot": 1}
    queues = {"search": 1, "write": 1, "get": 1, "management": 1,
              "snapshot": 1}
    sizes.update(overrides.get("sizes", {}))
    queues.update(overrides.get("queues", {}))
    return ThreadPool(sizes=sizes, queue_sizes=queues)


# ---------------------------------------------------------------------------
# named pools: submission, stats, rejection, isolation
# ---------------------------------------------------------------------------


def test_submit_executes_and_counts():
    pool = ThreadPool(sizes={"search": 2})
    try:
        tasks = [pool.submit("search", lambda x: x * 2, i) for i in range(8)]
        assert [t.get(timeout=10) for t in tasks] == [i * 2 for i in range(8)]
        st = pool.stats()["search"]
        assert st["completed"] == 8
        assert st["queue"] == 0 and st["active"] == 0
        assert 1 <= st["largest"] <= 2
        assert st["ewma_ms"] >= 0.0
    finally:
        pool.shutdown()


def test_a_result_in_hand_is_already_counted():
    """The worker counts a task before it publishes the result: the
    earliest a caller can hold a result, the counters include it."""
    pool = ThreadPool(sizes={"search": 1})
    at_publish = []

    class _Publish(threading.Event):
        def set(self):
            at_publish.append(pool.stats()["search"])
            super().set()

    try:
        gate = threading.Event()
        task = pool.submit("search", gate.wait, 10)
        task._done = _Publish()
        gate.set()
        assert task.get(timeout=10) is True
        assert [(st["completed"], st["active"]) for st in at_publish] \
            == [(1, 0)]
    finally:
        pool.shutdown()


def test_saturated_pool_rejects_with_429_and_pool_name():
    pool = tiny_pool()
    release = threading.Event()
    try:
        running = pool.submit("search", release.wait, 10)   # occupies the worker
        time.sleep(0.05)
        queued = pool.submit("search", lambda: "queued")    # fills the queue
        with pytest.raises(EsRejectedExecutionError) as ei:
            pool.submit("search", lambda: "rejected")
        assert ei.value.status == 429
        assert ei.value.error_type == "es_rejected_execution_exception"
        assert "search" in str(ei.value)
        assert pool.stats()["search"]["rejected"] == 1
        # the REST error body carries the type the clients retry on
        assert ei.value.to_dict()["type"] == "es_rejected_execution_exception"
        release.set()
        assert queued.get(timeout=10) == "queued"
        assert running.get(timeout=10) is True
    finally:
        release.set()
        pool.shutdown()


def test_write_saturation_does_not_reject_searches():
    pool = tiny_pool()
    release = threading.Event()
    try:
        pool.submit("write", release.wait, 10)
        time.sleep(0.05)
        pool.submit("write", lambda: None)                  # queue full now
        with pytest.raises(EsRejectedExecutionError):
            pool.submit("write", lambda: None)
        # the search stage is a different bounded pool: unaffected
        assert pool.submit("search", lambda: "ok").get(timeout=10) == "ok"
        assert pool.stats()["search"]["rejected"] == 0
        assert pool.stats()["write"]["rejected"] == 1
    finally:
        release.set()
        pool.shutdown()


def test_execute_reenters_inline_from_own_worker():
    """A stage calling itself must run inline, not wait on its own
    single-worker pool (self-deadlock under saturation)."""
    pool = tiny_pool()
    try:
        def nested():
            return pool.execute("search", lambda: "inner")

        assert pool.execute("search", nested) == "inner"
    finally:
        pool.shutdown()


def test_task_errors_propagate_to_waiter():
    pool = ThreadPool(sizes={"management": 1})
    try:
        def boom():
            raise ValueError("broken task")

        with pytest.raises(ValueError, match="broken task"):
            pool.execute("management", boom)
        assert pool.stats()["management"]["completed"] == 1
    finally:
        pool.shutdown()


def test_pool_for_request_classification():
    assert pool_for_request("POST", "/idx/_search") == "search"
    assert pool_for_request("GET", "/_msearch") == "search"
    assert pool_for_request("POST", "/idx/_bulk") == "write"
    assert pool_for_request("POST", "/_reindex") == "write"
    assert pool_for_request("GET", "/idx/_doc/1") == "get"
    assert pool_for_request("PUT", "/idx/_doc/1") == "write"
    assert pool_for_request("GET", "/idx/_source/1") == "get"
    assert pool_for_request("PUT", "/_snapshot/repo/snap") == "snapshot"
    assert pool_for_request("GET", "/_cluster/health") == "management"
    assert pool_for_request("GET", "/") == "management"


def test_http_server_sheds_load_with_429():
    """End to end: a saturated search pool answers 429 with
    es_rejected_execution_exception while management keeps serving."""
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.rest import (
        HttpServer, RestController, register_handlers,
    )

    node = Node()
    pool = tiny_pool()
    node.thread_pool.shutdown()
    node.thread_pool = pool          # stats routes report the live pool
    rc = RestController()
    register_handlers(node, rc)
    release = threading.Event()
    started = threading.Event()

    def slow_search(req):
        from elasticsearch_tpu.rest.controller import RestResponse

        started.set()
        release.wait(10)
        return RestResponse(body={"slow": True})

    rc.register("GET", "/_slowtest/_search", slow_search)
    server = HttpServer(rc, port=0, thread_pool=pool)
    server.start()
    base = f"http://127.0.0.1:{server.port}"

    def http(path):
        try:
            with urllib.request.urlopen(base + path, timeout=15) as resp:
                return resp.status, json.loads(resp.read() or b"{}")
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")

    try:
        t1 = threading.Thread(target=http, args=("/_slowtest/_search",))
        t1.start()
        assert started.wait(10)
        t2 = threading.Thread(target=http, args=("/_slowtest/_search",))
        t2.start()                       # sits in the queue (capacity 1)
        deadline = time.monotonic() + 5
        while pool.stats()["search"]["queue"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        status, body = http("/_slowtest/_search")
        assert status == 429
        assert body["error"]["type"] == "es_rejected_execution_exception"
        assert "search" in body["error"]["reason"]
        # management pool unaffected: the cat route still answers and
        # reports the rejection
        status, _ = http("/_cluster/health")
        assert status == 200
        with urllib.request.urlopen(base + "/_cat/thread_pool/search",
                                    timeout=15) as resp:
            line = resp.read().decode()
        cols = line.split()
        assert cols[:5] == [node.node_name, "search", "1", "1", "1"]
        # PR 9 queue-wait columns: EWMA + histogram p99, both numeric
        assert len(cols) == 7
        float(cols[5])
        float(cols[6])
    finally:
        release.set()
        t1.join(timeout=10)
        t2.join(timeout=10)
        server.stop()
        pool.shutdown()
        node.close()


# ---------------------------------------------------------------------------
# scheduler lanes: keyed by (engine, k), bit-identical to solo execution
# ---------------------------------------------------------------------------


def test_scheduler_keys_by_k_and_window_zero_disables(monkeypatch):
    svc = _build_index(monkeypatch, turbo=False, uuid="u_co2")
    try:
        eng = svc.serving.snapshot().engine("body")
        monkeypatch.setenv("ES_TPU_COALESCE_US", "0")
        sched = AdaptiveDispatchScheduler()
        s, p, o = sched.dispatch(eng, [["alpha"]], 10)
        want_s, want_p, want_o = eng.search_many([[["alpha"]]], k=10)[0]
        _assert_rows_equal((s[0], p[0], o[0]),
                           (want_s[0], want_p[0], want_o[0]), "win0")
        assert sched.stats()["sched_dispatches"] == 0
        assert sched.stats()["direct_dispatches"] == 1

        # different k values never share a device dispatch: each k is a
        # lane of its own, however long the two wait side by side
        monkeypatch.setenv("ES_TPU_COALESCE_US", "50000")
        sched2 = AdaptiveDispatchScheduler(buckets=(2,),
                                           interactive_us=50_000.0)
        out = {}

        def run(k):
            out[k] = sched2.dispatch(eng, [["beta", "gamma"]], k)

        ts = [threading.Thread(target=run, args=(k,)) for k in (5, 10)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        for k in (5, 10):
            want = eng.search_many([[["beta", "gamma"]]], k=k)[0]
            _assert_rows_equal((out[k][0][0], out[k][1][0], out[k][2][0]),
                               (want[0][0], want[1][0], want[2][0]), k)
            assert out[k][0].shape == (1, k)
        st = sched2.stats()
        assert st["sched_dispatches"] == 2 and st["largest_batch"] == 1
    finally:
        svc.close()


def test_mid_flight_engine_swap_keeps_lanes_separate(monkeypatch):
    """A snapshot refresh while a waiter is parked swaps the engine
    object: waiters on the OLD engine finish on the snapshot they
    captured, new arrivals key onto the new engine's lane — both
    bit-identical to solo execution."""
    svc = _build_index(monkeypatch, turbo=True, uuid="u_co3")
    try:
        monkeypatch.setenv("ES_TPU_COALESCE_US", "400000")
        snap1 = svc.serving.snapshot()
        eng1 = snap1.engine("body")
        solo1 = eng1.search_many([[["alpha"]]], k=10)[0]

        # a bucket two singles would fill, budgets they have to wait out:
        # were the two engines one lane, they would share ONE dispatch
        sched = AdaptiveDispatchScheduler(buckets=(2,),
                                          interactive_us=400_000.0)
        got1 = {}

        def old_engine_waiter():
            got1["rows"] = sched.dispatch(eng1, [["alpha"]], 10)

        t = threading.Thread(target=old_engine_waiter)
        t.start()
        deadline = time.monotonic() + 5       # old-engine waiter is parked
        while sched.stats()["lanes"] == 0 and time.monotonic() < deadline:
            time.sleep(0.005)

        # refresh swaps the serving snapshot -> NEW engine object
        svc.index_doc("new", {"body": "alpha alpha alpha fresh"})
        svc.refresh()
        snap2 = svc.serving.snapshot()
        eng2 = snap2.engine("body")
        assert eng2 is not eng1
        rows2 = sched.dispatch(eng2, [["alpha"]], 10)
        t.join(timeout=60)

        _assert_rows_equal(
            (got1["rows"][0][0], got1["rows"][1][0], got1["rows"][2][0]),
            (solo1[0][0], solo1[1][0], solo1[2][0]), "old engine")
        solo2 = eng2.search_many([[["alpha"]]], k=10)[0]
        _assert_rows_equal((rows2[0][0], rows2[1][0], rows2[2][0]),
                           (solo2[0][0], solo2[1][0], solo2[2][0]),
                           "new engine")
        st = sched.stats()
        assert st["sched_dispatches"] == 2 and st["largest_batch"] == 1
    finally:
        svc.close()
