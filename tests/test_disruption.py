"""Coordinator shard failover under transport faults (PR 6).

The disruption lane: injected `rpc_*` faults, organic kills/partitions, and
deadline expiry all exercise the SAME coordinator recovery paths — replica
retry with excluded-node tracking, node transport circuits, and partial
results with per-shard `_shards.failures` accounting.
"""

import time

import pytest

from elasticsearch_tpu.action.search_action import _COORD_COUNTERS
from elasticsearch_tpu.cluster_node import form_local_cluster
from elasticsearch_tpu.common import faults
from elasticsearch_tpu.common.errors import SearchPhaseExecutionError
from elasticsearch_tpu.transport.channels import NodeUnavailableError

pytestmark = pytest.mark.disruption

MAPPINGS = {"properties": {"n": {"type": "integer"},
                           "body": {"type": "text"}}}


def make_cluster(n_data=3, data_path=None):
    names = ["m0"] + [f"d{i}" for i in range(n_data)]
    roles = {"m0": ("master",)}
    return form_local_cluster(names, data_path=data_path, roles=roles)


def index_body(shards=2, replicas=1):
    return {"settings": {"number_of_shards": shards,
                         "number_of_replicas": replicas},
            "mappings": MAPPINGS}


def bulk_ops(start, count):
    return [{"op": "index", "id": str(i),
             "source": {"n": i, "body": f"word{i % 7} common text"}}
            for i in range(start, start + count)]


def snap():
    return dict(_COORD_COUNTERS)


def delta(before, key):
    return _COORD_COUNTERS[key] - before[key]


def ranked_first(coordinator, store, index="docs", sid=0):
    """The copy holder the coordinator would query first for this shard."""
    copies = [r for r in store.current().shard_copies(index, sid)
              if r.state == "STARTED"]
    return coordinator.search_action._rank_copies(copies)[0]


def normalized(resp):
    out = dict(resp)
    out.pop("took", None)
    return out


BODY = {"query": {"match": {"body": "common"}}, "size": 10,
        "track_total_hits": True}


def test_injected_rpc_fault_fails_over_bit_identical():
    """The acceptance differential: with one node's query RPC faulted and a
    second STARTED copy available, the response is bit-identical to the
    fault-free run, `_shards.failed == 0`, and `shard_retries > 0`."""
    nodes, store, channels = make_cluster()
    master, a, b, c = nodes
    a.create_index("docs", index_body(2, 1))
    a.bulk("docs", bulk_ops(0, 40))
    a.refresh("docs")

    victim = ranked_first(master, store)
    before = snap()
    with faults.inject(f"rpc_query#{victim}:raisexinf"):
        r_fault = master.search("docs", BODY)
    assert r_fault["_shards"]["failed"] == 0
    assert "failures" not in r_fault["_shards"]
    assert delta(before, "shard_retries") >= 1

    r_clean = master.search("docs", BODY)
    assert normalized(r_fault) == normalized(r_clean)


def test_organic_kill_fails_over_and_revives():
    nodes, store, channels = make_cluster()
    master, a, b, c = nodes
    a.create_index("docs", index_body(2, 1))
    a.bulk("docs", bulk_ops(0, 40))
    a.refresh("docs")

    victim = ranked_first(master, store)
    channels.kill(victim)
    r = master.search("docs", BODY)
    assert r["_shards"]["failed"] == 0
    assert r["hits"]["total"]["value"] == 40

    channels.revive(victim)
    r2 = master.search("docs", BODY)
    assert r2["_shards"]["failed"] == 0
    assert normalized(r) == normalized(r2)


def test_partition_and_heal():
    """A one-sided partition (coordinator cut off from one data node) is
    routed around via replicas; heal restores the direct path."""
    nodes, store, channels = make_cluster()
    master, a, b, c = nodes
    a.create_index("docs", index_body(2, 1))
    a.bulk("docs", bulk_ops(0, 40))
    a.refresh("docs")

    victim = ranked_first(master, store)
    channels.partition({"m0"}, {victim})
    r = master.search("docs", BODY)
    assert r["_shards"]["failed"] == 0
    assert r["hits"]["total"]["value"] == 40

    channels.heal()
    r2 = master.search("docs", BODY)
    assert normalized(r) == normalized(r2)


def test_all_copies_down_partial_results():
    """Every copy of every shard faulted: the response is a PARTIAL with a
    populated `_shards.failures` array — not an exception."""
    nodes, store, channels = make_cluster()
    master, a, b, c = nodes
    a.create_index("docs", index_body(2, 1))
    a.bulk("docs", bulk_ops(0, 40))
    a.refresh("docs")

    with faults.inject("rpc_query:raisexinf"):
        r = master.search("docs", BODY)
    assert r["_shards"]["failed"] == r["_shards"]["total"] == 2
    assert r["_shards"]["successful"] == 0
    assert r["hits"]["hits"] == []
    failures = r["_shards"]["failures"]
    assert len(failures) == 2
    for f in failures:
        assert f["reason"]["type"] == "node_not_connected_exception"
        assert f["reason"]["phase"] == "query"
        # excluded-node tracking: every copy was attempted before giving up
        assert len(f["reason"]["attempted_nodes"]) == 2


def test_all_copies_down_strict_raises():
    """allow_partial_search_results=false escalates exhausted shards to a
    search_phase_execution_exception instead of a partial."""
    nodes, store, channels = make_cluster()
    master, a, b, c = nodes
    a.create_index("docs", index_body(2, 1))
    a.bulk("docs", bulk_ops(0, 40))
    a.refresh("docs")

    body = dict(BODY, allow_partial_search_results=False)
    with faults.inject("rpc_query:raisexinf"):
        with pytest.raises(SearchPhaseExecutionError) as ei:
            master.search("docs", body)
    assert ei.value.error_type == "search_phase_execution_exception"
    assert ei.value.metadata["failures"]
    # reader contexts must not leak out of the failed request
    for n in nodes:
        assert n.search_action.contexts.open_contexts == 0


def test_hung_node_deadline_yields_timed_out_partial():
    """A hung query RPC is abandoned when the request timeout expires; the
    response comes back `timed_out: true` within the budget."""
    nodes, store, channels = make_cluster()
    master, a, b, c = nodes
    a.create_index("docs", index_body(2, 1))
    a.bulk("docs", bulk_ops(0, 40))
    a.refresh("docs")

    before = snap()
    body = dict(BODY, timeout="150ms")
    t0 = time.monotonic()
    with faults.inject("rpc_query:hangxinf=0.5"):
        r = master.search("docs", body)
    assert time.monotonic() - t0 < 2.0
    assert r["timed_out"] is True
    assert r["_shards"]["failed"] >= 1
    assert delta(before, "rpc_timeouts") >= 1
    assert any(f["reason"]["type"] == "receive_timeout_transport_exception"
               for f in r["_shards"]["failures"])
    time.sleep(0.6)   # drain the abandoned hang threads before teardown


def test_rpc_timeout_floor_fails_over_to_replica(monkeypatch):
    """With no request timeout, ES_TPU_RPC_TIMEOUT_MS alone bounds each RPC:
    a hung node times out and the shard recovers on its replica — full
    results, no timed_out flag, bit-identical to the fault-free run."""
    nodes, store, channels = make_cluster()
    master, a, b, c = nodes
    a.create_index("docs", index_body(2, 1))
    a.bulk("docs", bulk_ops(0, 40))
    a.refresh("docs")

    # warm the query path first: cold-start compilation must not read as a
    # hung node once the floor applies
    clean = master.search("docs", BODY)

    monkeypatch.setenv("ES_TPU_RPC_TIMEOUT_MS", "400")
    victim = ranked_first(master, store)
    before = snap()
    with faults.inject(f"rpc_query#{victim}:hangxinf=2.0"):
        r = master.search("docs", BODY)
    assert r["_shards"]["failed"] == 0
    assert r["timed_out"] is False
    assert r["hits"]["total"]["value"] == 40
    assert delta(before, "rpc_timeouts") >= 1
    assert delta(before, "shard_retries") >= 1
    assert normalized(r) == normalized(clean)
    time.sleep(1.8)   # drain the abandoned hang threads before teardown


def test_transport_circuit_opens_then_recovers(monkeypatch):
    """Consecutive transport failures to one node open its circuit (routing
    quarantine); after the backoff a half-open probe against the revived
    node closes it again."""
    # long enough that a loaded machine (six test workers) cannot spend the
    # whole backoff between the search that opens the circuit and the next
    monkeypatch.setenv("ES_TPU_HEALTH_BACKOFF_MS", "400")
    nodes, store, channels = make_cluster(n_data=2)
    master, a, b = nodes
    a.create_index("docs", index_body(2, 0))
    a.bulk("docs", bulk_ops(0, 30))
    a.refresh("docs")

    victim = ranked_first(master, store)
    channels.kill(victim)
    svc = master.search_action
    for _ in range(4):
        r = master.search("docs", BODY)
        assert r["_shards"]["failed"] >= 1   # single-copy shard is down
        if (h := svc._node_health.get(victim)) and h.state == "open":
            break
    h = svc._node_health.get(victim)
    assert h is not None and h.state == "open"

    # quarantined-but-only-copy: the next search still force-probes it
    before = snap()
    master.search("docs", BODY)
    assert delta(before, "node_circuit_open") >= 1

    channels.revive(victim)
    time.sleep(0.45)   # past the 400ms backoff -> half-open probe admitted
    r = master.search("docs", BODY)
    assert r["_shards"]["failed"] == 0
    assert h.state == "closed"


def test_can_match_failopen_reroutes_to_replica():
    """A can_match fault fails OPEN (shard kept) and demotes the
    unreachable node so the query phase targets the replica directly."""
    nodes, store, channels = make_cluster()
    master, a, b, c = nodes
    a.create_index("docs", index_body(4, 1))
    a.bulk("docs", [{"op": "index", "id": "special",
                     "source": {"n": 1, "body": "uniqueterm only here"}}]
           + bulk_ops(0, 40))
    a.refresh("docs")

    victim = ranked_first(master, store)
    before = snap()
    body = {"query": {"term": {"body": "uniqueterm"}},
            "track_total_hits": True}
    with faults.inject(f"rpc_can_match#{victim}:raisexinf"):
        r = master.search("docs", body)
    assert r["hits"]["total"]["value"] == 1
    assert r["_shards"]["failed"] == 0
    # ES semantics: `successful` counts skipped shards too
    assert r["_shards"]["successful"] == r["_shards"]["total"]
    assert delta(before, "can_match_reroutes") >= 1


def test_fetch_failure_drops_one_shard_keeps_rest():
    """A failed fetch drops THAT shard's hits — with a phase:fetch failure
    entry — while other shards' hits and every reader context survive."""
    nodes, store, channels = make_cluster()
    master, a, b, c = nodes
    a.create_index("docs", index_body(2, 1))
    a.bulk("docs", bulk_ops(0, 40))
    a.refresh("docs")

    body = dict(BODY, size=20)
    clean = master.search("docs", body)
    assert len(clean["hits"]["hits"]) == 20

    # the fetch goes to whichever node SERVED the query; fault them all
    before = snap()
    with faults.inject("rpc_fetch:raisexinf"):
        r = master.search("docs", body)
    assert r["hits"]["total"]["value"] == 40    # query phase succeeded
    assert r["hits"]["hits"] == []              # every fetch dropped
    assert r["_shards"]["failed"] == 2
    assert r["_shards"]["successful"] == 0
    assert all(f["reason"]["phase"] == "fetch"
               for f in r["_shards"]["failures"])
    assert delta(before, "fetch_failures") == 2
    # the leak fix: contexts freed even though the fetch never ran
    for n in nodes:
        assert n.search_action.contexts.open_contexts == 0

    # single-node fault: the OTHER shard's hits survive
    served_nodes = {ranked_first(master, store, sid=s) for s in range(2)}
    if len(served_nodes) == 2:
        victim = sorted(served_nodes)[0]
        with faults.inject(f"rpc_fetch#{victim}:raisexinf"):
            r2 = master.search("docs", body)
        assert r2["_shards"]["failed"] == 1
        assert 0 < len(r2["hits"]["hits"]) < 20


def test_deadline_expired_mid_fanout_skips_remaining_shards():
    """When the budget dies between shards, un-attempted shards become
    timed-out failures rather than hanging the request."""
    nodes, store, channels = make_cluster()
    master, a, b, c = nodes
    a.create_index("docs", index_body(3, 1))
    a.bulk("docs", bulk_ops(0, 40))
    a.refresh("docs")

    before = snap()
    # every copy of every shard hangs 120ms; 200ms budget covers ~1 shard
    with faults.inject("rpc_query:hangxinf=0.12"):
        r = master.search("docs", dict(BODY, timeout="200ms"))
    assert r["timed_out"] is True
    assert delta(before, "rpc_timeouts") + delta(
        before, "deadline_expired") >= 1
    assert r["_shards"]["failed"] + r["_shards"]["successful"] \
        == r["_shards"]["total"]
    time.sleep(0.3)   # drain the abandoned hang threads before teardown


def test_coordinator_stats_section():
    """GET /_nodes/stats exposes the resilience counters + circuits under
    `tpu_coordinator`."""
    from elasticsearch_tpu.rest.handlers import _tpu_coordinator_stats

    s = _tpu_coordinator_stats()
    for key in ("shard_retries", "node_circuit_open", "rpc_timeouts",
                "fetch_failures", "can_match_reroutes", "deadline_expired"):
        assert isinstance(s[key], int)
    assert "open_circuits" in s["transport"]
    assert "transport_failures" in s["transport"]


def test_disruptable_transport_error_taxonomy():
    """DisruptableMockTransport-style drops surface NodeUnavailableError to
    arg-accepting callbacks; legacy zero-arg callbacks still fire."""
    from elasticsearch_tpu.testing.deterministic import DeterministicTaskQueue
    from elasticsearch_tpu.testing.disruptable_transport import (
        DisruptableTransport,
    )

    q = DeterministicTaskQueue(seed=7)
    t = DisruptableTransport(q)
    t.register("a", lambda sender, msg, reply: reply({"ok": True}))

    errs, legacy, replies = [], [], []
    t.send("x", "missing", {"m": 1}, replies.append, errs.append)
    t.send("x", "missing", {"m": 2}, replies.append,
           lambda: legacy.append(1))
    q.run_until_quiet()
    assert len(errs) == 1 and isinstance(errs[0], NodeUnavailableError)
    assert "no route" in str(errs[0])
    assert legacy == [1]

    # a two-sided partition drops the request the same way
    t.register("b", lambda sender, msg, reply: reply({"ok": True}))
    t.partition({"a"}, {"b"})
    t.send("a", "b", {"m": 3}, replies.append, errs.append)
    q.run_until_quiet()
    assert len(errs) == 2 and isinstance(errs[1], NodeUnavailableError)
    t.heal()
    t.send("a", "b", {"m": 4}, replies.append, errs.append)
    q.run_until_quiet()
    assert replies and replies[-1] == {"ok": True}
