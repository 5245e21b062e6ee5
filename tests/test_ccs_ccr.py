"""Cross-cluster search & replication suite (PR 20).

Two in-process clusters over independent `LocalNodeChannels`, joined by
a `RemoteClusterService` registry on the querying side. Pins:

  * CCS fan-out for `remote:index` patterns merges BIT-identically to
    the local multi-index merge (the acceptance bar: a healthy fan-out
    and a local merged search over the same data agree hit-for-hit).
  * partial-results semantics: a dead `skip_unavailable=true` remote
    degrades to a `_clusters.skipped` entry — never a 5xx; without the
    flag the transport error propagates.
  * `#cluster` fault selectors: `rpc_remote_search#<alias>:raise` burns
    attempts against the retry budget, `rpc_ccr_fetch#<alias>:hang`
    surfaces as RpcTimeoutError under the ES_TPU_RPC_TIMEOUT_MS floor
    and the next poll recovers.
  * CCR: follow -> converge -> pause -> resume, seq-no idempotent
    re-apply, checksum-mismatch bounded re-fetch, follower stats lag
    accounting.
  * REST: /_remote/info, /{index}/_ccr/*, `tpu_ccs`/`tpu_ccr` stats
    sections, and the msearch line that targets only dead
    skip_unavailable remotes coming back empty-but-well-formed.
"""

import json

import pytest

from elasticsearch_tpu.cluster.remote import (
    RemoteClusterService, merge_leg_responses,
)
from elasticsearch_tpu.cluster_node import form_local_cluster
from elasticsearch_tpu.common import faults, metrics
from elasticsearch_tpu.common.errors import IllegalArgumentError
from elasticsearch_tpu.common.faults import inject
from elasticsearch_tpu.common.integrity import SegmentCorruptedError
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.parallel.routing import shard_for_id
from elasticsearch_tpu.rest import RestController, register_handlers
from elasticsearch_tpu.transport.channels import (
    LocalNodeChannels, NodeUnavailableError,
)

pytestmark = pytest.mark.distributed


@pytest.fixture(autouse=True)
def _clean():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture()
def two_clusters(tmp_path):
    """A 'follower' 2-node cluster with a 'leader' 3-node cluster
    registered as remote alias `leader` (skip_unavailable=True)."""
    L_nodes, L_store, L_ch = form_local_cluster(
        ["L-m0", "L-d0", "L-d1"], str(tmp_path / "L"))
    F_nodes, F_store, F_ch = form_local_cluster(
        ["F-m0", "F-d0"], str(tmp_path / "F"))
    for n in F_nodes:
        n.remotes.register_remote("leader", L_ch, ["L-d0", "L-d1"],
                                  skip_unavailable=True)
    yield L_nodes, L_ch, F_nodes, F_ch
    for n in L_nodes + F_nodes:
        n.close()


def _seed_leader(L, index="logs", n=20, shards=2, replicas=1):
    L[0].create_index(index, {"settings": {
        "index.number_of_shards": shards,
        "index.number_of_replicas": replicas}})
    for i in range(n):
        L[0].index_doc(index, f"d{i}", {"n": i, "body": f"doc {i}"})
    L[0].refresh(index)


def _seed_local(F, index="local", n=5):
    F[0].create_index(index, {"settings": {
        "index.number_of_shards": 1, "index.number_of_replicas": 0}})
    for i in range(n):
        F[0].index_doc(index, f"l{i}", {"n": 100 + i, "body": f"loc {i}"})
    F[0].refresh(index)


def _read(nodes, index, doc_id):
    """Realtime get through the current primary's engine (the chaos
    harness's authoritative-read idiom)."""
    state = nodes[0].state
    sid = shard_for_id(doc_id, state.indices[index].number_of_shards)
    r = state.primary_of(index, sid)
    owner = next(n for n in nodes if n.node_name == r.node_id)
    hit = owner.shard_service.get_shard(index, sid).engine.get(doc_id)
    return None if hit is None else hit["_source"]


# ------------------------------------------------------------ registry


def test_split_expression_and_unknown_alias():
    svc = RemoteClusterService("n0")
    svc.register_remote("east", LocalNodeChannels(), ["a"])
    local, remote = svc.split_expression("idx1,east:logs-*,idx2,east:more")
    assert local == ["idx1", "idx2"]
    assert remote == {"east": ["logs-*", "more"]}
    with pytest.raises(IllegalArgumentError):
        svc.split_expression("typo:logs")
    with pytest.raises(IllegalArgumentError):
        svc.register_remote("bad:name", LocalNodeChannels(), ["a"])
    with pytest.raises(IllegalArgumentError):
        svc.register_remote("noseeds", LocalNodeChannels(), [])
    assert not svc.has_remote_parts("idx1,idx2")
    assert svc.has_remote_parts("east:logs")


# ------------------------------------------------------------ CCS


def test_ccs_fanout_bit_identical_to_local_merge(two_clusters):
    """A healthy `local,leader:logs` fan-out must agree hit-for-hit with
    the same data merged locally: mirror the leader index into the
    follower cluster and compare (only `_index` carries the alias)."""
    L, _, F, _ = two_clusters
    _seed_leader(L, "logs", n=20)
    _seed_local(F, "local", n=5)
    # mirror of the leader data inside the follower cluster
    F[0].create_index("logs_mirror", {"settings": {
        "index.number_of_shards": 2, "index.number_of_replicas": 0}})
    for i in range(20):
        F[0].index_doc("logs_mirror", f"d{i}", {"n": i, "body": f"doc {i}"})
    F[0].refresh("logs_mirror")

    body = {"query": {"match": {"body": "doc"}}, "size": 30, "from": 0}
    ccs = F[0].search("local,leader:logs", dict(body))
    loc = F[0].search("local,logs_mirror", dict(body))

    assert ccs["_clusters"] == {
        "total": 2, "successful": 2, "skipped": 0, "partial": 0,
        "details": ccs["_clusters"]["details"]}
    assert ccs["hits"]["total"]["value"] == loc["hits"]["total"]["value"]

    def normalize(hits):
        return [(h["_id"], h.get("_score"), h.get("sort"))
                for h in hits]

    assert normalize(ccs["hits"]["hits"]) == normalize(loc["hits"]["hits"])
    # remote hits carry the cluster-qualified index name
    remote_hits = [h for h in ccs["hits"]["hits"]
                   if h["_index"].startswith("leader:")]
    assert len(remote_hits) == 20


def test_ccs_sorted_fanout_agreement(two_clusters):
    L, _, F, _ = two_clusters
    _seed_leader(L, "logs", n=12)
    _seed_local(F, "local", n=6)
    body = {"query": {"match_all": {}}, "size": 10,
            "sort": [{"n": {"order": "desc"}}]}
    r = F[0].search("local,leader:logs", dict(body))
    ns = [h["_source"]["n"] for h in r["hits"]["hits"]]
    assert ns == sorted(ns, reverse=True)
    assert ns[:6] == [105, 104, 103, 102, 101, 100]


def test_ccs_aggs_rejected(two_clusters):
    L, _, F, _ = two_clusters
    _seed_leader(L, "logs", n=3)
    with pytest.raises(IllegalArgumentError):
        F[0].search("leader:logs", {"aggs": {
            "m": {"max": {"field": "n"}}}})


def test_ccs_skip_unavailable_dead_remote_degrades_to_skipped(two_clusters):
    L, L_ch, F, _ = two_clusters
    _seed_leader(L, "logs", n=8)
    _seed_local(F, "local", n=4)
    for name in ("L-d0", "L-d1"):
        L_ch.kill(name)
    r = F[0].search("local,leader:logs", {"query": {"match_all": {}},
                                          "size": 20})
    assert r["hits"]["total"]["value"] == 4     # local leg only
    c = r["_clusters"]
    assert (c["total"], c["successful"], c["skipped"]) == (2, 1, 1)
    assert c["details"]["leader"]["status"] == "skipped"
    # the skipped-cluster counter feeds the tpu_ccs stats section
    assert F[0].remotes.stats()["skipped_clusters"] >= 1


def test_ccs_skipped_remote_is_searched_again_after_heal(two_clusters):
    """A skip_unavailable remote that was skipped while partitioned is a
    full leg of the very next search once its nodes are back: nothing of
    the degradation sticks."""
    L, L_ch, F, _ = two_clusters
    _seed_leader(L, "logs", n=8)
    _seed_local(F, "local", n=4)
    body = {"query": {"match_all": {}}, "size": 20}
    for name in ("L-d0", "L-d1"):
        L_ch.kill(name)
    assert F[0].search("local,leader:logs", dict(body))[
        "_clusters"]["skipped"] == 1
    for name in ("L-d0", "L-d1"):
        L_ch.revive(name)
    r = F[0].search("local,leader:logs", dict(body))
    c = r["_clusters"]
    assert (c["total"], c["successful"], c["skipped"]) == (2, 2, 0)
    assert r["hits"]["total"]["value"] == 12
    assert {h["_id"] for h in r["hits"]["hits"]} == \
        {f"l{i}" for i in range(4)} | {f"d{i}" for i in range(8)}


def test_ccs_dead_remote_without_skip_unavailable_raises(two_clusters):
    L, L_ch, F, _ = two_clusters
    _seed_leader(L, "logs", n=4)
    for n in F:
        n.remotes.register_remote("strict", L_ch, ["L-d0"],
                                  skip_unavailable=False)
    L_ch.kill("L-d0")
    L_ch.kill("L-d1")
    with pytest.raises(NodeUnavailableError):
        F[0].search("strict:logs", {"query": {"match_all": {}}})


def test_ccs_fault_selector_per_cluster_with_retry(two_clusters,
                                                   monkeypatch):
    """`rpc_remote_search#leader:raisex1` kills the first attempt only;
    the budgeted retry (ES_TPU_REMOTE_RETRIES=1 default) rotates to the
    next seed and the fan-out still succeeds."""
    L, _, F, _ = two_clusters
    _seed_leader(L, "logs", n=6)
    monkeypatch.setenv("ES_TPU_REMOTE_BACKOFF_MS", "0")
    before = metrics.counter_values()["ccs_remote_retries"]
    with inject("rpc_remote_search#leader:raisex1"):
        r = F[0].search("leader:logs", {"query": {"match_all": {}},
                                        "size": 10})
    assert r["hits"]["total"]["value"] == 6
    assert r["_clusters"]["successful"] == 1
    assert metrics.counter_values()["ccs_remote_retries"] == before + 1


def test_ccs_fault_exhausted_budget_skips(two_clusters, monkeypatch):
    """Every attempt dies -> a skip_unavailable remote degrades to
    skipped, never an error response."""
    L, _, F, _ = two_clusters
    _seed_leader(L, "logs", n=6)
    _seed_local(F, "local", n=2)
    monkeypatch.setenv("ES_TPU_REMOTE_BACKOFF_MS", "0")
    with inject("rpc_remote_search#leader:raisexinf"):
        r = F[0].search("local,leader:logs",
                        {"query": {"match_all": {}}, "size": 20})
    assert r["hits"]["total"]["value"] == 2
    assert r["_clusters"]["skipped"] == 1


# ------------------------------------------------------------ CCR


def test_ccr_follow_converges_and_stays_idempotent(two_clusters,
                                                   monkeypatch):
    L, _, F, _ = two_clusters
    monkeypatch.setenv("ES_TPU_CCR_POLL_MS", "0")
    _seed_leader(L, "logs", n=15)
    r = F[0].ccr.follow("logs_copy", "leader", "logs")
    assert r["index_following_started"]
    assert F[0].ccr.poll_once() == 15
    F[0].refresh("logs_copy")
    got = F[0].search("logs_copy", {"query": {"match_all": {}},
                                    "size": 50})
    assert got["hits"]["total"]["value"] == 15
    # idempotent: a second poll ships nothing
    assert F[0].ccr.poll_once() == 0
    # incremental: updates + deletes converge too
    L[0].index_doc("logs", "d0", {"n": 999, "body": "updated"})
    L[0].bulk("logs", [{"op": "delete", "id": "d1"}])
    L[0].index_doc("logs", "d99", {"n": 99, "body": "fresh"})
    assert F[0].ccr.poll_once() > 0
    F[0].refresh("logs_copy")
    got = F[0].search("logs_copy", {"query": {"match_all": {}},
                                    "size": 50})
    assert got["hits"]["total"]["value"] == 15  # -1 delete +1 fresh
    assert _read(F, "logs_copy", "d0")["n"] == 999
    # per-shard lag accounting is zero after convergence
    st = F[0].ccr.follower_stats("logs_copy")["indices"][0]
    assert all(s["lag_ops"] == 0 for s in st["shards"])


def test_ccr_pause_resume(two_clusters, monkeypatch):
    L, _, F, _ = two_clusters
    monkeypatch.setenv("ES_TPU_CCR_POLL_MS", "0")
    _seed_leader(L, "logs", n=5)
    F[0].ccr.follow("logs_copy", "leader", "logs")
    F[0].ccr.poll_once()
    F[0].ccr.pause_follow("logs_copy")
    L[0].index_doc("logs", "late", {"n": 1000, "body": "late"})
    assert F[0].ccr.poll_once() == 0        # paused: nothing moves
    F[0].ccr.resume_follow("logs_copy")
    assert F[0].ccr.poll_once() >= 1
    F[0].refresh("logs_copy")
    assert _read(F, "logs_copy", "late")["n"] == 1000


def test_ccr_fetch_hang_times_out_then_recovers(two_clusters,
                                                monkeypatch):
    """`rpc_ccr_fetch#leader:hang` under a 50ms RPC floor surfaces as a
    timeout; the in-request budgeted retry recovers, counting
    ccr_fetch_retries."""
    L, _, F, _ = two_clusters
    monkeypatch.setenv("ES_TPU_CCR_POLL_MS", "0")
    monkeypatch.setenv("ES_TPU_RPC_TIMEOUT_MS", "50")
    monkeypatch.setenv("ES_TPU_REMOTE_BACKOFF_MS", "0")
    _seed_leader(L, "logs", n=8, replicas=0)
    F[0].ccr.follow("logs_copy", "leader", "logs")
    before = metrics.counter_values()["ccr_fetch_retries"]
    with inject("rpc_ccr_fetch#leader:hangx1=0.2"):
        applied = F[0].ccr.poll_once()
    assert applied == 8
    assert metrics.counter_values()["ccr_fetch_retries"] > before
    F[0].refresh("logs_copy")
    got = F[0].search("logs_copy", {"query": {"match_all": {}},
                                    "size": 20})
    assert got["hits"]["total"]["value"] == 8


def test_ccr_leader_down_poll_survives_then_catches_up(two_clusters,
                                                       monkeypatch):
    L, L_ch, F, _ = two_clusters
    monkeypatch.setenv("ES_TPU_CCR_POLL_MS", "0")
    monkeypatch.setenv("ES_TPU_REMOTE_BACKOFF_MS", "0")
    _seed_leader(L, "logs", n=6)
    F[0].ccr.follow("logs_copy", "leader", "logs")
    F[0].ccr.poll_once()
    for name in ("L-d0", "L-d1"):
        L_ch.kill(name)
    # leader gone: the poll records the error and returns, no raise
    assert F[0].ccr.poll_once() == 0
    st = F[0].ccr.follower_stats("logs_copy")["indices"][0]
    assert "last_error" in st
    for name in ("L-d0", "L-d1"):
        L_ch.revive(name)
    L[0].index_doc("logs", "post", {"n": 7, "body": "post-heal"})
    assert F[0].ccr.poll_once() >= 1
    F[0].refresh("logs_copy")
    assert _read(F, "logs_copy", "post")["n"] == 7


def test_ccr_checksum_mismatch_bounded_refetch(two_clusters,
                                               monkeypatch):
    """Wire corruption (`segment_transfer#leader`, fired follower-side
    on a COPY of the batch) fails sha256 verification and re-fetches,
    bounded by ES_TPU_REMOTE_RETRIES; persistent rot raises
    SegmentCorruptedError without poisoning the follower."""
    L, _, F, _ = two_clusters
    monkeypatch.setenv("ES_TPU_CCR_POLL_MS", "0")
    _seed_leader(L, "logs", n=10, shards=1, replicas=0)
    F[0].ccr.follow("logs_copy", "leader", "logs")
    before = metrics.counter_values()["ccr_checksum_mismatches"]
    # one corrupted transfer, then clean: the bounded re-fetch recovers
    with inject("segment_transfer#leader:raisex1"):
        assert F[0].ccr.poll_once() == 10
    assert metrics.counter_values()["ccr_checksum_mismatches"] == before + 1
    F[0].refresh("logs_copy")
    got = F[0].search("logs_copy", {"query": {"match_all": {}},
                                    "size": 20})
    assert got["hits"]["total"]["value"] == 10
    # persistent rot: every fetch+retry corrupted -> bounded error;
    # nothing half-applied on the follower
    L[0].index_doc("logs", "rot", {"n": -1, "body": "rot"})
    with inject("segment_transfer#leader:raisexinf"):
        assert F[0].ccr.poll_once() == 0
    st = F[0].ccr.follower_stats("logs_copy")["indices"][0]
    assert "SegmentCorruptedError" in st.get("last_error", "")
    assert _read(F, "logs_copy", "rot") is None
    # heal: the same ops land on the next clean poll
    assert F[0].ccr.poll_once() == 1


def test_ccr_follow_unknown_remote_or_index(two_clusters):
    L, _, F, _ = two_clusters
    _seed_leader(L, "logs", n=2)
    with pytest.raises(IllegalArgumentError):
        F[0].ccr.follow("x", "nope", "logs")
    from elasticsearch_tpu.common.errors import IndexNotFoundError

    with pytest.raises(IndexNotFoundError):
        F[0].ccr.follow("x", "leader", "missing")
    with pytest.raises(IndexNotFoundError):
        F[0].ccr.pause_follow("never_followed")


# ------------------------------------------------------------ stats / info


def test_remote_info_probes_liveness(two_clusters):
    L, L_ch, F, _ = two_clusters
    info = F[0].remotes.remote_info()
    assert info["leader"]["connected"]
    assert info["leader"]["num_nodes_connected"] == 2
    assert info["leader"]["skip_unavailable"] is True
    L_ch.kill("L-d0")
    L_ch.kill("L-d1")
    info = F[0].remotes.remote_info()
    assert not info["leader"]["connected"]
    assert info["leader"]["num_nodes_connected"] == 0


def test_tpu_ccs_stats_edges_and_circuits(two_clusters, monkeypatch):
    L, L_ch, F, _ = two_clusters
    _seed_leader(L, "logs", n=3)
    monkeypatch.setenv("ES_TPU_REMOTE_BACKOFF_MS", "0")
    F[0].search("leader:logs", {"query": {"match_all": {}}})
    st = F[0].remotes.stats()
    assert st["remote_clusters"] == ["leader"]
    assert st["remote_searches"] >= 1
    assert any(e["name"].startswith("leader:") for e in st["edges"])


# ------------------------------------------------------------ REST layer


@pytest.fixture()
def rest_pair(tmp_path):
    """A standalone REST node with a second standalone node registered
    as remote `east` over a private LocalNodeChannels."""
    local = Node(node_name="rest-local")
    east = Node(node_name="east-0")
    ch = LocalNodeChannels()
    ch.register("east-0", east.transport)
    local.remotes.register_remote("east", ch, ["east-0"],
                                  skip_unavailable=True)
    rc = RestController()
    register_handlers(local, rc)

    def call(method, path, body=None, params=None):
        if isinstance(body, (dict, list)):
            body = json.dumps(body)
        return rc.dispatch(method, path, params or {}, body)

    yield call, local, east, ch
    local.close()
    east.close()


def test_rest_ccs_search_and_remote_info(rest_pair):
    call, local, east, ch = rest_pair
    east.create_index("logs", {"settings": {"number_of_shards": 1}})
    east.indices.get("logs").index_doc("e1", {"n": 1, "body": "hello"})
    east.indices.get("logs").refresh()
    call("PUT", "/home", {"settings": {"number_of_shards": 1}})
    call("PUT", "/home/_doc/h1", {"n": 2, "body": "hello"})
    call("POST", "/home/_refresh")
    r = call("POST", "/home,east:logs/_search",
             {"query": {"match": {"body": "hello"}}, "size": 10})
    assert r.status == 200
    assert r.body["hits"]["total"]["value"] == 2
    assert r.body["_clusters"]["successful"] == 2
    assert {h["_index"] for h in r.body["hits"]["hits"]} \
        == {"home", "east:logs"}
    info = call("GET", "/_remote/info")
    assert info.status == 200 and info.body["east"]["connected"]


def test_rest_msearch_dead_remote_line_well_formed(rest_pair):
    """The satellite fix: an msearch line whose expression targets only
    dead skip_unavailable remotes returns an EMPTY well-formed response
    with `_clusters.skipped` counted — not a shard-failure/error entry,
    and it must not poison sibling lines."""
    call, local, east, ch = rest_pair
    call("PUT", "/home", {"settings": {"number_of_shards": 1}})
    call("PUT", "/home/_doc/h1", {"n": 2, "body": "hi"})
    call("POST", "/home/_refresh")
    ch.kill("east-0")
    payload = (json.dumps({"index": "east:logs"}) + "\n"
               + json.dumps({"query": {"match_all": {}}}) + "\n"
               + json.dumps({"index": "home"}) + "\n"
               + json.dumps({"query": {"match_all": {}}}) + "\n")
    r = call("POST", "/_msearch", payload)
    assert r.status == 200
    dead, alive = r.body["responses"]
    assert "error" not in dead
    assert dead["status"] == 200
    assert dead["hits"]["total"]["value"] == 0
    assert dead["hits"]["hits"] == []
    assert dead["_clusters"]["skipped"] == 1
    assert alive["hits"]["total"]["value"] == 1


def test_rest_ccr_endpoints_and_stats_sections(rest_pair, monkeypatch):
    call, local, east, ch = rest_pair
    monkeypatch.setenv("ES_TPU_CCR_POLL_MS", "0")
    east.create_index("logs", {"settings": {"number_of_shards": 1}})
    for i in range(4):
        east.indices.get("logs").index_doc(f"e{i}", {"n": i})
    r = call("PUT", "/logs_copy/_ccr/follow",
             {"remote_cluster": "east", "leader_index": "logs"})
    assert r.status == 200 and r.body["index_following_started"]
    assert call("PUT", "/nocluster/_ccr/follow",
                {"leader_index": "logs"}).status == 400
    local.ccr.poll_once()
    r = call("GET", "/logs_copy/_ccr/stats")
    assert r.status == 200
    shard = r.body["indices"][0]["shards"][0]
    assert shard["follower_checkpoint"] == 3 and shard["lag_ops"] == 0
    assert call("POST", "/logs_copy/_ccr/pause_follow").body["acknowledged"]
    assert call("POST", "/logs_copy/_ccr/resume_follow").body["acknowledged"]
    stats = call("GET", "/_nodes/stats")
    node_stats = next(iter(stats.body["nodes"].values()))
    assert "tpu_ccs" in node_stats and "tpu_ccr" in node_stats
    assert node_stats["tpu_ccr"]["followers"][0]["index"] == "logs_copy"
    assert node_stats["tpu_ccs"]["remote_clusters"] == ["east"]


# ------------------------------------------------------------ merge unit


def test_merge_leg_responses_prefixes_and_slices():
    def leg(idx, scores):
        return {"took": 1, "timed_out": False,
                "_shards": {"total": 1, "successful": 1, "skipped": 0,
                            "failed": 0},
                "hits": {"total": {"value": len(scores), "relation": "eq"},
                         "max_score": max(scores),
                         "hits": [{"_index": idx, "_id": f"{idx}{i}",
                                   "_score": s}
                                  for i, s in enumerate(scores)]}}

    merged = merge_leg_responses(
        [(None, leg("a", [3.0, 1.0])), ("r", leg("b", [2.0]))],
        from_=0, size=2)
    assert [h["_id"] for h in merged["hits"]["hits"]] == ["a0", "b0"]
    assert merged["hits"]["hits"][1]["_index"] == "r:b"
    assert merged["hits"]["total"]["value"] == 3
    # pagination slices AFTER the global merge
    page2 = merge_leg_responses(
        [(None, leg("a", [3.0, 1.0])), ("r", leg("b", [2.0]))],
        from_=2, size=2)
    assert [h["_id"] for h in page2["hits"]["hits"]] == ["a1"]
