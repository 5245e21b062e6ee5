"""REST API conformance tests (in-process dispatch + one real-HTTP smoke)."""

import json

import pytest

from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest import HttpServer, RestController, register_handlers


@pytest.fixture()
def api():
    node = Node()
    rc = RestController()
    register_handlers(node, rc)

    def call(method, path, body=None, params=None):
        if isinstance(body, (dict, list)):
            body = json.dumps(body)
        return rc.dispatch(method, path, params or {}, body)

    yield call, node
    node.close()


def test_root_info(api):
    call, _ = api
    r = call("GET", "/")
    assert r.status == 200
    assert r.body["tagline"] == "You Know, for Search"
    assert r.body["version"]["build_flavor"] == "tpu"


def test_index_crud(api):
    call, _ = api
    r = call("PUT", "/books", {"settings": {"number_of_shards": 2},
                               "mappings": {"properties": {"title": {"type": "text"}}}})
    assert r.status == 200 and r.body["acknowledged"]
    assert call("HEAD", "/books").status == 200
    assert call("HEAD", "/missing").status == 404
    r = call("GET", "/books")
    assert r.body["books"]["mappings"]["properties"]["title"]["type"] == "text"
    assert r.body["books"]["settings"]["index"]["number_of_shards"] == "2"
    r = call("PUT", "/books")
    assert r.status == 400  # already exists
    assert "resource_already_exists_exception" in json.dumps(r.body)
    assert call("DELETE", "/books").body["acknowledged"]
    assert call("HEAD", "/books").status == 404
    assert call("DELETE", "/missing").status == 404


def test_doc_crud_and_versioning(api):
    call, _ = api
    r = call("PUT", "/idx/_doc/1", {"title": "hello"})
    assert r.status == 201 and r.body["result"] == "created" and r.body["_version"] == 1
    r = call("PUT", "/idx/_doc/1", {"title": "hello again"})
    assert r.status == 200 and r.body["result"] == "updated" and r.body["_version"] == 2
    r = call("GET", "/idx/_doc/1")
    assert r.body["found"] and r.body["_source"]["title"] == "hello again"
    assert call("GET", "/idx/_source/1").body == {"title": "hello again"}
    assert call("HEAD", "/idx/_doc/1").status == 200
    r = call("PUT", "/idx/_create/1", {"title": "nope"})
    assert r.status == 409
    r = call("DELETE", "/idx/_doc/1")
    assert r.status == 200 and r.body["result"] == "deleted"
    assert call("GET", "/idx/_doc/1").status == 404
    # optimistic concurrency via url params
    r = call("PUT", "/idx/_doc/2", {"n": 1})
    seq = r.body["_seq_no"]
    r = call("PUT", "/idx/_doc/2", {"n": 2}, params={"if_seq_no": str(seq + 5), "if_primary_term": "1"})
    assert r.status == 409
    r = call("PUT", "/idx/_doc/2", {"n": 2}, params={"if_seq_no": str(seq), "if_primary_term": "1"})
    assert r.status == 200


def test_auto_id_and_update(api):
    call, _ = api
    r = call("POST", "/idx/_doc", {"x": 1})
    assert r.status == 201 and len(r.body["_id"]) > 0
    doc_id = r.body["_id"]
    r = call("POST", f"/idx/_update/{doc_id}", {"doc": {"y": 2}})
    assert r.body["result"] == "updated"
    src = call("GET", f"/idx/_doc/{doc_id}").body["_source"]
    assert src == {"x": 1, "y": 2}
    # noop detection
    r = call("POST", f"/idx/_update/{doc_id}", {"doc": {"y": 2}})
    assert r.body["result"] == "noop"
    # upsert on missing
    r = call("POST", "/idx/_update/zzz", {"doc": {"a": 1}, "doc_as_upsert": True})
    assert r.body["result"] == "created"
    r = call("POST", "/idx/_update/missing2", {"doc": {"a": 1}})
    assert r.status == 404


def test_bulk_and_search_flow(api):
    call, _ = api
    bulk = "\n".join([
        json.dumps({"index": {"_index": "lib", "_id": "1"}}),
        json.dumps({"title": "the quick brown fox", "year": 2001}),
        json.dumps({"index": {"_index": "lib", "_id": "2"}}),
        json.dumps({"title": "lazy dogs sleep", "year": 2005}),
        json.dumps({"create": {"_index": "lib", "_id": "3"}}),
        json.dumps({"title": "quick quick fox fox", "year": 2010}),
        json.dumps({"delete": {"_index": "lib", "_id": "2"}}),
        json.dumps({"update": {"_index": "lib", "_id": "1"}}),
        json.dumps({"doc": {"year": 2002}}),
    ]) + "\n"
    r = call("POST", "/_bulk", bulk, params={"refresh": "true"})
    assert r.status == 200
    assert not r.body["errors"]
    ops = [next(iter(item)) for item in r.body["items"]]
    assert ops == ["index", "index", "create", "delete", "update"]

    r = call("GET", "/lib/_search", {"query": {"match": {"title": "quick fox"}}})
    hits = r.body["hits"]["hits"]
    assert [h["_id"] for h in hits] == ["3", "1"]
    assert r.body["hits"]["total"]["value"] == 2

    r = call("GET", "/lib/_count")
    assert r.body["count"] == 2

    # bulk with error item
    bulk_err = "\n".join([
        json.dumps({"create": {"_index": "lib", "_id": "1"}}),
        json.dumps({"title": "dup"}),
    ]) + "\n"
    r = call("POST", "/_bulk", bulk_err)
    assert r.body["errors"] is True
    assert r.body["items"][0]["create"]["status"] == 409


def test_msearch(api):
    call, _ = api
    call("PUT", "/a/_doc/1", {"t": "alpha"}, params={"refresh": "true"})
    call("PUT", "/b/_doc/1", {"t": "beta"}, params={"refresh": "true"})
    body = "\n".join([
        json.dumps({"index": "a"}),
        json.dumps({"query": {"match_all": {}}}),
        json.dumps({"index": "b"}),
        json.dumps({"query": {"match": {"t": "beta"}}}),
        json.dumps({"index": "missing"}),
        json.dumps({"query": {"match_all": {}}}),
    ]) + "\n"
    r = call("POST", "/_msearch", body)
    rs = r.body["responses"]
    assert rs[0]["hits"]["total"]["value"] == 1
    assert rs[1]["hits"]["hits"][0]["_id"] == "1"
    assert rs[2]["status"] == 404


@pytest.mark.parametrize("where", ["url", "header"])
def test_msearch_passes_search_type_on(api, where):
    """A multi-shard index scores with global stats only under dfs; the
    search_type of the URL or of a header line must reach the index."""
    call, node = api
    call("PUT", "/two", {"settings": {"number_of_shards": 2}})
    seen = []
    svc = node.indices.get("two")
    real = svc.msearch
    svc.msearch = lambda reqs, st="query_then_fetch": (
        seen.append(st), real(reqs, st))[1]
    header = {"index": "two"}
    params = {}
    if where == "url":
        params["search_type"] = "dfs_query_then_fetch"
    else:
        header["search_type"] = "dfs_query_then_fetch"
    body = json.dumps(header) + "\n" + json.dumps(
        {"query": {"match_all": {}}}) + "\n"
    r = call("POST", "/_msearch", body, params=params)
    assert r.body["responses"][0]["status"] == 200
    assert seen == ["dfs_query_then_fetch"]


def test_multi_index_and_wildcard_search(api):
    call, _ = api
    call("PUT", "/logs-1/_doc/1", {"msg": "error one"}, params={"refresh": "true"})
    call("PUT", "/logs-2/_doc/2", {"msg": "error two"}, params={"refresh": "true"})
    r = call("GET", "/logs-*/_search", {"query": {"match": {"msg": "error"}}})
    assert r.body["hits"]["total"]["value"] == 2
    r = call("GET", "/_search", {"query": {"match_all": {}}})
    assert r.body["hits"]["total"]["value"] >= 2
    r = call("GET", "/_cat/indices")
    assert "logs-1" in r.body


def test_aliases(api):
    call, _ = api
    call("PUT", "/idx-v1/_doc/1", {"x": 1}, params={"refresh": "true"})
    r = call("POST", "/_aliases", {"actions": [{"add": {"index": "idx-v1", "alias": "current"}}]})
    assert r.body["acknowledged"]
    r = call("GET", "/current/_search", {"query": {"match_all": {}}})
    assert r.body["hits"]["total"]["value"] == 1
    r = call("GET", "/idx-v1/_alias")
    assert "current" in r.body["idx-v1"]["aliases"]
    call("POST", "/_aliases", {"actions": [{"remove": {"index": "idx-v1", "alias": "current"}}]})
    r = call("GET", "/current/_search", {"query": {"match_all": {}}})
    assert r.status == 404


def test_delete_by_query(api):
    call, _ = api
    for i in range(6):
        call("PUT", f"/dbq/_doc/{i}", {"n": i})
    call("POST", "/dbq/_refresh")
    r = call("POST", "/dbq/_delete_by_query", {"query": {"range": {"n": {"gte": 3}}}})
    assert r.body["deleted"] == 3
    assert call("GET", "/dbq/_count").body["count"] == 3


def test_analyze(api):
    call, _ = api
    r = call("POST", "/_analyze", {"analyzer": "standard", "text": "The Quick Fox"})
    assert [t["token"] for t in r.body["tokens"]] == ["the", "quick", "fox"]
    assert r.body["tokens"][1]["position"] == 1


def test_cluster_apis(api):
    call, node = api
    call("PUT", "/x", {"settings": {"number_of_shards": 1, "number_of_replicas": 0}})
    r = call("GET", "/_cluster/health")
    assert r.body["status"] in ("green", "yellow")
    assert r.body["number_of_nodes"] == 1
    r = call("GET", "/_cluster/state")
    assert "x" in r.body["metadata"]["indices"]
    r = call("GET", "/_nodes")
    assert r.body["_nodes"]["total"] == 1
    r = call("GET", "/_nodes/stats")
    assert "breakers" in r.body["nodes"][node.node_id]
    r = call("GET", "/_cat/health")
    assert "elasticsearch-tpu" in r.body
    r = call("GET", "/_cat/shards")
    assert "x 0 p STARTED" in r.body


def test_sharded_index_via_rest(api):
    call, _ = api
    call("PUT", "/big", {"settings": {"number_of_shards": 3, "number_of_replicas": 0}})
    for i in range(30):
        call("PUT", f"/big/_doc/{i}", {"body": f"word{i % 5} filler"})
    call("POST", "/big/_refresh")
    r = call("GET", "/big/_count")
    assert r.body["count"] == 30
    r = call("GET", "/big/_search", {"query": {"match": {"body": "word3"}}, "size": 20})
    assert r.body["hits"]["total"]["value"] == 6
    assert r.body["_shards"]["total"] == 3
    r = call("GET", "/big/_stats")
    assert r.body["_all"]["primaries"]["docs"]["count"] == 30


def test_error_shapes(api):
    call, _ = api
    r = call("GET", "/missing/_search", {"query": {"match_all": {}}})
    assert r.status == 404
    assert r.body["error"]["type"] == "index_not_found_exception"
    call("PUT", "/e/_doc/1", {"a": 1}, params={"refresh": "true"})
    r = call("GET", "/e/_search", {"query": {"bad_query": {}}})
    assert r.status == 400
    assert r.body["error"]["type"] == "parsing_exception"


def test_real_http_roundtrip():
    import urllib.request

    node = Node()
    rc = RestController()
    register_handlers(node, rc)
    server = HttpServer(rc, port=0)
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        def http(method, path, body=None):
            data = json.dumps(body).encode() if body is not None else None
            req = urllib.request.Request(base + path, data=data, method=method,
                                         headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req) as resp:
                    return resp.status, json.loads(resp.read() or b"{}")
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read() or b"{}")

        status, body = http("GET", "/")
        assert status == 200 and body["tagline"] == "You Know, for Search"
        status, body = http("PUT", "/h/_doc/1?refresh=true", {"t": "hello http"})
        assert status == 201
        status, body = http("POST", "/h/_search", {"query": {"match": {"t": "hello"}}})
        assert body["hits"]["total"]["value"] == 1
        status, _ = http("GET", "/nope/_doc/1")
        assert status == 404
    finally:
        server.stop()
        node.close()


def test_index_blocks_read_and_metadata_enforced(api):
    """index.blocks.read gates data reads, index.blocks.metadata gates
    mapping/settings access — and a metadata-blocked index must still
    accept a blocks-only settings update so the block can be lifted
    (ref: TransportUpdateSettingsAction.checkBlock)."""
    call, _ = api
    assert call("PUT", "/b", {"mappings": {
        "properties": {"t": {"type": "text"}}}}).status == 200
    assert call("PUT", "/b/_doc/1", {"t": "hello world"}).status == 201
    call("POST", "/b/_refresh")

    assert call("PUT", "/b/_settings",
                {"index.blocks.read": True}).status == 200
    for method, path, body in [
            ("GET", "/b/_doc/1", None),
            ("POST", "/b/_search", {"query": {"match_all": {}}}),
            ("POST", "/b/_count", None),
            ("POST", "/b/_mget", {"ids": ["1"]})]:
        r = call(method, path, body)
        assert r.status == 403, (method, path, r.body)
        assert "cluster_block_exception" in json.dumps(r.body)
    # a read block does NOT gate writes
    assert call("PUT", "/b/_doc/2", {"t": "two"}).status == 201
    assert call("PUT", "/b/_settings",
                {"index.blocks.read": False}).status == 200
    assert call("GET", "/b/_doc/1").status == 200

    assert call("PUT", "/b/_settings",
                {"index.blocks.metadata": True}).status == 200
    assert call("GET", "/b/_mapping").status == 403
    assert call("GET", "/b/_settings").status == 403
    assert call("PUT", "/b/_mapping",
                {"properties": {"x": {"type": "keyword"}}}).status == 403
    # non-block settings updates are refused while metadata-blocked...
    assert call("PUT", "/b/_settings",
                {"index.refresh_interval": "1s"}).status == 403
    # ...but the block itself can always be lifted
    assert call("PUT", "/b/_settings",
                {"index.blocks.metadata": False}).status == 200
    assert call("GET", "/b/_mapping").status == 200
