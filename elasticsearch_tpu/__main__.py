"""`python -m elasticsearch_tpu` — start a single node with the HTTP frontend.

The analog of the reference's bin/elasticsearch -> Elasticsearch.main ->
Bootstrap.init -> Node.start (ref: bootstrap/Elasticsearch.java:64,
bootstrap/Bootstrap.java:327).
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading


def start_node(host: str = "127.0.0.1", port: int = 9200, data=None,
               name: str = "node-0",
               cluster_name: str = "elasticsearch-tpu"):
    """Wire one node to its HTTP frontend and start serving; port 0 binds
    a free port (read it back from server.port). Returns (node, server) —
    the caller owns shutdown (server.stop(), node.close())."""
    from elasticsearch_tpu.common import hbm_ledger, tracing
    from elasticsearch_tpu.common.compile_cache import configure_compile_cache
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.rest import HttpServer, RestController, register_handlers

    configure_compile_cache()
    # what the program says of itself beyond its own spans (both
    # idempotent): the programs JAX builds (`tpu_compile.jit_builds`, ring
    # entries by fun_name and open span) and the interpreter's collector
    # (`jvm.gc.collectors`)
    hbm_ledger.install_jit_listener()
    tracing.install_gc_hook()
    node = Node(Settings({"cluster.name": cluster_name}),
                data_path=data, node_name=name)
    rc = RestController()
    register_handlers(node, rc)
    from elasticsearch_tpu.plugins import load_plugins

    loaded = load_plugins(node, rc)
    if loaded:
        print(f"[{name}] plugins loaded: {', '.join(loaded)}", flush=True)
    server = HttpServer(rc, host=host, port=port,
                        thread_pool=node.thread_pool)
    server.start()
    print(f"[{name}] started, http on {host}:{server.port}", flush=True)
    return node, server


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="elasticsearch-tpu")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=9200)
    ap.add_argument("--data", default=None, help="data path (translog/commits); in-memory if unset")
    ap.add_argument("--name", default="node-0")
    ap.add_argument("--cluster-name", default="elasticsearch-tpu")
    args = ap.parse_args(argv)

    node, server = start_node(args.host, args.port, data=args.data,
                              name=args.name, cluster_name=args.cluster_name)

    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    stop.wait()
    server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
