"""A tiny copy of the benchmark's data files, for the CPU tests: the
manifest and every metric and traffic file as committed, the two
configurations cut to a few thousand documents. The code under test is
the package's own; only the DATA root moves.

Beside the accepted cells the tiny root holds the cell PR 29 built the
harness for and then held back (PERF.md section 7: no cut of the v5e-8's
four-chip share fits a run's 360 s until the program builds its partition
engines side by side): `FOUR`, four shards over a 4-device mesh under a
closed `_msearch` loop. It is added the way a later PR will add it, as
new files and new manifest entries (`add_four_shard_cell`), so these tests
also hold the harness to taking shards, chips, path parameters and the
loop from data."""

import json
import os
import shutil

from benchmark.manifest import ROOT, Manifest

TINY_DOCS = {"text": 6000, "vectors": 3000}
TINY_VOCAB = 5000
FOUR = "msmarco-bm25-4shard.msearch-closed"
CELLS = tuple(Manifest(ROOT).cell_names()) + (FOUR,)
# what the held cell reads beside the accepted bm25 cell's own metrics
FOUR_METRICS = {
    "queries_per_s.search": ("queries/s", "higher", "host_clock",
                             "load generator"),
    "merge_device_pct.search": ("%", "higher", "program_counter", "engines"),
    "merge_topk_ms.search": ("ms", "lower", "device_trace", "kernels"),
    "chip_busy_skew.search": ("ratio", "lower", "device_trace", "device"),
}


def add_four_shard_cell(root: str, doc: dict) -> None:
    """The held cell as files and entries: the accepted bm25 configuration
    with `index.shards` 4 (one segment each), the path parameter a
    multi-shard index needs to reach the device, and what must and must
    not have moved; `search-open`'s request under a closed loop of one
    client; four metrics of its own. The three bm25 metrics that carry a
    list take the cell into it."""
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "msmarco-passage-bm25.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "msmarco-passage-bm25-4shard"
    cfg["index"].update(shards=4, segments=1, search_params={
        "search_type": "dfs_query_then_fetch"})
    cfg["must_rise"] = ["tpu_turbo.fused_dispatches", "tpu_turbo.merge_device"]
    cfg["must_stay"] = ["tpu_turbo.merge_host"]
    path = "benchmark/configs/msmarco-passage-bm25-4shard.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic", "search-open.json")) as f:
        open_mix = json.load(f)
    with open(os.path.join(bdir, "traffic", "msearch-closed.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 1, "timeout_s": 120,
                   "plan_seed": open_mix["plan_seed"],
                   "request": dict(open_mix["request"], batch=256),
                   "warmup": {"calls": 6}}, f)
    doc["configs"].append({
        "name": cfg["name"], "source": cfg["source"], "file": path,
        "reduced": cfg["reduced"], "why": "four shards, one a chip"})
    doc["workloads"].append({
        "name": FOUR, "config": cfg["name"], "traffic": "msearch-closed",
        "chips": 4, "why": "closed loop, 1 client, _msearch of 256"})
    for m in doc["per_layer"]:
        if "msmarco-bm25.search-open" in m.get("workloads", ()):
            m["workloads"].append(FOUR)
    doc["per_layer"] += [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": "search_p50_ms", "workloads": [FOUR]}
        for name, (unit, better, source, layer) in FOUR_METRICS.items()]


def tiny_root(tmp: str) -> str:
    os.makedirs(os.path.join(tmp, "benchmark", "configs"))
    for d in ("metrics", "traffic"):
        shutil.copytree(os.path.join(ROOT, "benchmark", d),
                        os.path.join(tmp, "benchmark", d))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    add_four_shard_cell(tmp, doc)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    for c in doc["configs"]:
        added = os.path.join(tmp, c["file"])
        with open(added if os.path.exists(added)
                  else os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        cfg["corpus"]["docs"] = TINY_DOCS[cfg["kind"]]
        if cfg["kind"] == "text":
            cfg["corpus"]["vocab"] = TINY_VOCAB
        with open(os.path.join(tmp, c["file"]), "w") as f:
            json.dump(cfg, f)
    tdir = os.path.join(tmp, "benchmark", "traffic")
    for name in os.listdir(tdir):
        with open(os.path.join(tdir, name)) as f:
            t = json.load(f)
        # a test worker shares its cores with five others: few client
        # threads, a short prefill
        warm = t.setdefault("warmup", {})
        if t.get("loop") == "closed":
            # still over the scheduler's SMALL_BATCH_MAX of 8: the batch
            # skips the lane, as the cell's 256 do
            t["request"]["batch"] = 32
            warm["calls"] = 1
        else:
            t["connections"] = 4
            warm["buckets"] = [1, 4]
        if warm.get("prefill"):
            warm["prefill"] = 24
        if warm.get("lead_in_s"):
            warm["lead_in_s"] = 0.5
        with open(os.path.join(tdir, name), "w") as f:
            json.dump(t, f)
    return tmp


def tiny_manifest(tmp: str) -> Manifest:
    return Manifest(tiny_root(tmp))


def steer_engines(mp, cache_dir: str) -> None:
    """The test knobs that stand in for the TPU backend gate and the real
    thresholds (tiny segments reach none of them), as in
    tests/test_chip_smoke.py: steered here, not by an option of the
    benchmark."""
    mp.setenv("ES_TPU_FORCE_TURBO", "1")
    mp.setenv("ES_TPU_FORCE_KNN", "1")
    mp.setenv("ES_TPU_TURBO_COLD_DF", "32")
    # the four-chip cell's mesh, on the CPU's virtual devices
    mp.setenv("ES_TPU_TURBO_MESH", "4")
    mp.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
