"""A tiny copy of the benchmark's data files, for the CPU tests: the
manifest, every metric and traffic file and every kind as committed, each
configuration's corpus cut to the size its corpus kind names (`TINY`). The
code under test is the package's own; only the DATA root moves.

The tests take their cells from the manifest: `CELLS` is what
BENCHMARK.json holds, and what a cell is expected to print follows from
its data (`is_bm25` / `is_knn`: the configuration's `device_counter`;
`loop_of`: the traffic's). A cell's recorded trace is
benchmark/testdata/trace_<cell>.json (`recorded`): the PR that adds a
cell adds that file.

`FOUR` is the manifest's four-chip cell since PR 37 (PR 29 built the
harness for it and held it back; until it landed the tiny root added it
as new files and entries): four shards over a 4-device mesh under a
closed `_msearch` loop, so these tests hold the harness to taking shards,
chips, path parameters and the loop from data. `four_shard_config` is the
recipe its committed configuration file is held to
(test_bench_shards.py)."""

import json
import os
import shutil

from benchmark.manifest import ROOT, Cell, Manifest, load_kind

FOUR = "msmarco-bm25-4shard.msearch-closed"
FOUR_CONFIG = "msmarco-passage-bm25-4shard"
TWIN = "msmarco-bm25.msearch-closed"     # its one-chip twin, in the manifest
REAL = Manifest(ROOT)
CELLS = tuple(REAL.cell_names())
# `peak_bytes_in_use` a chip, as the four-chip cell's traced run read them
# (my chip call C, PR 37; PERF.md section 5): the CPU backend reports none,
# and a traced line of that cell holds the spread of them (`steer_engines`)
RECORDED_PEAKS = (8344152576, 1939730432, 1939730432, 1939730432)


def recorded(cell: str) -> str:
    """The small trace recorded on the chip in `cell`'s traced run."""
    return os.path.join(ROOT, "benchmark", "testdata", f"trace_{cell}.json")


def _read(root: str, *path: str) -> dict:
    with open(os.path.join(root, *path)) as f:
        return json.load(f)


def _write(doc: dict, root: str, *path: str) -> None:
    with open(os.path.join(root, *path), "w") as f:
        json.dump(doc, f)


def four_shard_config() -> dict:
    """The accepted bm25 configuration with `index.shards` 4 (one segment
    each), the path parameter a multi-shard index needs to reach the
    device, and what must and must not have moved: all of the committed
    four-shard file but its size, its cuts and the words about them."""
    cfg = _read(ROOT, "benchmark", "configs", "msmarco-passage-bm25.json")
    cfg["name"] = FOUR_CONFIG
    cfg["index"].update(shards=4, segments=1, search_params={
        "search_type": "dfs_query_then_fetch"})
    cfg["must_rise"] = ["tpu_turbo.fused_dispatches", "tpu_turbo.merge_device"]
    cfg["must_stay"] = ["tpu_turbo.merge_host"]
    return cfg


def tiny_root(tmp: str) -> str:
    os.makedirs(os.path.join(tmp, "benchmark", "configs"))
    for d in ("metrics", "traffic", "kinds"):
        shutil.copytree(os.path.join(ROOT, "benchmark", d),
                        os.path.join(tmp, "benchmark", d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    doc = _read(ROOT, "BENCHMARK.json")
    _write(doc, tmp, "BENCHMARK.json")
    for c in doc["configs"]:
        cfg = _read(ROOT, c["file"])
        cfg["corpus"].update(load_kind(
            os.path.join(tmp, "benchmark"), "corpus", cfg["kind"]).TINY)
        _write(cfg, tmp, c["file"])
    tdir = os.path.join(tmp, "benchmark", "traffic")
    for name in os.listdir(tdir):
        t = _read(tdir, name)
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
        _write(t, tdir, name)
    return tmp


def tiny_manifest(tmp: str) -> Manifest:
    return Manifest(tiny_root(tmp))


# What a PR that changes the program may do to the manifest: APPEND a cell
# to `workloads` and an entry to `per_layer`, edit nothing. The tests that
# hold an accepted cell's place take the manifest through conftest.py's
# `grown` fixture, as committed and with these two appended, and pass in
# both: a cell 7 meets no test that counts cells or holds a last place.
APPENDED = "appended.search-open"
APPENDED_MIX = "appended-search-open"
APPENDED_METRIC = "batch_queries.appended"


def _linked(src: str, dst: str) -> None:
    """`dst`, a directory of links to `src`'s entries: one can be added."""
    os.makedirs(dst)
    for name in os.listdir(src):
        os.symlink(os.path.join(src, name), os.path.join(dst, name))


def appended_root(tmp: str) -> str:
    """The committed data root (full size: links, nothing copied but the
    manifest) with one more cell appended to `workloads`, an accepted
    configuration under an accepted mix (its file under a name of the
    cell's own, so the pair meets no cell that lands later), and one more
    `per_layer` entry that lists only it, with its metric file."""
    src, bdir = os.path.join(ROOT, "benchmark"), os.path.join(tmp, "benchmark")
    os.makedirs(os.path.join(tmp, "tests"))
    os.symlink(os.path.join(ROOT, "tests", "bench_harness"),
               os.path.join(tmp, "tests", "bench_harness"))
    for d in ("metrics", "traffic"):
        _linked(os.path.join(src, d), os.path.join(bdir, d))
    for d in ("configs", "kinds", "testdata"):
        os.symlink(os.path.join(src, d), os.path.join(bdir, d))
    shutil.copy(os.path.join(src, "traffic", "search-open.json"),
                os.path.join(bdir, "traffic", APPENDED_MIX + ".json"))
    shutil.copy(os.path.join(src, "metrics", "batch_queries.bool.json"),
                os.path.join(bdir, "metrics", APPENDED_METRIC + ".json"))
    doc = _read(ROOT, "BENCHMARK.json")
    doc["workloads"].append({
        "name": APPENDED, "config": "msmarco-passage-bm25",
        "traffic": APPENDED_MIX, "chips": 1,
        "why": "a later PR's cell: the north-star request once more"})
    doc["per_layer"].append({
        "name": APPENDED_METRIC, "unit": "queries", "better": "higher",
        "source": "program_counter", "layer": "dispatch scheduler",
        "moves": "search_p50_ms", "workloads": [APPENDED]})
    _write(doc, tmp, "BENCHMARK.json")
    return tmp


# ---- what a cell is, read from its data ----------------------------------

def loop_of(cell: Cell) -> str:
    return cell.traffic.get("loop", "open")


def is_bm25(cell: Cell) -> bool:
    return cell.config["device_counter"].startswith("tpu_turbo.")


def is_knn(cell: Cell) -> bool:
    return cell.config["device_counter"].startswith("tpu_knn.")


def data_of(cell: str) -> Cell:
    """A cell's data as committed (not cut to tiny size), for what has to
    be known while tests are collected; a tiny root is a fixture's to
    make."""
    return REAL.cell(cell)


def cells_where(pred, manifest: Manifest = REAL) -> tuple:
    """The cells (the manifest's order) of whose data `pred` holds: the
    manifest as committed, or the one a test was handed."""
    return tuple(c for c in manifest.cell_names() if pred(manifest.cell(c)))


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
    # the device the recorded traces were taken on reports its peaks
    mp.setattr("benchmark.run.memory_peaks", lambda: list(RECORDED_PEAKS))
    mp.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
