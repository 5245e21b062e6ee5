"""PR 42's per-layer metrics: ten files under benchmark/metrics/, each a
`span_mean` over a histogram the program holds since this PR, and the ten
`per_layer` entries that read them, rehearsed on the CPU at tiny size.

The entries are NOT in BENCHMARK.json: they wait in
`pending_per_layer.json` beside this file, as a `benchmark` PR will append
them. A PR that changes the program may only append to `per_layer` (the
driver refused them placed before `gen_late_ms.agg`, where ISSUE 42 put
them), and `test_bench_filter_agg.py` holds the LAST entries of
`per_layer` to be the logs cell's nine, so an appended entry fails that
test until a `benchmark` PR rewrites its last assert. Here they are laid
at the END of a tiny root's copy of the manifest, where the driver's rule
puts them, and read through the harness as committed.
"""

import json

import pytest

import os

from benchmark import readers, run, trace, validate
from benchmark.manifest import Manifest, name_faults

import bench_tiny

SEED = 2147483647
CHIP = {"platform": "tpu", "kind": "TPU v5 lite"}
ALL = bench_tiny.CELLS
OPEN = bench_tiny.cells_where(lambda c: bench_tiny.loop_of(c) == "open")
MATCH = bench_tiny.cells_where(
    lambda c: bench_tiny.is_bm25(c)
    and c.traffic["request"]["kind"] == "match")

# metric -> (histogram, layer, the cells its reader finds something in)
TABLE = {
    "rescore_rows_ms.search": ("dispatch.rescore_rows", "host finish", MATCH),
    "rescore_survivors_ms.search": (
        "dispatch.rescore_survivors", "host finish", MATCH),
    "survivor_bound_ms.search": (
        "dispatch.survivor_bound", "host finish", MATCH),
    "merge_cert_ms.search": ("dispatch.merge_cert", "host finish", MATCH),
    "cert_fallback_ms.search": (
        "dispatch.cert_fallback", "host finish", MATCH),
    "rest_total_ms.search": ("rest_total", "HTTP + thread pools", ALL),
    "rest_parse_ms.search": ("rest.parse", "HTTP + thread pools", ALL),
    "route_ms.search": ("route", "routing", ALL),
    "rest_respond_ms.search": ("rest.respond", "HTTP + thread pools", ALL),
    "sched_fill_ms.search": ("sched_fill", "dispatch scheduler", OPEN),
}
REAL = bench_tiny.REAL.doc["per_layer"]
with open(os.path.join(os.path.dirname(__file__),
                       "pending_per_layer.json")) as _f:
    ENTRIES = json.load(_f)


def test_the_table_is_the_issues():
    assert MATCH == ("msmarco-bm25.search-open", "msmarco-bm25.msearch-closed",
                     "msmarco-bm25-4shard.msearch-closed")
    assert len(OPEN) == 4 and len(ALL) == 6 and len(ENTRIES) == 10
    assert bench_tiny.TWIN in MATCH and bench_tiny.TWIN not in OPEN


@pytest.mark.parametrize("name", list(TABLE))
def test_a_metric_file_names_span_mean_and_a_histogram_the_program_declares(
        name):
    from elasticsearch_tpu.common import metrics

    spec = bench_tiny.REAL.metric_spec(name)
    hist = TABLE[name][0]
    assert spec["kind"] == "span_mean"
    assert spec["path"] == "tpu_search_latency." + hist
    # what a fresh node's GET /_nodes/stats holds under that path
    assert hist in metrics.search_latency_stats()
    # narrowed by its `workloads` list alone (test_bench_manifest pins the
    # set of files with a `loops` key)
    assert "loops" not in spec and spec["what"]


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    root = bench_tiny.tiny_root(str(tmp_path_factory.mktemp("tiny")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["per_layer"] += ENTRIES
    with open(path, "w") as f:
        json.dump(doc, f)
    return Manifest(root)


def test_the_ten_wait_beside_the_manifest_and_are_laid_at_its_end(manifest):
    assert [m["name"] for m in ENTRIES] == list(TABLE)
    # the manifest as committed holds none of them ...
    assert not {m["name"] for m in REAL} & set(TABLE)
    # ... and laid over, nothing that was there moved
    laid = manifest.doc["per_layer"]
    assert laid[:len(REAL)] == REAL and laid[len(REAL):] == ENTRIES


def test_the_entries_fit_the_manifest_and_list_the_tables_cells(manifest):
    doc = manifest.doc
    assert name_faults(doc) == []
    assert len(json.dumps(doc)) < 64 * 1024
    layers = {m["layer"] for m in REAL}
    by_name = {m["name"]: m for m in doc["per_layer"]}
    assert len(by_name) == len(doc["per_layer"])
    for m in ENTRIES:
        assert by_name[m["name"]] == m
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "program_span", "moves": "search_p50_ms",
                     "layer": TABLE[m["name"]][1],
                     "workloads": m["workloads"]}
        assert tuple(m["workloads"]) == TABLE[m["name"]][2]
        # a layer the manifest already names, letter for letter
        assert m["layer"] in layers or m["layer"] == "routing"
        # every listed cell reports what the metric moves; none lists the
        # logs cell alone (test_bench_filter_agg holds that set to nine)
        for cell in m["workloads"]:
            assert m["moves"] in {x["name"]
                                  for x in manifest.end_to_end(cell)}
        assert len(m["workloads"]) >= 3
    for cell in ALL:
        want = [n for n, (_h, _l, cells) in TABLE.items() if cell in cells]
        got = [x["name"] for x in manifest.declared(cell, 1)]
        assert [n for n in got if n in TABLE] == want


@pytest.mark.parametrize("name", list(TABLE))
def test_a_window_of_a_tree_without_the_span_reads_zero(name):
    """The parent's traced run: its stats hold no such histogram, and the
    reader returns 0.0 and does not raise (`span_mean` is total)."""
    spec = bench_tiny.REAL.metric_spec(name)
    stats = {"tpu_search_latency": {}}
    w = readers.Window(
        config={}, traffic={}, seconds=1.0, setup_s=0.0, latency_ms=None,
        late_ms=None, queries_done=0, stats_before=stats, stats_after=stats,
        memory_peak_bytes=0, device_kind="cpu")
    assert readers.read(spec, w) == 0.0
    # ... nor one that took no observation in the window
    w.stats_before = w.stats_after = {"tpu_search_latency": {
        TABLE[name][0]: {"count": 0, "mean": 0.0}}}
    assert readers.read(spec, w) == 0.0


@pytest.fixture(scope="module")
def benches(manifest, tmp_path_factory):
    mp = pytest.MonkeyPatch()
    bench_tiny.steer_engines(mp, str(tmp_path_factory.mktemp("jax_cache")))
    made = {}

    def get(cell):
        if cell not in made:
            b = run.Bench(manifest, cell, require_chip=False,
                          out_dir=str(tmp_path_factory.mktemp("out")))
            b.dev = dict(CHIP, count=b.cell.chips)
            b.setup(SEED)
            made[cell] = b
        return made[cell]

    yield get
    for b in made.values():
        b.close()
    mp.undo()


def traced_line(bench, cell, seconds=1.0):
    events = trace.load_events(bench_tiny.recorded(cell))
    w = bench.window(SEED, seconds, 1, events=events)
    line, rc = bench.report(w, 1)
    assert rc == 0 and line is not None
    return line


def test_a_traced_closed_window_prints_the_finish_by_its_steps(
        benches, manifest, capsys):
    cell = bench_tiny.TWIN
    line = traced_line(benches(cell), cell)
    assert validate.line_faults(line, manifest, cell, 1) == [], \
        capsys.readouterr().err[-2000:]
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    want = [n for n, (_h, _l, cells) in TABLE.items() if cell in cells]
    assert [n for n in m if n in TABLE] == want and len(want) == 9
    assert "sched_fill_ms.search" not in m     # its batches skip the lane
    rows, surv = m["rescore_rows_ms.search"], m["rescore_survivors_ms.search"]
    assert rows > 0 and surv > 0
    assert rows + surv == pytest.approx(m["rescore_ms.search"], rel=0.02)
    steps = (m["rescore_ms.search"] + m["sparse_gather_ms.search"]
             + m["survivor_bound_ms.search"] + m["merge_cert_ms.search"]
             + m["cert_fallback_ms.search"])
    assert 0.5 * m["finish_ms.search"] <= steps \
        <= m["finish_ms.search"] * 1.001
    assert m["survivor_bound_ms.search"] > 0 and m["merge_cert_ms.search"] > 0
    # an _msearch's parse, its route, dispatch and demux lie inside
    # rest_total; the encode and the write come after it
    inside = (m["rest_parse_ms.search"] + m["route_ms.search"]
              + m["dispatch_ms.search"] + m["demux_ms.search"])
    assert m["rest_parse_ms.search"] > 0 and m["route_ms.search"] > 0
    assert m["rest_respond_ms.search"] > 0
    assert 0.5 * m["rest_total_ms.search"] <= inside \
        <= m["rest_total_ms.search"] * 1.001


def test_a_traced_open_window_prints_the_fill_wait_and_the_rest_total(
        benches, manifest, capsys):
    cell = OPEN[0]
    line = traced_line(benches(cell), cell, 2.0)
    assert validate.line_faults(line, manifest, cell, 1) == [], \
        capsys.readouterr().err[-2000:]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["sched_fill_ms.search"] > 0 and m["rest_total_ms.search"] > 0
    # a waiter's wait holds its batch's fill wait and the dispatch
    assert m["sched_wait_ms.search"] >= 0.5 * m["sched_fill_ms.search"]
    assert m["rest_total_ms.search"] >= m["dispatch_ms.search"]
