"""PR 42's per-layer metrics: ten files under benchmark/metrics/, each a
`span_mean` over a histogram the program holds since that PR, and the ten
`per_layer` entries that read them, rehearsed on the CPU at tiny size.

The entries are IN BENCHMARK.json since PR 44, appended behind what PR 40
left, in the table's order (they waited two PRs in a file beside this
one: a PR that changes the program may only append to `per_layer`, and a
test of the logs cell held the LAST entries to be that cell's nine). The
four REST / route steps carry no `workloads` list: every cell passes the
handler, the route step and the response, and a cell that comes later
reads them with no entry of its own. The tests that hold the entries'
place take the manifest through conftest.py's `grown`, as committed and
with one more cell appended.
"""

import json

import pytest

from benchmark import readers, run, trace, validate
from benchmark.manifest import name_faults

import bench_tiny

SEED = 2147483647
CHIP = {"platform": "tpu", "kind": "TPU v5 lite"}
# the cells PR 42 wrote the entries for: their lists, which no later PR
# may edit (a later bm25 or open cell reads these steps through entries
# of its own)
MATCH = ("msmarco-bm25.search-open", "msmarco-bm25.msearch-closed",
         "msmarco-bm25-4shard.msearch-closed")
OPEN = ("msmarco-bm25.search-open", "msmarco-knn.search-open",
        "msmarco-bm25.bool-open", "http-logs.filter-agg-open")
EVERY = None        # no `workloads` list: every cell's, a later cell's too

# metric -> (histogram, layer, the cells its entry lists)
TABLE = {
    "rescore_rows_ms.search": ("dispatch.rescore_rows", "host finish", MATCH),
    "rescore_survivors_ms.search": (
        "dispatch.rescore_survivors", "host finish", MATCH),
    "survivor_bound_ms.search": (
        "dispatch.survivor_bound", "host finish", MATCH),
    "merge_cert_ms.search": ("dispatch.merge_cert", "host finish", MATCH),
    "cert_fallback_ms.search": (
        "dispatch.cert_fallback", "host finish", MATCH),
    "rest_total_ms.search": ("rest_total", "HTTP + thread pools", EVERY),
    "rest_parse_ms.search": ("rest.parse", "HTTP + thread pools", EVERY),
    "route_ms.search": ("route", "routing", EVERY),
    "rest_respond_ms.search": ("rest.respond", "HTTP + thread pools", EVERY),
    "sched_fill_ms.search": ("sched_fill", "dispatch scheduler", OPEN),
}
PR40_LAST = "agg_reduce_roofline_pct.agg"   # the entry the ten stand behind


def table_names(cell: str) -> list:
    """The table's metrics a run of `cell` prints, in the table's order."""
    return [n for n, (_h, _l, cells) in TABLE.items()
            if cells is EVERY or cell in cells]


def test_the_table_is_the_issues(grown):
    # at least these, however many cells follow
    assert set(MATCH) <= set(bench_tiny.cells_where(
        lambda c: bench_tiny.is_bm25(c)
        and c.traffic["request"]["kind"] == "match", grown))
    assert set(OPEN) <= set(bench_tiny.cells_where(
        lambda c: bench_tiny.loop_of(c) == "open", grown))
    assert set(bench_tiny.CELLS) <= set(grown.cell_names())
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
    return bench_tiny.tiny_manifest(str(tmp_path_factory.mktemp("tiny")))


def test_the_ten_are_in_behind_what_pr_40_left_in_the_tables_order(grown):
    names = [m["name"] for m in grown.doc["per_layer"]]
    at = names.index(PR40_LAST) + 1
    assert names[at:at + len(TABLE)] == list(TABLE)


def test_the_entries_fit_the_manifest_and_list_the_tables_cells(grown):
    doc = grown.doc
    assert name_faults(doc) == []
    assert len(json.dumps(doc)) < 64 * 1024
    by_name = {m["name"]: m for m in doc["per_layer"]}
    assert len(by_name) == len(doc["per_layer"])
    layers = {m["layer"] for m in doc["per_layer"] if m["name"] not in TABLE}
    for name, (_hist, layer, cells) in TABLE.items():
        m = by_name[name]
        want = {"name": name, "unit": "ms", "better": "lower",
                "source": "program_span", "moves": "search_p50_ms",
                "layer": layer}
        if cells is not EVERY:
            want["workloads"] = list(cells)
            # every listed cell reports what the metric moves; none lists
            # one cell alone
            for cell in cells:
                assert m["moves"] in {x["name"]
                                      for x in grown.end_to_end(cell)}
            assert len(cells) >= 3
        assert m == want
        # a layer the manifest already names, letter for letter
        assert layer in layers or layer == "routing"
    # what a cell prints follows: a cell appended later reads the REST
    # path and the route with no entry of its own
    for cell in grown.cell_names():
        got = [x["name"] for x in grown.declared(cell, 1)]
        assert [n for n in got if n in TABLE] == table_names(cell)
        assert set(table_names(cell)) >= {
            "rest_total_ms.search", "rest_parse_ms.search",
            "route_ms.search", "rest_respond_ms.search"}


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
    want = table_names(cell)
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
