"""The filter + bucket cell's comparison, at tiny size on the CPU, with no
node: the plain reference (benchmark/kinds/request/filter_agg.py) answers
the mix's own requests; a served side built from the reference itself is
`correct`, and each of the kind's two controls has to come out not
`correct`: the reference with its timestamps one precision below the
int64 milliseconds the configuration states ("float32_time"), and the
reference of ANOTHER request, a clause left out or a bound moved
("drop_clause"). Then the configuration's own part of the verdict: the
aggregation engine must have answered with match sets made on the device
(`must_rise`), no host aggregator and no host-made mask may have
(`must_stay`).

The request kind's response is a bucket list and a total, not a hit list:
it compares under its own `numbers`, so the shared hit-list tests
(test_bench_compare.py) leave it to these. The served route itself is
held to the same reference in tests/test_filter_agg_route.py and, over
HTTP, in test_bench_cells.py.
"""

import os

import numpy as np
import pytest

from benchmark import compare, run
from benchmark.traffic import Mix

import bench_tiny
from test_bench_compare import _stats, _verdict

FILTER_AGG = bench_tiny.cells_where(
    lambda c: c.traffic["request"]["kind"] == "filter_agg")
LOGS = "http-logs.filter-agg-open"
# the cell's per-layer entries as PR 40 appended them
NINE = ["gen_late_ms.agg", "sched_wait_ms.agg", "batch_queries.agg",
        "lane_idle_ms.agg", "agg_plan_ms.agg", "agg_fold_ms.agg",
        "agg_device_pct.agg", "agg_reductions_per_dispatch.agg",
        "agg_reduce_roofline_pct.agg"]
SEED = 1556403449
CONTROLS = ("float32_time", "drop_clause")


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return bench_tiny.tiny_manifest(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture(scope="module")
def sides(manifest):
    """cell -> (Cell, the mix's first 64 requests, their answers by
    precision), the corpus drawn once."""
    made = {}

    def get(cell_name):
        if cell_name not in made:
            cell = manifest.cell(cell_name)
            parts = cell.corpus_kind.make_parts(cell.config, SEED)
            mix = Mix(cell, SEED, parts)
            reqs = [mix.request(i) for i in range(64)]
            made[cell_name] = (cell, reqs, {
                p: cell.request_kind.reference(cell.config, parts, p)
                .answers(reqs, 0) for p in (None,) + CONTROLS})
        return made[cell_name]

    return get


def _as_response(answer, name="by_time"):
    """What a server that computed `answer` would send."""
    return {"timed_out": False, "_shards": {"failed": 0},
            "hits": {"total": {"value": answer["total"], "relation": "eq"},
                     "max_score": None, "hits": []},
            "aggregations": {name: {"buckets": [
                {"key": int(k), "doc_count": int(c)}
                for k, c in zip(answer["keys"], answer["counts"])]}}}


def _pairs(answers, served):
    return [(_as_response(a), ref)
            for a, ref in zip(answers[served], answers[None])]


def test_the_cell_is_in_the_manifest_as_the_issue_names_it(grown):
    """Held by name and by neighbours, never by a last place or a count:
    `grown` hands the manifest over as committed and with one more cell
    and one more entry appended, and this passes in both."""
    assert LOGS in bench_tiny.cells_where(
        lambda c: c.traffic["request"]["kind"] == "filter_agg", grown)
    cell = grown.cell(LOGS)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "http-logs-filter-agg", "filter-agg-open", 1)
    assert os.path.isfile(bench_tiny.recorded(LOGS))
    cfg, t = cell.config, cell.traffic
    assert cfg["kind"] == "logs" and cfg["index"]["segments"] == 3
    assert cfg["corpus"]["days"] == 88
    assert cfg["corpus"]["published_docs"] == 247_249_096
    # the chip's share, or half of it; never under a quarter
    assert cfg["corpus"]["docs"] in (30_906_137, 15_453_068)
    assert ("docs" in cfg["reduced"]) == (cfg["corpus"]["docs"] != 30_906_137)
    assert cfg["index"]["mappings"]["properties"] == {
        "@timestamp": {"type": "date"}, "status": {"type": "integer"},
        "size": {"type": "integer"}}
    assert cfg["env"] == {"ES_TPU_SCHED_BUCKETS": "1,4,16"}
    assert (t["loop"], t["connections"], t["pool"]) == ("open", 32, 16384)
    assert t["rate_per_s"] == int(t["rate_per_s"]) and t["rate_per_s"] <= 19
    assert t["warmup"]["buckets"] == [1, 4, 16]
    assert t["warmup"]["lead_in_s"] == 10
    doc = grown.doc
    mine = [m for m in doc["per_layer"] if m.get("workloads") == [LOGS]]
    # at least these: a later PR may append a metric of the cell's own
    assert {m["name"] for m in mine} >= set(NINE) | {"agg_chunks_run_pct.agg"}
    assert all(m["moves"] == "search_p50_ms" for m in mine)
    # appended, and nothing that was there moved: the cell stands after
    # the five that were there before it, wherever the list ends now ...
    cells = [w["name"] for w in doc["workloads"]]
    assert set(cells[:cells.index(LOGS)]) >= {
        "msmarco-bm25.search-open", "msmarco-knn.search-open",
        "msmarco-bm25.msearch-closed", "msmarco-bm25-4shard.msearch-closed",
        "msmarco-bm25.bool-open"}
    # ... and its nine original entries stand together, in this order
    names = [m["name"] for m in doc["per_layer"]]
    at = names.index(NINE[0])
    assert names[at:at + len(NINE)] == NINE


@pytest.mark.parametrize("cell", FILTER_AGG)
def test_the_mix_is_the_cycle_the_issue_wrote(sides, cell):
    c, reqs, _ = sides(cell)
    req = c.traffic["request"]
    assert req["cycle"] == [
        "RangeHourly", "Status200sInRange", "RangeTenMinute", "HourlyAgg",
        "RangeHourly", "Status400sInRange", "RangeTenMinute",
        "Status200sInRange"]
    assert [r.shape for r in reqs[:8]] == req["cycle"]
    want = {"HourlyAgg": (None, "1h", None),
            "RangeHourly": (7 * 86400, "1h", None),
            "RangeTenMinute": (86400, "10m", None),
            "Status200sInRange": (7 * 86400, "1h", 200),
            "Status400sInRange": (7 * 86400, "1h", 404)}
    assert {n: (s["range_s"], s["interval"], s["status"])
            for n, s in req["shapes"].items()} == want
    for r in reqs:
        body = r.body
        assert body["size"] == 0 and body["track_total_hits"] is True
        (agg,) = body["aggs"].values()
        assert set(agg) == {"date_histogram"}
        assert agg["date_histogram"]["field"] == "@timestamp"
        length, interval, status = want[r.shape]
        assert agg["date_histogram"]["fixed_interval"] == interval
        if length is None:
            assert body["query"] == {"match_all": {}}
            continue
        clauses = body["query"]["bool"]["filter"]
        assert set(body["query"]["bool"]) == {"filter"}
        assert r.hi - r.lo == 1000 * length
        assert len(clauses) == 1 + (status is not None)
        if status is not None:
            assert clauses[1] == {"term": {"status": status}}


@pytest.mark.parametrize("cell", FILTER_AGG)
def test_the_reference_counts_a_request_document_by_document(sides, cell):
    """The reference against a second, slower reading of the same
    definition: a log line at a time."""
    c, reqs, answers = sides(cell)
    parts = c.corpus_kind.make_parts(c.config, SEED)
    ts = np.concatenate([p.ts for p in parts]).tolist()
    status = np.concatenate([p.status for p in parts]).tolist()
    for r, a in list(zip(reqs, answers[None]))[:16]:
        counts = {}
        for t, s in zip(ts, status):
            if (r.lo is None or r.lo <= t < r.hi) \
                    and (r.status is None or s == r.status):
                key = t // r.interval * r.interval
                counts[key] = counts.get(key, 0) + 1
        assert a["total"] == sum(counts.values())
        got = {int(k): int(n) for k, n in zip(a["keys"], a["counts"])}
        assert {k: n for k, n in got.items() if n} == counts
        if counts:      # the empty buckets between the first and the last
            assert sorted(got) == list(range(
                min(counts), max(counts) + 1, r.interval))
    assert sum(a["total"] > 0 for a in answers[None]) >= 32


@pytest.mark.parametrize("cell", FILTER_AGG)
def test_reference_against_itself_is_correct(sides, cell):
    c, _reqs, answers = sides(cell)
    cfg = c.config
    checked = _verdict(c, _pairs(answers, None), 0, _stats(cfg),
                       _stats(cfg), _stats(cfg, moved=5))
    assert compare.is_correct(checked), compare.lines(checked)
    assert all(c.request_kind.well_formed(r)
               for r, _ in _pairs(answers, None))


@pytest.mark.parametrize("control", CONTROLS)
@pytest.mark.parametrize("cell", FILTER_AGG)
def test_each_control_is_not_correct(sides, cell, control):
    c, _reqs, answers = sides(cell)
    cfg = c.config
    assert cfg["precision"]["control"] == "float32_time"
    assert control in cfg["precision"]["controls"]
    checked = _verdict(c, _pairs(answers, control), 0, _stats(cfg),
                       _stats(cfg), _stats(cfg, moved=5))
    assert not compare.is_correct(checked)
    # by a wide margin, not by luck: dozens of buckets, with every limit 0
    assert checked["counts_wrong"]["value"] + checked["keys_wrong"][
        "value"] > 20
    assert all(checked[n]["limit"] == 0
               for n in ("keys_wrong", "counts_wrong", "totals_wrong"))
    if control == "drop_clause":
        assert not checked["totals_wrong"]["ok"]


@pytest.mark.parametrize("cell", FILTER_AGG)
def test_a_host_answer_or_an_idle_device_route_is_not_correct(sides, cell):
    c, _reqs, answers = sides(cell)
    cfg = c.config
    assert cfg["device_counter"] == "tpu_agg.agg_device_dispatches"
    assert cfg["must_rise"] == ["tpu_agg.agg_queries",
                                "tpu_agg.filter_device"]
    assert cfg["must_stay"] == ["tpu_agg.agg_host_fallbacks",
                                "tpu_agg.filter_host"]
    pairs = _pairs(answers, None)
    for counter in cfg["must_stay"]:
        key = counter.replace(".", "__")
        on_host = _verdict(c, pairs, 0, _stats(cfg), _stats(cfg),
                           _stats(cfg, moved=5, **{key: 1}))
        assert on_host["host_tier_answers"]["value"] >= 1
        assert not compare.is_correct(on_host)
    for counter in cfg["must_rise"]:
        key = counter.replace(".", "__")
        idle = _verdict(c, pairs, 0, _stats(cfg), _stats(cfg),
                        _stats(cfg, moved=5, **{key: 0}))
        assert idle["device_dispatches"]["value"] == 0
        assert not compare.is_correct(idle)


def test_a_program_without_the_counters_is_refused_at_set_up(manifest,
                                                             monkeypatch):
    """The parent commit under these files: its `tpu_agg` has no
    `filter_device` / `filter_host`, so the corpus kind ends the run
    before it draws a line (benchmark/run.py turns the ManifestError
    into exit code 2)."""
    from benchmark.manifest import ManifestError
    from elasticsearch_tpu.search import agg_device

    cell = manifest.cell(LOGS)
    old = {"agg_queries": 0, "agg_device_dispatches": 0,
           "agg_host_fallbacks": 0, "agg_bytes": 0, "enabled": True}
    monkeypatch.setattr(agg_device, "agg_stats", lambda: dict(old))
    with pytest.raises(ManifestError, match="filter_device"):
        cell.corpus_kind.make_parts(cell.config, SEED)
