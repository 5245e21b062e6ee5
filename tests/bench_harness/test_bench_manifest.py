"""BENCHMARK.json and the data files it names: the character rules, the
files found by name, the chip-time limit, and a cell that exists only as
new files. Every test of the manifest's lists takes it through
conftest.py's `grown`: as committed, and with one
more cell and one more `per_layer` entry appended, which is all a PR
that changes the program may do to it."""

import json
import os
import shutil

import pytest

import numpy as np

from benchmark import readers, run, validate
from benchmark.manifest import (ROOT, Manifest, ManifestError, NAME_RE,
                                load_kind, name_faults)

import bench_tiny

M = Manifest(ROOT)
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_names_and_units_pass_the_character_rules(grown):
    assert name_faults(grown.doc) == []


def test_bad_names_and_units_are_found():
    doc = json.loads(json.dumps(M.doc))
    doc["end_to_end"][0]["unit"] = "tokens per second"
    doc["per_layer"][0]["name"] = "late ms"
    doc["workloads"][0]["traffic"] = "a/b"
    faults = name_faults(doc)
    assert len(faults) == 3


@pytest.mark.parametrize("key", ["configs", "workloads", "end_to_end",
                                 "per_layer"])
def test_entries_have_just_the_contract_keys(grown, key):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }[key]
    names = set()
    for entry in grown.doc[key]:
        assert set(entry) <= allowed, entry
        assert entry["name"] not in names
        names.add(entry["name"])
        for text in ("why", "source", "layer"):
            if text in entry and key != "end_to_end" and text != "source":
                assert 1 <= len(entry[text]) <= 200 and "\n" not in entry[text]
    assert set(grown.doc) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}


def test_metric_entries_are_sound(grown):
    e2e = {m["name"]: m for m in grown.doc["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in grown.doc["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = set(grown.cell_names())
    for m in grown.doc["per_layer"]:
        assert m["source"] in SOURCES
        assert m["moves"] in e2e
        assert set(m.get("workloads", ())) <= cells
        if "roofline" in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_setup_one_more_metric_and_a_layer_metric(grown):
    for cell in grown.cell_names():
        e2e = [m["name"] for m in grown.declared(cell, 0)]
        assert "setup_s" in e2e and len(e2e) >= 2
        per = grown.declared(cell, 1)
        assert per
        for m in per:                   # each moves something the cell reports
            assert m["moves"] in e2e, (cell, m["name"])


def test_every_declared_metric_has_a_reader_file_of_a_known_kind(grown):
    for cell in grown.cell_names():
        for trace in (0, 1):
            for m in grown.declared(cell, trace):
                spec = grown.metric_spec(m["name"])
                assert callable(
                    load_kind(grown.dir, "reader", spec["kind"]).read)
                if "cost" in spec:
                    assert callable(load_kind(grown.dir, "cost",
                                              spec["cost"]).cost)


def test_every_cell_names_kinds_that_are_files(grown):
    for cell in grown.cell_names():
        c = grown.cell(cell)
        assert c.corpus_kind.TINY and callable(c.corpus_kind.make_parts)
        assert callable(c.corpus_kind.segment)
        kind = c.request_kind
        for name in ("Requests", "top_k", "well_formed", "reference",
                     "numbers"):
            assert callable(getattr(kind, name)), (cell, name)


def _rewrite(root, relpath, change):
    with open(os.path.join(root, relpath)) as f:
        doc = json.load(f)
    change(doc)
    with open(os.path.join(root, relpath), "w") as f:
        json.dump(doc, f)


@pytest.mark.parametrize("family", ["corpus", "request", "reader", "cost"])
def test_an_unknown_kind_is_a_manifest_error_that_names_the_file(tmp_path,
                                                                 family):
    """A configuration, a mix or a metric file that names a kind nobody
    brought ends as `ManifestError` with the path looked for, never as a
    `KeyError` from inside a table; a run of such a cell ends before a
    node is started."""
    root = bench_tiny.tiny_root(str(tmp_path / "root"))
    m = Manifest(root)
    cell = m.cell(m.cell_names()[0])
    looked_for = os.path.join(m.dir, "kinds", family, "no-such.kind.py")
    with pytest.raises(ManifestError) as e:
        if family == "corpus":
            _rewrite(root, m.doc["configs"][0]["file"],
                     lambda cfg: cfg.update(kind="no-such.kind"))
            run.Bench(Manifest(root), cell.name, require_chip=False)
        elif family == "request":
            _rewrite(root, f"benchmark/traffic/{cell.traffic_name}.json",
                     lambda t: t["request"].update(kind="no-such.kind"))
            run.Bench(Manifest(root), cell.name, require_chip=False)
        else:
            w = readers.Window(
                config={}, traffic={}, seconds=1.0, setup_s=1.0,
                latency_ms=np.zeros(0), late_ms=np.zeros(0), queries_done=0,
                stats_before={}, stats_after={}, memory_peak_bytes=0,
                device_kind="TPU v5 lite", events=[], kinds_dir=m.dir)
            spec = {"kind": "no-such.kind"}
            if family == "cost":
                w = _a_window_that_reaches_the_cost(w)
                spec = dict(m.metric_spec("sweep_roofline_pct.search"),
                            cost="no-such.kind")
            readers.read(spec, w)
    assert looked_for in str(e.value), str(e.value)
    # a name that leaves the directory is refused by its characters
    with pytest.raises(ManifestError):
        load_kind(m.dir, family, "../reader/setup")


def _a_window_that_reaches_the_cost(w):
    """One traced sweep, one pass counted, one engine in the ledger."""
    w.events = [["/device:TPU:0", "XLA Ops",
                 "%sweep_rowmax.3 = (f32[6,8,32]) custom-call()", 0.0, 100.0,
                 ""]]
    hist = {"count": 0, "mean": 0.0}
    w.stats_before = {"tpu_turbo": {"fused_dispatches": 0},
                      "tpu_search_latency": {"coalesce_batch_size": hist}}
    w.stats_after = {"tpu_turbo": {"fused_dispatches": 1},
                     "tpu_search_latency": {"coalesce_batch_size": hist},
                     "tpu_hbm": {"engines": {"e": {
                         "kind": "fused_turbo",
                         "regions": {"cols_hi": 8, "cols_lo": 8}}}}}
    return w


def test_files_lie_under_paths_and_configs_are_used(grown):
    paths = grown.doc["paths"]
    assert all(os.path.isdir(os.path.join(grown.root, p)) for p in paths)
    used = {w["config"] for w in grown.doc["workloads"]}
    files = set()
    for c in grown.doc["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(paths[0] + "/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(grown.root, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME_RE.match(k) for k in c["reduced"])
        assert cfg["guarantees"] and cfg["assumed"] and cfg["source"]
    pairs = [(w["config"], w["traffic"]) for w in grown.doc["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_the_check_fits_its_chip_time_with_all_24_cells(grown):
    rs = grown.doc["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in grown.doc["workloads"])
    assert four <= max(1, len(grown.doc["workloads"]) // 2)


def test_command_names_nothing_outside_paths(grown):
    cmd = grown.doc["command"]
    assert cmd[:3] == ["python3", "-m", "benchmark"] and len(cmd) <= 32
    assert not any(w.startswith("/") or ".." in w for w in cmd)
    assert os.path.getsize(
        os.path.join(grown.root, "BENCHMARK.json")) <= 64 * 1024


def test_every_metric_file_is_named_by_exactly_one_entry(grown):
    """A file under benchmark/metrics/ that no entry names is read by no
    run (PR 42's ten lay inert for two PRs), and an entry without its
    file ends a run as a ManifestError: the two sets are the same, and
    no name stands twice."""
    files = sorted(n[:-len(".json")]
                   for n in os.listdir(os.path.join(grown.dir, "metrics")))
    named = sorted(m["name"] for m in
                   grown.doc["end_to_end"] + grown.doc["per_layer"])
    assert named == files
    assert len(set(named)) == len(named)


def test_unknown_workload_is_an_error():
    with pytest.raises(ManifestError, match="no workload"):
        M.cell("nope.search-open")


def test_a_cell_defined_only_by_new_files_is_found_by_name(tmp_path):
    """A later PR adds a configuration, a traffic mix, a counter-backed
    metric and a cell with new files and new entries only."""
    root = bench_tiny.tiny_root(str(tmp_path / "root"))
    bdir = os.path.join(root, "benchmark")
    shutil.copy(os.path.join(bdir, "configs", "msmarco-passage-bm25.json"),
                os.path.join(bdir, "configs", "later-config.json"))
    with open(os.path.join(bdir, "traffic", "later-mix.json"), "w") as f:
        json.dump({"loop": "open", "rate_per_s": 5.0, "batch": 1,
                   "plan_seed": 7, "request": {
                       "kind": "match", "size": 10, "terms_cycle": [3],
                       "term_zipf_s": 1.0}}, f)
    with open(os.path.join(bdir, "metrics", "sparse_queries.later.json"),
              "w") as f:
        json.dump({"kind": "counter_delta",
                   "paths": ["tpu_turbo.sparse_queries"]}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({
        "name": "later-config", "source": "s",
        "file": "benchmark/configs/later-config.json", "reduced": [],
        "why": "w"})
    doc["workloads"].append({"name": "later.cell", "config": "later-config",
                             "traffic": "later-mix", "chips": 1, "why": "w"})
    doc["per_layer"].append({
        "name": "sparse_queries.later", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "engines",
        "moves": "search_p50_ms", "workloads": ["later.cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    m = Manifest(root)
    cell = m.cell("later.cell")
    assert cell.traffic["rate_per_s"] == 5.0
    assert cell.config["kind"] == "text"
    declared = [x["name"] for x in m.declared("later.cell", 1)]
    assert "sparse_queries.later" in declared
    # with no `workloads` list an end-to-end metric is every cell's, and a
    # per-layer metric follows the end-to-end metric it moves
    assert "search_p50_ms" in [x["name"] for x in m.declared("later.cell", 0)]
    assert "pool_wait_ms.search" in declared
    assert "knn_uncertified_pct.search" not in declared
    # the lane's four carry lists since the first closed cell landed (the
    # driver holds a cell to the manifest's lists): a later open cell
    # reads the lane through metric entries and files of its own
    assert "sched_wait_ms.search" not in declared
    assert m.metric_spec("sparse_queries.later")["kind"] == "counter_delta"
    # and the validator holds the new cell to its own list
    line = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1, "busy_s": 1.0,
                       "window_s": 2.0}}
    faults = validate.line_faults(line, m, "later.cell", 1)
    assert "metrics lacks sparse_queries.later" in faults
    # a CLOSED cell that came as data (the four-chip cell, PR 37) gets
    # what has something to read there by the metric files' own
    # `loops`: the pools' wait and the dispatch's steps, not the lane's
    # histograms (its batches skip the lane) nor the generator's lateness
    closed = [x["name"] for x in m.declared(bench_tiny.FOUR, 1)]
    assert {"pool_wait_ms.search", "dispatch_ms.search", "finish_ms.search",
            "tail_p95_ms.search"} <= set(closed)
    assert "queries_per_s" in [
        x["name"] for x in m.declared(bench_tiny.FOUR, 0)]
    assert not {"sched_wait_ms.search", "batch_queries.search",
                "lane_idle_ms.search", "gen_late_ms.search"} & set(closed)
    assert "metrics lacks pool_wait_ms.search" in validate.line_faults(
        line, m, bench_tiny.FOUR, 1)
    assert "metrics lacks sched_wait_ms.search" not in validate.line_faults(
        line, m, bench_tiny.FOUR, 1)


def test_a_metric_files_loops_and_the_manifests_lists_agree(grown):
    """The driver holds a cell to the manifest (a metric without a
    `workloads` list in every cell that reports what it moves), the
    harness leaves out what a metric's file says has nothing to read
    under the cell's loop: for every cell the manifest holds the two
    give the same set. (The PR that lands a closed cell gives the
    `loops: ["open"]` metrics their lists, or the driver refuses it.)"""
    for cell in grown.cell_names():
        loop = grown.cell(cell).traffic.get("loop", "open")
        reported = {x["name"] for x in grown.end_to_end(cell)}
        by_list = [x["name"] for x in grown.doc["per_layer"]
                   if (cell in x["workloads"] if "workloads" in x
                       else x["moves"] in reported)]
        assert [x["name"] for x in grown.per_layer(cell)] == by_list
        for name in by_list:
            assert loop in grown.metric_spec(name).get("loops", [loop]), name
    narrowed = sorted(x["name"] for x in grown.doc["per_layer"]
                      if "loops" in grown.metric_spec(x["name"]))
    # at least these four: a later cell's own lane metric may say it too
    assert set(narrowed) >= {"batch_queries.search", "gen_late_ms.search",
                             "lane_idle_ms.search", "sched_wait_ms.search"}
    # the search pool's queue is every loop's: /_msearch passes it too
    assert "loops" not in grown.metric_spec("pool_wait_ms.search")
