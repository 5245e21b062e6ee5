"""What a deployment or a mix takes from its data files since PR 29 —
shard count, chips, path parameters, the closed `_msearch` loop, what
must and must not have moved — on the CPU at tiny size, over the 4-device
mesh the four-chip cell runs on; and that the two cells accepted before it
put the same bytes on the wire at the same times as they did. The
four-shard cell is the manifest's since PR 37 (PR 29 held it out): its
committed configuration file is held to bench_tiny's recipe here."""

import dataclasses
import hashlib
import json

import numpy as np
import pytest

from benchmark import compare, loadgen, readers, run, trace
from benchmark.manifest import ROOT, Manifest
from benchmark.traffic import Mix

import bench_tiny

SEED = 1556403449
FOUR = bench_tiny.FOUR
BM25, KNN = "msmarco-bm25.search-open", "msmarco-knn.search-open"
RECORDED = bench_tiny.recorded(FOUR)
# sha256 over one seed's 50 s window and 10 s lead-in (due times, order,
# every path and body), read on PR 28's tree; kNN over 3,000 vectors
GOLDEN = {
    BM25: (950, 190, "e6230ac7dac60ff7b071fa5514cb0a32"
                     "a670d5455a2b30aab51304a9a0f4362d"),
    KNN: (950, 190, "489cbb7ed6f6202dd9c9c734c9dda671"
                    "fedb38bdc29d95fef29a4db10be21698"),
}
# sha256 over the seeded parts of each corpus kind at its TINY size (field
# names, arrays' bytes), read on PR 29's tree before the kinds moved into
# files of their own
GOLDEN_CORPUS = {
    "msmarco-passage-bm25": (3, "e57834e4e998a5f8e90d5a3e18bf6da0"
                                "37f6c449901d4f92cc10be9d42d54625"),
    "msmarco-passage-knn": (3, "6858d5f2c034e9215a435ebb2c40d339"
                               "5093941ff0aa82d40c2adaa960d7b587"),
}


@pytest.mark.parametrize("cell_name", sorted(GOLDEN))
def test_the_accepted_cells_send_what_they_sent_before(cell_name):
    cell = Manifest(ROOT).cell(cell_name)
    cfg = json.loads(json.dumps(cell.config))
    parts = None
    if cell_name == KNN:      # its requests start from the corpus's rows
        cfg["corpus"]["docs"] = 3000
        parts = cell.corpus_kind.make_parts(cfg, SEED)
    mix = Mix(dataclasses.replace(cell, config=cfg), SEED, parts)
    h = hashlib.sha256()
    sched, lead = mix.window(50.0), None
    for span, n_bodies in ((sched, len(sched.due)), (None, 50)):
        if span is None:
            span = lead = mix.lead_in(10.0)
        h.update(np.asarray(span.due, np.float64).tobytes())
        h.update(np.asarray(span.index, np.int64).tobytes())
        for j in span.index[:n_bodies]:
            path, data, _req = mix.call(int(j))
            h.update(path.encode() + b"\0" + data + b"\0")
    assert (len(sched.due), len(lead.due), h.hexdigest()) == GOLDEN[cell_name]
    assert not mix.closed and mix.batch == 1 and mix.query == ""


@pytest.mark.parametrize("config", sorted(GOLDEN_CORPUS))
def test_a_seeded_tiny_corpus_is_what_it_was_before_the_kinds_moved(config):
    m = Manifest(ROOT)
    cell = m.cell(next(w["name"] for w in m.doc["workloads"]
                       if w["config"] == config))
    cfg = json.loads(json.dumps(cell.config))
    cfg["corpus"].update(cell.corpus_kind.TINY)
    parts = cell.corpus_kind.make_parts(cfg, SEED)
    h = hashlib.sha256()
    for p in parts:
        for f in dataclasses.fields(p):
            v = getattr(p, f.name)
            h.update(f.name.encode())
            h.update(np.ascontiguousarray(v).tobytes()
                     if isinstance(v, np.ndarray) else str(v).encode())
    assert (len(parts), h.hexdigest()) == GOLDEN_CORPUS[config]


def test_the_committed_four_shard_file_is_the_recipe_plus_its_size_and_cuts(
        grown):
    """benchmark/configs/<FOUR_CONFIG>.json = `four_shard_config()` (the
    accepted bm25 file, four shards of one segment, the path parameter,
    what must and must not move) and exactly what landing the cell added:
    the host's half of the collection, every cut named, the words about
    both. The recipe and the file cannot drift, and neither can the
    one-chip file it starts from."""
    cell = grown.cell(FOUR)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        4, bench_tiny.FOUR_CONFIG, "msearch-closed-4shard")
    got, want = cell.config, bench_tiny.four_shard_config()
    differs = {k for k in set(got) | set(want) if got.get(k) != want.get(k)}
    assert differs == {"corpus", "reduced", "deployment", "guarantees"}
    corpus = dict(got["corpus"])
    assert corpus.pop("docs") == 4_420_912 == 4 * want["corpus"].pop("docs")
    assert corpus == want["corpus"]          # every width of the source kept
    assert corpus["published_docs"] == 2 * 4_420_912 - 1
    assert got["reduced"] == ["docs", "stored_source", "segments"]
    assert got["guarantees"].startswith(want["guarantees"])
    for word in ("dfs_query_then_fetch", "merge_host"):
        assert word in got["guarantees"]
    for word in ("BASELINE.json", "collection.tsv", "segment"):
        assert word in got["deployment"]
    # the traffic is the twin's but for the warm-up calls (and why): the
    # two cells send the same calls in their windows
    twin = grown.cell(bench_tiny.TWIN).traffic
    own = {k: v for k, v in cell.traffic.items() if k != "why"}
    assert own == dict(twin, warmup={"calls": 8}) and cell.traffic["why"]


def test_hbm_peak_skew_reads_the_spread_of_the_chips_peaks(grown):
    """The fullest chip over the mean of the chips, from what a result
    line prints as `memory_peak_bytes_per_device`: even = 1.0, one chip of
    four holding everything = 4.0; where no device reports a peak (the
    CPU backend) there is nothing to read, never a 0."""
    entry = next(x for x in grown.doc["per_layer"]
                 if x["name"] == "hbm_peak_skew.search")
    assert entry["workloads"] == [FOUR] and entry["better"] == "lower"
    assert (entry["moves"], entry["layer"]) == ("hbm_peak_gb", "device")
    spec = grown.metric_spec("hbm_peak_skew.search")
    w = _roofline_window([], 4, {})
    for peaks, want in (([2 << 30] * 4, 1.0), ([8 << 30, 0, 0, 0], 4.0),
                        (bench_tiny.RECORDED_PEAKS, 2.3565),
                        ([5 << 30], 1.0)):
        w.notes["memory_peaks"] = peaks
        assert readers.read(spec, w) == pytest.approx(want, abs=1e-4)
    for peaks in ([], [0, 0, 0, 0]):
        w.notes["memory_peaks"] = peaks
        with pytest.raises(readers.NothingToRead):
            readers.read(spec, w)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.tiny_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture(scope="module")
def steered(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    bench_tiny.steer_engines(mp, str(tmp_path_factory.mktemp("jax_cache")))
    yield
    mp.undo()


def _bench(root, tmp_path_factory):
    b = run.Bench(Manifest(root), FOUR, require_chip=False,
                  out_dir=str(tmp_path_factory.mktemp("out")))
    b.dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    return b


@pytest.fixture(scope="module")
def bench(root, steered, tmp_path_factory):
    b = _bench(root, tmp_path_factory)
    b.setup(SEED)
    yield b
    b.close()


def test_the_corpus_is_installed_into_four_shards_by_range(bench):
    idx = bench.cell.config["index"]
    assert idx["shards"] == 4
    engines = bench.node.node.indices.get(idx["name"]).shards
    assert len(engines) == 4
    assert len(bench.parts) == 4 * idx["segments"]
    total = sum(p.n for p in bench.parts)
    assert bench.node.request(
        "GET", f"/{idx['name']}/_count")["count"] == total
    # shard s holds the ordinals of its own parts and no others
    per = idx["segments"]
    for s, bound in enumerate(range(0, len(bench.parts), per)):
        first = bench.parts[bound]
        last = bench.parts[bound + per - 1]
        for doc, want in ((first.doc0, True), (last.doc0 + last.n - 1, True),
                          (first.doc0 - 1, False), (last.doc0 + last.n, False)):
            if 0 <= doc < total:
                got = engines[s].get(str(doc))
                assert (got is not None) == want, (s, doc)


def test_every_search_path_carries_the_configurations_parameters(bench):
    c = bench.cell
    mix = Mix(c, SEED, bench.parts)
    assert mix.closed and mix.batch == c.traffic["request"]["batch"]
    path, data, _req = mix.call(0)
    assert path == "/msmarco/_search?search_type=dfs_query_then_fetch"
    path, nd, reqs = mix.msearch(mix.closed_call(1))
    assert path == "/_msearch?search_type=dfs_query_then_fetch"
    lines = nd.decode().splitlines()
    assert len(lines) == 2 * mix.batch == 2 * len(reqs)
    assert json.loads(lines[0]) == {"index": "msmarco"}
    assert json.loads(lines[1]) == mix.request(mix.batch).body
    # call i is the same requests for every seed
    other = Mix(c, SEED + 1, bench.parts)
    assert other.msearch(other.closed_call(1))[1] == nd
    with pytest.raises(ValueError):
        mix.closed_call(mix.window_pool // mix.batch)
    # the pool is the mix's own: absent = 16,384, and a mix that names a
    # larger one begins with the smaller one's requests
    assert mix.pool == c.traffic["pool"] and mix.window_pool == mix.pool // 2
    small = Mix(dataclasses.replace(c, traffic={
        k: v for k, v in c.traffic.items() if k != "pool"}), SEED,
        bench.parts)
    assert small.pool == 16384
    assert small.msearch(small.closed_call(1))[1] == nd


def test_a_closed_window_is_answered_on_the_fast_path_and_is_correct(
        bench, capsys):
    w = bench.window(SEED + 2, 6.0, 0)
    line, rc = bench.report(w, 0)
    assert rc == 0 and line["correct"] is True, capsys.readouterr().err[-2000:]
    rose = lambda path: (compare.dotted(w.stats_after, path)      # noqa: E731
                         - compare.dotted(w.stats_before, path))
    calls = w.notes["attempted"]
    assert calls >= 1 and line["attempted"] == calls and line["failed"] == 0
    assert w.queries_done == calls * bench.cell.traffic["request"]["batch"]
    assert rose("tpu_turbo.fused_dispatches") >= calls
    assert rose("tpu_turbo.merge_device") >= calls
    assert rose("tpu_turbo.merge_host") == 0
    assert all(rose(c) == 0 for c in compare.ZERO_COUNTERS)
    assert line["checked"]["compared"]["value"] >= 24
    assert line["checked"]["device_dispatches"]["value"] >= calls
    # a call is one sample: due and sent the moment its client was free,
    # each after the one before was answered
    assert len(w.latency_ms) == calls and not w.late_ms.any()
    done = w.notes["done_s"]
    due = done - w.latency_ms / 1e3
    assert np.all(np.diff(done) > 0) and np.all(due < 6.0)
    assert np.all(due[1:] >= done[:-1])      # one client: one call in flight
    per_device = line["device"]["memory_peak_bytes_per_device"]
    assert len(per_device) >= 4     # one a device JAX sees (8 virtual here)
    assert max(per_device) == line["device"]["memory_peak_bytes"]
    spec = bench.manifest.metric_spec("queries_per_s")
    assert readers.read(spec, w) == pytest.approx(
        w.queries_done / max(6.0, done[-1]))
    spec = bench.manifest.metric_spec("merge_device_pct.search")
    assert readers.read(spec, w) == 100.0


def test_one_malformed_response_fails_the_whole_call(bench, monkeypatch):
    """`attempted` and `failed` count CALLS: a call whose body lacks a
    well-formed response to one of its requests is not answered, and none
    of its searches is compared."""
    post = loadgen.Conn.post
    seen = []

    def spoil_the_first(self, path, data, ndjson=False):
        status, raw = post(self, path, data, ndjson)
        seen.append(path)
        if len(seen) == 1:
            doc = json.loads(raw)
            doc["responses"][3]["_shards"]["failed"] = 1
            raw = json.dumps(doc).encode()
        return status, raw

    monkeypatch.setattr(loadgen.Conn, "post", spoil_the_first)
    w = bench.window(SEED + 3, 6.0, 0)
    monkeypatch.undo()
    batch = bench.cell.traffic["request"]["batch"]
    calls = w.notes["attempted"]
    assert calls >= 1 and w.notes["calls_answered"] == calls - 1
    assert w.queries_done == (calls - 1) * batch
    line, rc = bench.report(w, 0)
    assert rc == 0 and line["attempted"] == calls and line["failed"] == 1


def test_without_the_search_type_the_dense_executor_answers_not_correct(
        root, steered, tmp_path_factory, capsys):
    """The same deployment with `search_params` left out: a multi-shard
    index then never reaches the device, the dense executor answers every
    search with a 200 and the right hits — a host tier in the device's
    place, which only the counters can see."""
    b = _bench(root, tmp_path_factory)
    del b.cell.config["index"]["search_params"]
    b.setup(SEED)
    try:
        w = b.window(SEED + 2, 3.0, 0)
    finally:
        b.close()
    line, rc = b.report(w, 0)
    assert rc == 0 and line["failed"] == 0 and line["correct"] is False
    assert line["checked"]["device_dispatches"]["value"] == 0
    assert line["checked"]["hits_wrong"]["ok"]


def _roofline_window(events, devices, regions, answered=800):
    hist = {"count": 0, "mean": 0.0}
    before = {"tpu_turbo": {"fused_dispatches": 0},
              "tpu_search_latency": {"coalesce_batch_size": dict(hist)},
              "tpu_hbm": {"engines": {}}}
    after = {"tpu_turbo": {"fused_dispatches": 4},
             "tpu_search_latency": {"coalesce_batch_size": dict(hist)},
             "tpu_hbm": {"engines": {"fused_turbo-1": {
                 "kind": "fused_turbo", "devices": devices,
                 "regions": regions}}}}
    return readers.Window(
        config={}, traffic={"loop": "closed", "request": {"batch": 200}},
        seconds=1.0, setup_s=1.0, latency_ms=np.zeros(0),
        late_ms=np.zeros(0), queries_done=answered, stats_before=before,
        stats_after=after, memory_peak_bytes=0, device_kind="TPU v5 lite",
        events=events)


def test_a_roofline_share_is_per_device_on_one_chip_and_on_four():
    """The recorded four-plane trace with the regions of four devices
    reads what one of its planes reads with one device's regions: least
    time and traced time are both one device's."""
    spec = Manifest(ROOT).metric_spec("sweep_roofline_pct.search")
    events = trace.load_events(RECORDED)
    planes = trace.device_planes(events)
    assert len(planes) == 4
    one_chip = {"cols_hi": 152_000_000, "cols_lo": 152_000_000, "live": 0}
    four_chips = {k: 4 * v for k, v in one_chip.items()}
    four = _roofline_window(events, 4, four_chips)
    got4 = readers.read(spec, four)
    shares = []
    for p in planes:
        own = [e for e in events
               if e[0] == p or not e[0].startswith(trace.DEVICE_PLANE)]
        shares.append(readers.read(spec, _roofline_window(own, 1, one_chip)))
    # the mean of the planes' times, so the harmonic mean of their shares
    assert got4 == pytest.approx(len(shares) / sum(1 / s for s in shares))
    assert 0 < got4 <= 105
    note = four.notes["roofline"]["sweep_rowmax"]
    # the width is what ran, not what the mix meant to send: the searches
    # answered over the passes counted (800 / 4), rounded up to a compiled
    # width; had the engine split each batch in two it would read 64
    assert note["width"] == 256 and note["passes"] == 4
    split = _roofline_window(events, 4, four_chips, answered=200)
    readers.read(spec, split)
    assert split.notes["roofline"]["sweep_rowmax"]["width"] == 64
    # what it read before this PR: all devices' regions over one device's
    # time, four times too high
    wrong = _roofline_window(events, 1, four_chips)
    assert readers.read(spec, wrong) == pytest.approx(4 * got4, rel=0.02)


def test_the_recorded_trace_holds_what_the_four_chip_readers_read():
    events = trace.load_events(RECORDED)
    busy = trace.busy_by_plane(events)
    assert len(busy) == 4 and all(v > 0 for v in busy.values())
    assert trace.busy_seconds(events) == pytest.approx(sum(busy.values()) / 4)
    w = _roofline_window(events, 4, {})
    m = Manifest(ROOT)
    skew = readers.read(m.metric_spec("chip_busy_skew.search"), w)
    assert 1.0 <= skew <= 4.0
    assert skew == pytest.approx(max(busy.values()) * 4 / sum(busy.values()))
    ms = readers.read(m.metric_spec("merge_topk_ms.search"), w)
    seconds, runs = trace.module_seconds(events, "_partition_merge_program")
    assert runs > 0 and ms == pytest.approx(1e3 * seconds / runs) and ms > 0
    host_only = [e for e in events if not e[0].startswith(trace.DEVICE_PLANE)]
    for name in ("chip_busy_skew.search", "merge_topk_ms.search"):
        with pytest.raises(readers.NothingToRead):
            readers.read(m.metric_spec(name),
                         _roofline_window(host_only, 4, {}))
