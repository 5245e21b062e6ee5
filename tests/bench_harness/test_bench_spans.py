"""PR 27's spans, seen through the benchmark's own eyes, at tiny size on
the CPU: the thirteen metric files that read the new histograms and
counters (each names a reader kind that exists and a path a fresh node
has; a traced line that holds them passes the manifest's check; the four
top-level steps add up to `dispatch_ms.search`), and a window under a real
`jax.profiler` session with the options benchmark/run.py sets, loaded with
`trace.load_xplane`: the program's `es.*` spans sit on the host plane,
children inside parents, a lane is never idle and dispatching at once,
and `trace.breakdown` names them in `idle_gaps`.

The thirteen `per_layer` entries waited in a file of their own until PR
29 (a traced run of a parent without the paths would have died); they are
in BENCHMARK.json now, as they stood. The held four-chip cell
(bench_tiny.py) sends `_msearch` batches that skip the scheduler's lane:
the lane's metric files say `"loops": ["open"]`, so it declares the
dispatch's steps and none of the lane's.
"""

import json
import os

import pytest

from benchmark import readers, run, trace, validate
from benchmark.compare import dotted
from benchmark.manifest import ROOT, Manifest

import bench_tiny

SEED = 1556403449
CELLS = bench_tiny.CELLS      # the accepted cells and the held one
BM25, KNN, FOUR = CELLS
CHIP = {"platform": "tpu", "kind": "TPU v5 lite"}
TOP = ("prep_ms.search", "launch_ms.search", "device_wait_ms.search",
       "finish_ms.search")
NEW = {
    BM25: TOP + ("rescore_ms.search", "slice_build_ms.search",
                 "sparse_gather_ms.search", "lane_idle_ms.search",
                 "jit_builds.search", "jit_build_ms.search",
                 "gc_old_ms.search"),
    KNN: TOP + ("rescore_ms.search", "mask_ms.search",
                "dense_rerun_ms.search", "lane_idle_ms.search",
                "jit_builds.search", "jit_build_ms.search",
                "gc_old_ms.search"),
    FOUR: TOP + ("rescore_ms.search", "slice_build_ms.search",
                 "sparse_gather_ms.search", "jit_builds.search",
                 "jit_build_ms.search", "gc_old_ms.search"),
}
ALL_NEW = sorted(set(NEW[BM25]) | set(NEW[KNN]))
TOP_SPANS = ("es.dispatch.prep", "es.dispatch.launch",
             "es.dispatch.device_wait", "es.dispatch.finish")


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    root = bench_tiny.tiny_root(str(tmp_path_factory.mktemp("tiny")))
    # a rate the CPU backend keeps up with (its kernels are interpreted):
    # the lane has to come to rest between dispatches to be seen idle
    tdir = os.path.join(root, "benchmark", "traffic")
    for name in os.listdir(tdir):
        with open(os.path.join(tdir, name)) as f:
            t = json.load(f)
        if t["loop"] == "open":
            t["rate_per_s"] = 4
        with open(os.path.join(tdir, name), "w") as f:
            json.dump(t, f)
    return Manifest(root)


@pytest.fixture(scope="module")
def benches(manifest, tmp_path_factory):
    mp = pytest.MonkeyPatch()
    bench_tiny.steer_engines(mp, str(tmp_path_factory.mktemp("jax_cache")))
    made = {}

    def get(cell):
        if cell not in made:
            b = run.Bench(manifest, cell, require_chip=False,
                          out_dir=str(tmp_path_factory.mktemp("out")))
            # the device the recorded trace was taken on
            b.dev = dict(CHIP, count=b.cell.chips)
            b.setup(SEED)
            made[cell] = b
        return made[cell]

    yield get
    for b in made.values():
        b.close()
    mp.undo()


def test_the_manifest_declares_the_new_metrics_for_their_cells(manifest):
    for cell in CELLS:
        declared = [m["name"] for m in manifest.declared(cell, 1)]
        assert set(NEW[cell]) <= set(declared)
        other = set(ALL_NEW) - set(NEW[cell])
        assert not other & set(declared)
    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    for name in ALL_NEW:
        m = by_name[name]
        assert m["moves"] == "search_p50_ms"
        kind = manifest.metric_spec(name)["kind"]
        assert m["source"] == {"histogram_mean": "program_span",
                               "counter_delta": "program_counter"}[kind]
    assert by_name["gc_old_ms.search"]["layer"] == "interpreter"


@pytest.mark.parametrize("name", ALL_NEW)
def test_a_new_metric_file_names_a_kind_and_a_path_a_fresh_node_has(
        benches, manifest, name):
    spec = manifest.metric_spec(name)
    assert spec["kind"] in readers.KINDS
    fresh = benches(BM25).stats0          # read before anything was indexed
    for path in spec.get("paths", [spec.get("path")]):
        got = dotted(fresh, path)
        if spec["kind"] == "histogram_mean":
            assert {"count", "mean"} <= set(got)
        else:
            assert isinstance(got, (int, float))


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_line_holds_them_and_the_steps_add_up(benches, manifest,
                                                       cell, capsys):
    bench = benches(cell)
    recorded = trace.load_events(os.path.join(
        ROOT, "benchmark", "testdata", f"trace_{cell}.json"))
    w = bench.window(SEED, 2.5, 1, events=recorded)
    line, rc = bench.report(w, 1)
    assert rc == 0 and line is not None, capsys.readouterr().err[-2000:]
    assert validate.line_faults(line, manifest, cell, 1) == []
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW[cell]) <= set(got)
    dispatch = got["dispatch_ms.search"]
    steps = sum(got[name] for name in TOP)
    assert dispatch > 0
    assert abs(steps - dispatch) <= 0.1 * dispatch, (steps, dispatch, got)
    # children sum under their parents
    assert got["rescore_ms.search"] <= got["finish_ms.search"]
    if cell in (BM25, FOUR):
        assert got["slice_build_ms.search"] <= got["prep_ms.search"]
        assert got["sparse_gather_ms.search"] <= got["finish_ms.search"]
    else:
        assert got["mask_ms.search"] <= got["prep_ms.search"]
        assert got["dense_rerun_ms.search"] <= got["finish_ms.search"]
        assert got["mask_ms.search"] > 0         # half the mix is filtered
    # the lane is never parked for more than the window (one lane a cell;
    # how long it IS parked depends on this machine's load: the profiled
    # test below makes it rest)
    if cell != FOUR:
        assert 0 <= got["lane_idle_ms.search"] <= \
            1e3 * w.notes["window_s"] * 1.05
    assert got["jit_builds.search"] >= 0 and got["gc_old_ms.search"] >= 0


def _inside(child, parents) -> bool:
    return any(p[0] == child[0] and p[1] == child[1]
               and p[3] <= child[3] and child[3] + child[4] <= p[3] + p[4]
               for p in parents)


def profiled(bench, n: int) -> list:
    """The compact events of a real `jax.profiler` session (the options
    benchmark/run.py sets) around n of the cell's own requests, sent one
    after another with a rest between them: however loaded this machine
    is, the lane comes to rest on its empty queue n times."""
    import time

    import jax

    from benchmark.traffic import Mix

    mix = Mix(bench.cell.traffic, bench.cell.config, SEED, bench.parts)
    calls = [mix.call(mix.warm()) for _ in range(n)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(bench.trace_dir, profiler_options=opts)
    try:
        for path, data, _req in calls:
            bench.node.request("POST", path, json.loads(data))
            time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    return trace.load_xplane(trace.newest_xplane(bench.trace_dir))


@pytest.mark.parametrize("cell", CELLS)
def test_a_profiled_span_of_traffic_holds_the_programs_spans(benches, cell):
    """A real `jax.profiler` session around live traffic (the CPU backend
    has no device plane: the host plane is what is under test)."""
    bench = benches(cell)
    idle0 = bench.node.stats()["tpu_scheduler"]["lane_idle_ms"]
    events = profiled(bench, 8)
    assert bench.node.stats()["tpu_scheduler"]["lane_idle_ms"] > idle0
    host = [e for e in events if e[2].startswith("es.")]
    by_name = {}
    for e in host:
        by_name.setdefault(e[2], []).append(e)
    devices = by_name.get("es.device", [])
    assert devices, sorted(by_name)
    for name in TOP_SPANS:
        assert by_name.get(name), (name, sorted(by_name))
    assert by_name.get("es.sched.idle"), sorted(by_name)
    assert by_name.get("es.rest_total") and by_name.get("es.demux")
    assert "es.device.fused_chunk" not in by_name
    # children lie inside parents, on the thread that ran the dispatch
    for name in TOP_SPANS:
        for e in by_name[name]:
            assert _inside(e, devices), (name, e)
    finishes = by_name["es.dispatch.finish"]
    for name in ("es.dispatch.rescore", "es.dispatch.sparse_gather",
                 "es.dispatch.dense_rerun"):
        for e in by_name.get(name, ()):
            assert _inside(e, finishes), (name, e)
    assert by_name.get("es.dispatch.rescore")
    # the four top-level steps cover the dispatch
    covered = sum(e[4] for name in TOP_SPANS for e in by_name[name])
    assert covered >= 0.9 * sum(e[4] for e in devices)
    # a lane is parked on its empty queue or dispatching, never both
    for idle in by_name["es.sched.idle"]:
        for d in devices:
            if (d[0], d[1]) == (idle[0], idle[1]):
                assert idle[3] + idle[4] <= d[3] or d[3] + d[4] <= idle[3]
    # a synthetic device plane, busy from every launch to the end of its
    # fetch (on the CPU backend the "device" runs on host threads, whose
    # events are not what a chip's host plane holds: the program's spans
    # are what `breakdown` is given): every gap is the program's to name,
    # and the rests between requests are the lane's
    plane = "/device:TPU:0"
    synthetic = [[plane, "XLA Ops", "%op.1 = f32[8]{0} fusion()",
                  e[3], w[3] + w[4] - e[3], ""]
                 for e, w in zip(sorted(by_name["es.dispatch.launch"],
                                        key=lambda e: e[3]),
                                 sorted(by_name["es.dispatch.device_wait"],
                                        key=lambda e: e[3]))]
    gaps = trace.breakdown(synthetic + host)["idle_gaps"]
    assert gaps and all(name.startswith("es.") for name, _s in gaps), gaps
    assert "es.sched.idle" in [name for name, _s in gaps], gaps


@pytest.mark.parametrize("cell", (BM25, KNN))
def test_one_batch_of_one_costs_at_most_25_spans(benches, cell):
    """The hot path is guarded by COUNT, not by a timing: one traced
    request alone on the node is one batch-1 dispatch. (The cells that
    send single requests: over twelve partitions the same dispatch holds
    43 spans, a few a partition, and the four-chip cell sends batches.)"""
    from benchmark.traffic import Mix
    from elasticsearch_tpu.common import tracing

    bench = benches(cell)
    mix = Mix(bench.cell.traffic, bench.cell.config, SEED, bench.parts)
    worst = 0
    for j in range(4):                  # kNN: unfiltered and both filters
        path, data, _req = mix.call(mix.warm())
        body = dict(json.loads(data), profile=True)
        resp = bench.node.request("POST", path, body)
        tid = resp["profile"]["tpu"]["trace_id"]
        spans = next(t["spans"] for t in reversed(tracing.recent_traces())
                     if t["trace_id"] == tid)
        by_id = {s["id"]: s for s in spans}
        device = [s for s in spans if s["name"] == "device"]
        assert len(device) == 1 and device[0]["meta"]["batch"] == 1

        def under_device(s):
            while s is not None:
                if s["id"] == device[0]["id"]:
                    return True
                s = by_id.get(s["parent"])
            return False

        n = sum(1 for s in spans if under_device(s))
        assert {"dispatch.prep", "dispatch.launch", "dispatch.device_wait",
                "dispatch.finish"} <= {s["name"] for s in spans
                                       if under_device(s)}
        worst = max(worst, n)
        # the request's phases are a partition of it: nothing counted twice
        phases = resp["profile"]["tpu"]["phases"]
        rest = next(s for s in spans if s["name"] == "rest_total")
        assert sum(phases.values()) <= rest["duration_ms"] * 1.001 + 0.01
    assert worst <= 25, worst
