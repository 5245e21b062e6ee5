"""The reduction from trace and counters to metrics: on hand-made events
whose answers are known, on the small traces recorded on the chip, and on
windows that hold nothing of a reader's source."""


import numpy as np
import pytest

from benchmark import costs, readers, trace
from benchmark.manifest import ROOT, Manifest, load_kind

import bench_tiny

M = Manifest(ROOT)
DEV, HOST = "/device:TPU:0", "/host:CPU"


def sweep_cost(regions, qc):
    return load_kind(M.dir, "cost", "sweep_rowmax").cost(regions, qc)


def ev(name, start, dur, text="", plane=DEV, line="XLA Ops"):
    return [plane, line, name, float(start), float(dur), text]


HAND_MADE = [
    ev("%fusion.1 = f32[8] fusion(f32[8] %p)", 0, 100),
    ev("%sweep_rowmax.3 = (f32[6,8,32]) custom-call(f32[8,1] %copy.4)",
       50, 100),                                          # overlaps fusion.1
    ev("%sweep_rowmax.4 = (f32[6,8,32]) custom-call(f32[8,1] %copy.5)",
       400, 200),
    # a consumer names the kernel as its operand: not the kernel's time
    ev("%while.2 = f32[8] while(f32[6,8,32] %sweep_rowmax.4)", 390, 300),
    ev("jit_fused(123)", 0, 1000, line="XLA Modules"),    # not an op
    ev("PjitFunction(f)", 120, 260, plane=HOST, line="python3"),
    ev("lower_sharding_computation", 900, 50, plane=HOST, line="python3"),
]


def test_busy_is_the_union_of_op_intervals():
    # [0, 150) and [390, 690): nested and overlapping ops count once
    assert trace.busy_seconds(HAND_MADE) == pytest.approx(450e-9)


def test_kernel_time_sums_every_event_that_holds_the_name():
    seconds, n = trace.kernel_seconds(HAND_MADE, "sweep_rowmax")
    assert n == 2 and seconds == pytest.approx(300e-9)
    assert trace.kernel_seconds(HAND_MADE, "knn_int8_window_topc") == (0.0, 0)


def test_compiles_are_counted_from_the_host_plane():
    assert trace.count_host_events(HAND_MADE, "lower_sharding_computation") == 1
    assert trace.count_host_events(HAND_MADE[:4], "lower_sharding") == 0


def test_no_device_plane_reads_zero_busy():
    host_only = [e for e in HAND_MADE if e[0] == HOST]
    assert trace.busy_seconds(host_only) == 0.0
    assert trace.breakdown(host_only) == {"device_ops": [], "idle_gaps": []}


def test_module_line_stands_in_where_a_trace_has_no_op_line():
    modules = [e for e in HAND_MADE if e[1] == "XLA Modules"]
    assert trace.busy_seconds(modules) == pytest.approx(1000e-9)


def test_breakdown_names_ops_and_what_the_host_did_in_the_gaps():
    b = trace.breakdown(HAND_MADE)
    assert b["device_ops"][0] == ["%while.2", pytest.approx(300e-9)]
    # the one gap, [150, 390), has its middle inside the host's span
    assert b["idle_gaps"] == [["PjitFunction(f)", pytest.approx(240e-9)]]
    assert len(b["device_ops"]) <= 10


def test_seen_names_lists_what_a_refused_run_writes_down():
    rows = trace.seen_names(HAND_MADE)
    assert rows[0][0].startswith("%while.2 = ") and rows[0][2] == 1
    assert any(r[0].startswith("%sweep_rowmax.3") for r in rows)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return bench_tiny.tiny_manifest(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell", bench_tiny.CELLS)
def test_recorded_chip_traces_hold_their_kernel(tiny, cell):
    """A cell's recorded trace holds one device plane a chip and events
    of every kernel whose roofline share the cell declares."""
    events = trace.load_events(bench_tiny.recorded(cell))
    assert trace.device_planes(events) == [
        f"/device:TPU:{d}" for d in range(tiny.cell(cell).chips)]
    busy = trace.busy_seconds(events)
    kernels = [spec["match"] for spec in (
        tiny.metric_spec(m["name"]) for m in tiny.declared(cell, 1))
        if spec["kind"] == "kernel_roofline"]
    for kernel in kernels:
        seconds, n = trace.kernel_seconds(events, kernel)
        assert n > 0 and 0 < seconds <= busy, (cell, kernel)
    span = (max(e[3] + e[4] for e in events)
            - min(e[3] for e in events)) / 1e9
    assert busy <= span
    b = trace.breakdown(events)
    assert b["device_ops"] and len(b["idle_gaps"]) <= 10


def _window(**over):
    hist = {"count": 0, "buckets": 0, "mean": 0.0}
    steps = ("prep", "launch", "device_wait", "finish", "rescore",
             "slice_build", "sparse_gather", "mask", "dense_rerun")
    stats = {
        "tpu_search_latency": {
            "device": dict(hist), "demux": dict(hist), "fetch": dict(hist),
            "coalesce_batch_size": dict(hist),
            "queue_wait.search": dict(hist),
            "sched_tier_wait.interactive": dict(hist),
            **{"dispatch." + s: dict(hist) for s in steps}},
        "tpu_knn": {"knn_uncertified": 0, "knn_queries": 0,
                    "knn_int8_dispatches": 0},
        "tpu_turbo": {"fused_dispatches": 0, "merge_device": 0,
                      "merge_host": 0},
        "tpu_scheduler": {"lane_idle_ms": 0.0},
        "tpu_compile": {"misses": 4, "retraces": 1, "jit_builds": 0,
                        "jit_build_ms": 0.0},
        "jvm": {"gc": {"collectors": {"old": {
            "collection_time_in_millis": 0}}}},
        "tpu_hbm": {"engines": {}},
    }
    kw = dict(config={"index": {"segments": 3}}, traffic={}, seconds=1.0,
              setup_s=2.0, latency_ms=np.zeros(0), late_ms=np.zeros(0),
              queries_done=0, stats_before=stats,
              stats_after=stats, memory_peak_bytes=0,
              device_kind="TPU v5 lite", events=[])
    kw.update(over)
    w = readers.Window(**kw)
    w.notes.update(busy_s=0.25, window_s=1.0, done_s=np.asarray([0.5]))
    return w


def test_every_counter_and_histogram_reader_is_total():
    """A window with zero events of every source: each reader still
    returns a number (0.0 for an empty histogram or ratio). The readers
    of the device trace, and of the chips' peaks where no device reports
    one, are the exception: see the next test."""
    w = _window()
    names = [m["name"] for m in M.doc["end_to_end"] + M.doc["per_layer"]]
    for name in names:
        spec = M.metric_spec(name)
        if spec["kind"] in ("kernel_roofline", "busy_skew",
                            "module_mean_ms", "peak_skew"):
            with pytest.raises(readers.NothingToRead):
                readers.read(spec, w)
            continue
        value = readers.read(spec, w)
        assert isinstance(value, float) and np.isfinite(value), name
        if spec["kind"] in ("histogram_mean", "counter_ratio", "client_rate",
                            "counter_delta", "trace_event_count"):
            assert value == 0.0, name
    assert readers.read(M.metric_spec("device_idle_pct.search"), w) == 75.0


def test_a_roofline_share_is_never_zero_it_has_nothing_to_read():
    w = _window(events=HAND_MADE)
    for name in ("sweep_roofline_pct.search", "knn_pass_roofline_pct.search"):
        with pytest.raises(readers.NothingToRead):
            readers.read(M.metric_spec(name), w)


def test_histogram_mean_is_the_windows_own_mean():
    before = _window().stats_before
    after = {**before, "tpu_search_latency": {
        **before["tpu_search_latency"],
        "device": {"count": 10, "mean": 4.0}}}
    before["tpu_search_latency"]["device"] = {"count": 2, "mean": 20.0}
    w = _window(stats_before=before, stats_after=after)
    # (10 * 4 - 2 * 20) / 8: the warm-up's two slow dispatches drop out
    assert readers.read(M.metric_spec("dispatch_ms.search"), w) == 0.0
    after["tpu_search_latency"]["device"] = {"count": 10, "mean": 8.0}
    assert readers.read(M.metric_spec("dispatch_ms.search"), w) == 5.0


def test_roofline_arithmetic_on_known_shapes():
    regions = {"cols_hi": 819_000_000, "cols_lo": 819_000_000, "live": 0}
    least, bound = costs.least_seconds(sweep_cost, regions, 8,
                                       "TPU v5 lite")
    assert bound == "memory" and least == pytest.approx(2e-3)
    # at 256 queries a pass the same sweep is bound by the int8 peak: each
    # int8 cell meets both int8 halves of each query weight (the kernel's
    # four s8 x s8 products: hi.hi, hi.lo, lo.hi, lo.lo)
    least, bound = costs.least_seconds(sweep_cost, regions, 256,
                                       "TPU v5 lite")
    assert bound == "compute"
    assert least == pytest.approx(2 * 2 * 256 * 1.638e9 / 393e12)
    assert costs.least_seconds(sweep_cost, regions, 64,
                               "TPU v5 lite")[1] == "memory"
    with pytest.raises(KeyError, match="no peaks"):
        costs.least_seconds(sweep_cost, regions, 8, "cpu")


def test_kernel_roofline_reads_trace_counter_and_ledger_together():
    before = _window().stats_before
    after = {**before,
             "tpu_turbo": {"fused_dispatches": 2},
             "tpu_hbm": {"engines": {"fused_turbo-5": {
                 "kind": "fused_turbo", "regions": {
                     "cols_hi": 40_950, "cols_lo": 40_950, "live": 0}}}}}
    w = _window(stats_before=before, stats_after=after, events=HAND_MADE)
    # least time of a pass: 81,900 B / 819 GB/s = 100 ns; 2 passes in 300 ns
    got = readers.read(M.metric_spec("sweep_roofline_pct.search"), w)
    assert got == pytest.approx(100.0 * 200 / 300)
    assert w.notes["roofline"]["sweep_rowmax"]["bound"] == "memory"
