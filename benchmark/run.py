"""One run of one cell:

    python3 -m benchmark --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, the cell's chips: start the node as `python -m elasticsearch_tpu`
does, install the seeded corpus, warm the cell's own shapes (all of that
is `setup_s`), drive the window over HTTP from this process's threads,
then free the node and hold a sample of what the window's own requests
returned against the plain reference. The last line of standard output
is the result, built by walking the manifest's metric list for the cell
and checked against the manifest before it is printed.
"""

from __future__ import annotations

import argparse
import gc
import http.client
import json
import os
import shutil
import sys
import threading
import time
from typing import List, Optional

import numpy as np

from benchmark import compare, datagen, loadgen, readers, trace, validate
from benchmark.manifest import ROOT, Cell, Manifest, ManifestError
from benchmark.traffic import Mix

WARM_TIMEOUT_S = 900.0   # a warm-up call may compile

# what JAX says it traced, lowered and compiled (or loaded from its cache),
# kept for `Stalls`: (event, seconds). One listener for the process.
_BUILDS: List[tuple] = []


def _listen_for_builds() -> None:
    import jax.monitoring

    if not _BUILDS:
        _BUILDS.append(("listening", 0.0))
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **kw: _BUILDS.append((event, secs))
            if event.startswith("/jax/core/compile/") else None)


def log(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def find_devices(chips: int, require_chip: bool) -> Optional[dict]:
    """The device as JAX reports it, or None when it is not the chip the
    cell asks for (the run then ends non-zero without a result)."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if not require_chip:
        return dev
    if dev["platform"] != "tpu":
        log(f"JAX found {dev}: this benchmark measures the TPU path and "
            "does not run without one")
        return None
    if dev["count"] != chips:
        log(f"the cell asks for {chips} chip(s), JAX sees {dev['count']}")
        return None
    return dev


def memory_peaks() -> List[int]:
    """`peak_bytes_in_use` of every local device, in device order."""
    import jax

    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.local_devices()]


class Stalls:
    """What held this whole process (node and clients alike) inside the
    window, written to standard error and into no metric: the
    interpreter's collector (a full collection walks the millions of
    objects a 1.1M-document shard keeps on the host), and gaps in a
    heartbeat thread that only sleeps 20 ms at a time — a late beat means
    the interpreter lock or the machine's cores were not to be had, which
    no span of the program names — and the programs JAX built or loaded
    (a shape the warm-up did not reach), by JAX's own monitoring events."""

    BEAT_S = 0.02
    GAP_MS = 300         # a heartbeat gap over this is a freeze, not jitter

    def __init__(self):
        self.t_gc = 0.0
        self.gc_ms: List[float] = []
        self.gc_full_ms: List[float] = []      # generation 2 only
        self.late_beats: List[tuple] = []      # (seconds into window, ms)
        self._stop = threading.Event()
        self.t0 = time.monotonic()
        _listen_for_builds()
        self.builds0 = len(_BUILDS)
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._beat, daemon=True)
        self._thread.start()

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.t_gc = time.monotonic()
        else:
            self.gc_ms.append((time.monotonic() - self.t_gc) * 1e3)
            if info.get("generation") == 2:
                self.gc_full_ms.append(self.gc_ms[-1])

    def _beat(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(self.BEAT_S):
            now = time.monotonic()
            if now - last > 0.1:
                self.late_beats.append((round(last - self.t0, 2),
                                        round((now - last) * 1e3)))
            last = now

    def stop(self, calls) -> dict:
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)
        log(f"gc in the window: {len(self.gc_ms)} collections, "
            f"{sum(self.gc_ms):.0f} ms in all, longest "
            f"{max(self.gc_ms, default=0.0):.0f} ms; heartbeat gaps over "
            f"100 ms (at s, ms): {self.late_beats[:12]}")
        builds = _BUILDS[self.builds0:]
        backend = [s for e, s in builds if e.endswith("backend_compile_duration")]
        log(f"programs built or loaded in the window: {len(backend)}, "
            f"{sum(backend):.2f} s in the backend (compiled, or read from "
            f"the cache), {sum(s for _, s in builds) - sum(backend):.2f} s "
            f"traced and lowered")
        slow = sorted(calls, key=lambda s: s.due - s.done)[:10]
        log("slowest calls (due s, ms): " + str(sorted(
            (round(s.due, 2), round((s.done - s.due) * 1e3)) for s in slow)))
        # the same, as numbers a table can be made of (the result line's
        # `stalls`, which the driver ignores): what a whole-window rate
        # carries and a median call does not
        took = [(s.done - s.due) * 1e3 for s in calls] or [0.0]
        summary = {
            "calls": len(calls),
            "call_mean_ms": float(np.mean(took)),
            "call_p50_ms": float(np.median(took)),
            "beat_gap_s": sum(ms for _, ms in self.late_beats
                              if ms > self.GAP_MS) / 1e3,
            "gc_full_s": sum(self.gc_full_ms) / 1e3,
            "builds": len(backend),
            "build_s": sum(s for _, s in builds)}
        if len(took) <= 200:         # a closed loop's calls, one by one
            summary["call_ms"] = [round(t) for t in took]
        log("stalls in the window: " + json.dumps(summary))
        return summary


class Node:
    """The system under test, seen from a client in the same process."""

    def __init__(self, env: Optional[dict] = None):
        from elasticsearch_tpu.__main__ import start_node

        # the configuration's operator settings (declared ES_TPU_* knobs),
        # put back when the node closes
        self._env_before = {k: os.environ.get(k) for k in (env or {})}
        os.environ.update({k: str(v) for k, v in (env or {}).items()})
        self.node, self.server = start_node(port=0, name="bench-node")
        self.port = self.server.port

    def request(self, method: str, path: str, body=None) -> dict:
        c = http.client.HTTPConnection("127.0.0.1", self.port, timeout=600)
        try:
            c.request(method, path,
                      body=None if body is None else json.dumps(body).encode(),
                      headers={"Content-Type": "application/json"})
            resp = c.getresponse()
            raw = resp.read()
            if resp.status >= 300:
                raise RuntimeError(f"{method} {path} -> HTTP {resp.status}: "
                                   f"{raw[:300]!r}")
            return json.loads(raw) if raw else {}
        finally:
            c.close()

    def stats(self) -> dict:
        nodes = self.request("GET", "/_nodes/stats")["nodes"]
        return next(iter(nodes.values()))

    def close(self) -> None:
        self.server.stop()
        self.node.close()
        for k, v in self._env_before.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def install_corpus(node: Node, cell: Cell, seed: int):
    """Create the index and install the seeded corpus; returns the
    corpus kind's parts (the mix and the reference read them)."""
    from benchmark import install

    cfg = cell.config
    idx = cfg["index"]
    kind = cell.corpus_kind
    node.request("PUT", "/" + idx["name"], {
        "settings": {"number_of_shards": int(idx.get("shards", 1)),
                     "number_of_replicas": 0},
        "mappings": idx["mappings"]})
    parts = kind.make_parts(cfg, seed)
    n = install.install(node.node, cfg, parts, kind.segment)
    got = node.request("GET", f"/{idx['name']}/_count")["count"]
    if got != n:
        raise RuntimeError(f"installed {n} documents, _count says {got}")
    return parts


def warm_up(node: Node, mix: Mix) -> None:
    """Every shape the window will use, and no other, all on set-up's own
    requests (`Mix.warm`: the upper half of the mix's pool, which no
    window sends), as benchmark/traffic/<mix>.json says under `warmup`:

    `prefill` calls, `prefill_threads` at a time: the engine's caches
    reach the state a node that has served for a while is in (a text
    index builds a term's dense column or sparse slice when a query first
    names it; the pool of slices doubles as it fills, and every doubling
    is a new shape for the gather and update programs, so a text mix
    prefills until the pool is at its cap).

    Then, for every request variant of the mix (kNN: with and without a
    filter, which are different kernels) and every scheduler bucket in
    `buckets`, bursts of concurrent calls until the scheduler's own
    counters say a batch of that bucket was dispatched: a bucket is a
    compiled shape, and one that first occurs inside the window compiles
    there (my chip run 1, PR 26: 21 s in the kNN cell).

    Last, `lead_in_s` seconds of the mix's own arrivals at its own rate:
    what only live traffic instantiates happens before the window.

    A closed mix has a warm-up of its own: `warm_up_closed`."""
    warm = mix.t.get("warmup", {})
    if mix.closed:
        return warm_up_closed(node, mix, int(warm.get("calls", 1)))
    lock = threading.Lock()

    def one(conn, variant=None):
        with lock:
            j = mix.warm(variant)
        path, data, _req = mix.call(j)
        status, raw = conn.post(path, data)
        if status != 200 or mix.responses(raw, 1) is None:
            raise RuntimeError(f"warm-up call {path} -> HTTP {status}: "
                               f"{raw[:300]!r}")

    def together(n_threads: int, n_calls: int, variant=None):
        errs: List[BaseException] = []
        left = [n_calls]

        def work():
            conn = loadgen.Conn(node.port, timeout=WARM_TIMEOUT_S)
            try:
                while True:
                    with lock:
                        if left[0] <= 0:
                            return
                        left[0] -= 1
                    one(conn, variant)
            except BaseException as e:   # noqa: BLE001 — re-raised below
                errs.append(e)
            finally:
                conn.close()

        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise errs[0]

    def bucket_count(b: int) -> int:
        counts = node.stats()["tpu_scheduler"]["bucket_counts"]
        return int(counts.get(str(b), 0))

    t0 = time.monotonic()
    together(1, int(warm.get("calls", 4)))
    t1 = time.monotonic()
    together(int(warm.get("prefill_threads", 8)), int(warm.get("prefill", 0)))
    t2 = time.monotonic()
    for variant in mix.variants():
        for b in warm.get("buckets", ()):
            before = bucket_count(b)
            for attempt in range(int(warm.get("tries", 8))):
                # the first flush takes whoever has arrived, the next one
                # the rest: widths around b leave a rest that pads to b
                width = 1 if b == 1 else max(
                    2, b + (attempt + 1) // 2 * (1 if attempt % 2 else -1))
                together(width, width, variant)
                if bucket_count(b) > before:
                    break
            else:
                raise RuntimeError(
                    f"warm-up never saw a batch of bucket {b} "
                    f"(variant {variant}): "
                    f"{node.stats()['tpu_scheduler']['bucket_counts']}")
    t3 = time.monotonic()
    lead_s = float(warm.get("lead_in_s", 0))
    lead = loadgen.open_loop(node.port, mix, mix.lead_in(lead_s)) \
        if lead_s > 0 else []
    bad = [s for s in lead if s.responses(mix) is None]
    if bad:
        raise RuntimeError(f"lead-in: {len(bad)} of {len(lead)} calls "
                           f"unanswered, first HTTP {bad[0].status}")
    log(f"warm-up: first calls {t1 - t0:.1f} s, prefill {t2 - t1:.1f} s, "
        f"buckets {t3 - t2:.1f} s, lead-in {time.monotonic() - t3:.1f} s "
        f"({len(lead)} calls); HBM regions now "
        f"{readers.engine_regions(node.stats())}")


def warm_up_closed(node: Node, mix: Mix, calls: int) -> None:
    """`calls` `_msearch` calls of the mix's own batch, one after another,
    on set-up's requests, and nothing else: a batch over the scheduler's
    `SMALL_BATCH_MAX` skips the lane, so there is no bucket to see, and
    its width is the one shape the window uses. The log gives each
    call's seconds and the engines' regions after it: `calls` is as many
    as it takes for the regions to stop growing (a text index's pool of
    slices doubles as it fills), and no more."""
    took, regions = [], []
    conn = loadgen.Conn(node.port, timeout=WARM_TIMEOUT_S)
    try:
        for _ in range(calls):
            t0 = time.monotonic()
            path, data, reqs = mix.msearch(
                [mix.warm() for _ in range(mix.batch)])
            status, raw = conn.post(path, data, ndjson=True)
            if status != 200 or mix.responses(raw, len(reqs)) is None:
                raise RuntimeError(f"warm-up call {path} -> HTTP {status}: "
                                   f"{raw[:300]!r}")
            took.append(round(time.monotonic() - t0, 1))
            regions.append(readers.engine_regions(node.stats()))
    finally:
        conn.close()
    log(f"warm-up: {calls} calls of {mix.batch}, s each {took}; HBM "
        f"regions after each {regions}")


def answered(calls, mix: Mix) -> tuple:
    """(calls answered, [(served response, Request)] of every search in
    them, in call order). A call is answered when it got a 200 with a
    well-formed response to each of its requests."""
    n, done = 0, []
    for s in calls:
        resps = s.responses(mix)
        if resps is not None:
            n += 1
            done.extend(zip(resps, s.requests))
    return n, done


def sample_pairs(cell: Cell, done: list, seed: int, parts, precision=None):
    """(served response, reference answer) of a seeded sample of the
    searches the window answered (`answered`), the reference (the
    request kind's; `precision` = its lower-precision control) computed
    now."""
    cfg = cell.config
    if not done:
        return []
    rng = datagen.rng_for(seed, datagen.STREAM_SAMPLE)
    pick = rng.choice(len(done), replace=False,
                      size=min(int(cfg["limits"]["sample"]), len(done)))
    chosen = [done[int(i)] for i in pick]
    ref = cell.request_kind.reference(cfg, parts, precision)
    answers = ref.answers([q for _, q in chosen], top_k(cell))
    return [(resp, a) for (resp, _), a in zip(chosen, answers)]


def top_k(cell: Cell) -> int:
    return int(cell.request_kind.top_k(cell.traffic["request"]))


def verdict(cell: Cell, pairs, stats0: dict, stats1: dict,
            stats2: dict) -> dict:
    """`compare.verdict` with the cell's own comparison: name ->
    {value, limit, ok}."""
    return compare.verdict(pairs, cell.request_kind.numbers, cell.config,
                           stats0, stats1, stats2, top_k(cell))


class Bench:
    """One cell's run in three steps: `setup` (node, corpus, warm-up),
    `window` (the timed traffic, optionally traced) and `report` (the
    result line: every declared metric, the comparison, the check
    against the manifest). `run_cell` is the three in order."""

    def __init__(self, manifest: Manifest, workload: str, *,
                 require_chip: bool = True, out_dir: Optional[str] = None):
        self.manifest = manifest
        self.workload = workload
        self.cell = manifest.cell(workload)
        # an unknown kind ends here, before a node is started
        self.cell.corpus_kind, self.cell.request_kind
        self.dev = find_devices(self.cell.chips, require_chip)
        self.out_dir = out_dir or os.path.join(manifest.root, ".bench_out")
        self.trace_dir = os.path.join(self.out_dir, "trace", workload)
        self.node: Optional[Node] = None

    def setup(self, seed: int, t_start: Optional[float] = None) -> None:
        t_start = time.monotonic() if t_start is None else t_start
        self.seed = seed
        self.node = Node(self.cell.config.get("env"))
        self.stats0 = self.node.stats()
        self.parts = install_corpus(self.node, self.cell, seed)
        t_inst = time.monotonic()
        warm_up(self.node, Mix(self.cell, seed, self.parts))
        self.setup_s = time.monotonic() - t_start
        built = {k: round(v["count"] * v["mean"] / 1e3, 1) for k, v in
                 self.node.stats()["tpu_search_latency"].items()
                 if k.startswith("engine_build.") and v["count"]}
        log(f"set-up {self.setup_s:.1f} s (installed at "
            f"{t_inst - t_start:.1f} s, warm-up "
            f"{time.monotonic() - t_inst:.1f} s; of it the program's "
            f"engine build, s by step: {built})")

    def window(self, seed: int, seconds: float, trace_on: int,
               events: Optional[List[list]] = None,
               first: int = 0) -> readers.Window:
        """Drive the cell's traffic for `seconds`. With `trace_on` the
        whole window is traced; `events` (tests only: a recorded trace)
        stands in for the profiler where there is no device to trace;
        `first` (the rate sweep: one node, many windows) is the canonical
        request the window starts from."""
        node, cell = self.node, self.cell
        mix = Mix(cell, seed, self.parts)
        sched = None if mix.closed else mix.window(seconds, first)
        profile = bool(trace_on) and events is None
        stats1 = node.stats()
        t0 = time.monotonic()
        if profile:
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            t0 = time.monotonic()
        stalls = Stalls()
        calls: list = []
        try:
            calls = (loadgen.closed_loop(node.port, mix, seconds, first)
                     if mix.closed
                     else loadgen.open_loop(node.port, mix, sched))
        finally:
            span_s = time.monotonic() - t0
            stalled = stalls.stop(calls)
            if profile:
                jax.profiler.stop_trace()
        stats2 = node.stats()
        if not calls:
            raise RuntimeError("the window sent no call")
        n_answered, done = answered(calls, mix)
        lat = np.asarray([(s.done - s.due) * 1e3 for s in calls])
        head = lat[np.asarray([s.due for s in calls]) < 0.8 * seconds]
        peaks = memory_peaks()
        log(f"window: {len(calls)} calls, {n_answered} answered "
            f"({len(done)} searches); ms from due to answer: p50 "
            f"{np.percentile(lat, 50):.2f}, p95 {np.percentile(lat, 95):.2f}, "
            f"p50 of the first four fifths {np.percentile(head, 50):.2f}; "
            f"last answer at {max(s.done for s in calls):.2f} s; HBM regions "
            f"now {readers.engine_regions(stats2)}; peak bytes per device "
            f"{peaks}")
        w = readers.Window(
            config=cell.config, traffic=cell.traffic, seconds=float(seconds),
            setup_s=self.setup_s,
            latency_ms=lat,
            late_ms=np.asarray([(s.sent - s.due) * 1e3 for s in calls]),
            queries_done=len(done),
            stats_before=stats1, stats_after=stats2,
            memory_peak_bytes=max(peaks, default=0),
            device_kind=self.dev["kind"], kinds_dir=self.manifest.dir)
        w.notes.update(attempted=len(calls), calls_answered=n_answered,
                       answered=done, seed=seed, memory_peaks=peaks,
                       stalls=stalled,
                       done_s=np.asarray([s.done for s in calls]))
        if trace_on:
            w.events = events if events is not None else trace.load_xplane(
                trace.newest_xplane(self.trace_dir))
            w.notes["busy_s"] = trace.busy_seconds(w.events)
            w.notes["window_s"] = span_s
        return w

    def close(self) -> None:
        """Stop the node and drop the program's state: the reference runs
        after this, so it never sets the device's peak."""
        if self.node is not None:
            self.node.close()
            self.node = None
            gc.collect()

    def report(self, w: readers.Window, trace_on: int):
        """(result line or None, exit code). The metrics are built by
        walking the manifest's list for the cell and trace mode."""
        attempted = w.notes["attempted"]
        device = dict(self.dev, memory_peak_bytes=w.memory_peak_bytes,
                      memory_peak_bytes_per_device=w.notes["memory_peaks"])
        line = {"correct": False, "attempted": attempted,
                "failed": attempted - w.notes["calls_answered"],
                "metrics": {}, "device": device}
        if trace_on:
            device["busy_s"] = w.notes["busy_s"]
            device["window_s"] = w.notes["window_s"]
            line["breakdown"] = trace.breakdown(w.events)
        problems: List[str] = []
        for m in self.manifest.declared(self.workload, trace_on):
            try:
                value = readers.read(self.manifest.metric_spec(m["name"]), w)
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
            except readers.NothingToRead as e:
                problems.append(f"{m['name']}: {e}")
        if w.notes.get("roofline"):
            line["roofline"] = w.notes["roofline"]
        line["stalls"] = w.notes["stalls"]
        if problems and trace_on:
            # an earlier line and a file beside the trace say what WAS seen
            seen = trace.seen_names(w.events or [])
            os.makedirs(self.trace_dir, exist_ok=True)
            with open(os.path.join(self.trace_dir, "seen_names.json"),
                      "w") as f:
                json.dump(seen, f, indent=1)
            log("device events seen: " + json.dumps(seen[:20]))

        t_ref = time.monotonic()
        pairs = sample_pairs(self.cell, w.notes["answered"], w.notes["seed"],
                             self.parts)
        checked = verdict(self.cell, pairs, self.stats0, w.stats_before,
                          w.stats_after)
        log(f"reference and comparison took {time.monotonic() - t_ref:.1f} s")
        line["correct"] = compare.is_correct(checked)
        line["checked"] = checked        # last: each number beside its limit

        problems += validate.line_faults(line, self.manifest, self.workload,
                                         trace_on)
        for text in compare.lines(checked):
            print(text, file=sys.stderr)
        sys.stderr.flush()
        if problems:
            for p in problems:
                log("result line refused: " + p)
            return None, 3
        return line, 0


def run_cell(manifest: Manifest, workload: str, seed: int, seconds: float,
             trace_on: int, *, require_chip: bool = True,
             t_start: Optional[float] = None,
             out_dir: Optional[str] = None):
    """Returns (result line or None, exit code)."""
    bench = Bench(manifest, workload, require_chip=require_chip,
                  out_dir=out_dir)
    if bench.dev is None:
        return None, 2
    try:
        bench.setup(seed, t_start)
        w = bench.window(seed, seconds, trace_on)
    finally:
        bench.close()
    return bench.report(w, trace_on)


def main(argv=None) -> int:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser(prog="python3 -m benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        manifest = Manifest(ROOT)
        line, rc = run_cell(manifest, args.workload, args.seed, args.seconds,
                            args.trace, t_start=t_start)
    except (ManifestError, ImportError) as e:
        # a missing data file, or a checkout without the program under test
        log(f"{type(e).__name__}: {e}")
        return 2
    if line is not None:
        print(json.dumps(line), flush=True)
    return rc
