"""Builder's probes, run on the chip by hand; no check runs them. Each
is how a figure or a fault that PERF.md states can be read again.

    python3 -m benchmark.probe sweep <workload> <seed> <seconds> <rate>...
        one set-up, then one open-loop window per rate, each on requests
        no earlier window or the warm-up has sent: the table of PERF.md
        section 4 that the cell's fixed rate was read from.

    python3 -m benchmark.probe control <workload> <seed> <seconds>
        one short window; the sample is compared with the reference AND
        with the lower-precision control: the readings limits are set from.

    python3 -m benchmark.probe closed <workload> <seed> <seconds> <batch> <clients>...
        the cell's configuration and requests under a CLOSED loop of
        `_msearch` batches (`loadgen.closed_loop`, the generator's own),
        one window per client count on one set-up, each compared with the
        reference: how PERF.md's open question (two concurrent batches
        corrupt the sparse tier) was shown, and how its cure can be.
"""

from __future__ import annotations

import copy
import json
import sys

import numpy as np

from benchmark import compare, loadgen, run
from benchmark.manifest import ROOT, Manifest
from benchmark.traffic import Mix


def _setup(workload: str, seed: int):
    bench = run.Bench(Manifest(ROOT), workload)
    if bench.dev is None:
        sys.exit(2)
    bench.setup(seed)
    print(json.dumps({"probe": "setup", "workload": workload,
                      "setup_s": bench.setup_s}), flush=True)
    return bench


def _mean(s1, s2, path):
    a, b = compare.dotted(s1, path), compare.dotted(s2, path)
    n = b["count"] - a["count"]
    return (b["count"] * b["mean"] - a["count"] * a["mean"]) / n if n else 0.0


def sweep(workload: str, seed: int, seconds: float, rates) -> None:
    bench = _setup(workload, seed)
    base = bench.cell.traffic
    first = 0
    try:
        for rate in rates:
            bench.cell.traffic = dict(copy.deepcopy(base), rate_per_s=rate)
            w = bench.window(seed, seconds, 0, first=first)
            first += w.notes["attempted"]
            lat, half = w.latency_ms, len(w.latency_ms) // 2
            print(json.dumps({
                "probe": "sweep", "rate": rate, "offered": len(lat),
                "answered_in_window": int(np.sum(
                    w.notes["done_s"] <= seconds)),
                "ok": w.queries_done,
                "drain_s": float(max(w.notes["done_s"]) - seconds),
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "p50_first_half": float(np.percentile(lat[:half], 50)),
                "p50_second_half": float(np.percentile(lat[half:], 50)),
                "late_p95_ms": float(np.percentile(w.late_ms, 95)),
                "dispatch_ms": _mean(w.stats_before, w.stats_after,
                                     "tpu_search_latency.device"),
                "batch": _mean(w.stats_before, w.stats_after,
                               "tpu_search_latency.coalesce_batch_size"),
            }), flush=True)
    finally:
        bench.cell.traffic = base
        bench.close()


def control(workload: str, seed: int, seconds: float) -> None:
    bench = _setup(workload, seed)
    try:
        w = bench.window(seed, seconds, 0)
    finally:
        bench.close()
    cell = bench.cell
    out = {"probe": "control", "workload": workload, "seed": seed}
    for label, precision in (("reference", None), (
            "control", cell.config["precision"]["control"])):
        pairs = run.sample_pairs(cell, w.notes["answered"], seed,
                                 bench.parts, precision)
        checked = compare.verdict(
            pairs, cell.config, bench.stats0, w.stats_before,
            w.stats_after, run.top_k(cell))
        out[label] = {k: v["value"] for k, v in checked.items()}
        out[label + "_correct"] = compare.is_correct(checked)
    print(json.dumps(out), flush=True)


def closed(workload: str, seed: int, seconds: float, batch: int,
           client_counts) -> None:
    bench = run.Bench(Manifest(ROOT), workload)
    if bench.dev is None:
        sys.exit(2)
    cell = bench.cell
    base = dict(cell.traffic, loop="closed", warmup={"calls": 2},
                request=dict(cell.traffic["request"], batch=batch))
    cell.traffic = dict(base, clients=1)
    bench.setup(seed)
    try:
        for n, clients in enumerate(client_counts):
            cell.traffic = dict(base, clients=clients)
            mix = Mix(cell.traffic, cell.config, seed + n, bench.parts)
            s1 = bench.node.stats()
            calls = loadgen.closed_loop(bench.node.port, mix, seconds)
            s2 = bench.node.stats()
            n_answered, done = run.answered(calls)
            took = max(s.done for s in calls)
            pairs = run.sample_pairs(cell, done, seed + n, bench.parts)
            checked = compare.verdict(pairs, cell.config, s1, s1, s2,
                                      run.top_k(cell))
            print(json.dumps({
                "probe": "closed", "clients": clients, "batch": batch,
                "seed": seed + n, "calls": len(calls),
                "calls_answered": n_answered,
                "queries_per_s": len(done) / took,
                "window_s": took, "correct": compare.is_correct(checked),
                "checked": {k: v["value"] for k, v in checked.items()}}),
                flush=True)
    finally:
        bench.close()


def main(argv) -> int:
    cmd, workload, seed, seconds = argv[0], argv[1], int(argv[2]), \
        float(argv[3])
    if cmd == "sweep":
        sweep(workload, seed, seconds, [float(r) for r in argv[4:]])
    elif cmd == "control":
        control(workload, seed, seconds)
    elif cmd == "closed":
        closed(workload, seed, seconds, int(argv[4]),
               [int(c) for c in argv[5:]])
    else:
        print(__doc__, file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
