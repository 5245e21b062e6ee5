"""One reader per source KIND; a metric is a data file that names its kind
and what to read (benchmark/metrics/<metric>.json). Adding a metric of a
kind that exists is a new file; a new kind is code.

Every reader is total: it returns a number for any window, also one with
no event of its source (a histogram with count 0 reads 0.0, a ratio over
0 reads 0.0). The exceptions read the device trace: `kernel_roofline` (a
share of a roofline is never reported as 0), `module_mean_ms` and
`busy_skew` raise `NothingToRead` on a span without an event of theirs —
the run then writes down the event names it did see and ends non-zero
without a result line.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmark import costs, trace
from benchmark.compare import dotted


class NothingToRead(Exception):
    """A reader's source held nothing where something has to be."""


@dataclass
class Window:
    """What one run leaves for the readers."""
    config: dict
    traffic: dict
    seconds: float                      # the window, as measured
    setup_s: float
    latency_ms: np.ndarray              # per call: body read - due
    late_ms: np.ndarray                 # per call: sent - due
    queries_done: int                   # searches in well-formed 200s
    stats_before: dict                  # GET /_nodes/stats at window start
    stats_after: dict                   # ... and at its end
    memory_peak_bytes: int
    device_kind: str
    events: Optional[List[list]] = None     # the traced span, if traced
    notes: Dict[str, object] = field(default_factory=dict)


def _hist(stats: dict, path: str):
    h = dotted(stats, path)
    return float(h["count"]), float(h["count"]) * float(h["mean"])


def _hist_delta(w: Window, path: str):
    c0, s0 = _hist(w.stats_before, path)
    c1, s1 = _hist(w.stats_after, path)
    return c1 - c0, s1 - s0


def _delta(w: Window, paths) -> float:
    return float(sum(dotted(w.stats_after, p) - dotted(w.stats_before, p)
                     for p in paths))


def client_latency(spec: dict, w: Window) -> float:
    arr = w.late_ms if spec.get("of") == "lateness" else w.latency_ms
    return float(np.percentile(arr, spec["percentile"])) if len(arr) else 0.0


def client_rate(spec: dict, w: Window) -> float:
    """Searches answered per second, from the window's start to its last
    answer (a closed loop's last call ends after the window's length)."""
    return w.queries_done / max(w.seconds, float(np.max(w.notes["done_s"])))


def memory_peak(spec: dict, w: Window) -> float:
    return w.memory_peak_bytes / float(spec.get("divide", 1e9))


def setup(spec: dict, w: Window) -> float:
    return w.setup_s


def histogram_mean(spec: dict, w: Window) -> float:
    count, total = _hist_delta(w, spec["path"])
    return total / count if count > 0 else 0.0


def counter_delta(spec: dict, w: Window) -> float:
    return _delta(w, spec["paths"])


def counter_ratio(spec: dict, w: Window) -> float:
    den = _delta(w, spec["denominator"])
    if "denominator_times_config" in spec:
        den *= float(dotted(w.config, spec["denominator_times_config"]))
    return (float(spec.get("scale", 1.0)) * _delta(w, spec["numerator"]) / den
            if den > 0 else 0.0)


def trace_event_count(spec: dict, w: Window) -> float:
    return float(trace.count_host_events(w.events or [], spec["match"]))


def device_idle(spec: dict, w: Window) -> float:
    return 100.0 * (1.0 - w.notes["busy_s"] / w.notes["window_s"])


def busy_skew(spec: dict, w: Window) -> float:
    """The busiest device's busy seconds over the mean of the devices':
    1.0 = even, the device count = one chip did everything. It is what
    `device_idle`'s mean over the planes hides."""
    busy = list(trace.busy_by_plane(w.events or []).values())
    if not busy or sum(busy) <= 0:
        raise NothingToRead("no device event in the traced span")
    return max(busy) * len(busy) / sum(busy)


def module_mean_ms(spec: dict, w: Window) -> float:
    """Mean traced time of one run of a device program, in ms."""
    seconds, runs = trace.module_seconds(w.events or [], spec["match"])
    if runs <= 0:
        raise NothingToRead(f"no run of a program named {spec['match']!r}")
    return 1e3 * seconds / runs


def engine_regions(stats: dict, kind: Optional[str] = None,
                   per_device: bool = False) -> Dict[str, int]:
    """Summed HBM regions of the engines (of one kind) in the ledger:
    over all devices, or (`per_device`) each engine's divided by the
    number of devices that hold it (`devices`, 1 where absent)."""
    out: Dict[str, int] = {}
    for eng in stats["tpu_hbm"]["engines"].values():
        if kind is None or eng["kind"] == kind:
            over = int(eng.get("devices", 1)) if per_device else 1
            for k, v in eng["regions"].items():
                out[k] = out.get(k, 0) + int(v) // over
    return out


def kernel_roofline(spec: dict, w: Window) -> float:
    """100 x (least time the chip could take for the kernel passes of the
    span) / (traced time of the kernel's device events), both per device:
    the regions a pass reads are one device's share of them, the traced
    time the mean over the device planes. The dispatch width is what
    ran: where the loop is closed, the searches the window answered over
    the passes the program counted (a batch over the scheduler's
    SMALL_BATCH_MAX never passes the lane, so the histogram never sees
    it; a batch the engine split in two reads half as wide), the
    histogram's mean otherwise."""
    seconds, n_events = trace.kernel_seconds(w.events or [], spec["match"])
    passes = _delta(w, [spec["passes"]])
    if seconds <= 0 or passes <= 0:
        raise NothingToRead(
            f"kernel {spec['match']!r}: {n_events} device events, "
            f"{seconds} s, {passes} passes counted by {spec['passes']}")
    regions = {}
    for kind in spec["engine_kinds"]:       # first kind the ledger holds
        regions = engine_regions(w.stats_after, kind, per_device=True)
        if regions:
            break
    if w.traffic.get("loop") == "closed":
        batch = w.queries_done / passes
    else:
        batch = histogram_mean({"path": spec["batch_histogram"]}, w)
    qc = next((s for s in spec["widths"] if s >= batch), spec["widths"][-1])
    least, bound = costs.least_seconds(spec["cost"], regions, qc,
                                       w.device_kind)
    w.notes.setdefault("roofline", {})[spec["match"]] = {
        "kernel_s": seconds, "events": n_events, "passes": passes,
        "width": qc, "least_s_per_pass": least, "bound": bound}
    return 100.0 * least * passes / seconds


KINDS: Dict[str, Callable[[dict, Window], float]] = {
    f.__name__: f for f in (
        client_latency, client_rate, memory_peak, setup, histogram_mean,
        counter_delta, counter_ratio, device_idle, busy_skew,
        trace_event_count, module_mean_ms, kernel_roofline)}


def read(spec: dict, w: Window) -> float:
    return float(KINDS[spec["kind"]](spec, w))
