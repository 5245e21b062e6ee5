"""One reader per source KIND, each a file benchmark/kinds/reader/<kind>.py
with one function `read(spec, window)`; a metric is a data file that names
its kind and what to read (benchmark/metrics/<metric>.json). A metric of a
kind that exists is a new data file; a new kind is a new file beside the
others. What they share (the window, the deltas) is here.

Every reader is total: it returns a number for any window, also one with
no event of its source (a histogram with count 0 reads 0.0, a ratio over
0 reads 0.0). The exceptions read the device: `kernel_roofline` (a
share of a roofline is never reported as 0), `module_mean_ms` and
`busy_skew` raise `NothingToRead` on a span without an event of theirs,
`peak_skew` where no device reports a peak — the run then writes down the
event names it did see and ends non-zero without a result line.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from benchmark.compare import dotted
from benchmark.manifest import DATA_DIR, load_kind


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
    # the data root whose kinds/ holds the readers and costs
    kinds_dir: str = DATA_DIR
    notes: Dict[str, object] = field(default_factory=dict)


def _hist(stats: dict, path: str):
    h = dotted(stats, path)
    return float(h["count"]), float(h["count"]) * float(h["mean"])


def hist_delta(w: Window, path: str):
    c0, s0 = _hist(w.stats_before, path)
    c1, s1 = _hist(w.stats_after, path)
    return c1 - c0, s1 - s0


def delta(w: Window, paths) -> float:
    return float(sum(dotted(w.stats_after, p) - dotted(w.stats_before, p)
                     for p in paths))


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


def read(spec: dict, w: Window) -> float:
    """The metric whose file is `spec`, by the reader kind it names."""
    return float(load_kind(w.kinds_dir, "reader", spec["kind"]).read(spec, w))
