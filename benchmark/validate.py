"""The last line, checked against the manifest before it is printed.

The rule is the one the driver's refusal quotes (ledger, PR 25): the last
line is a JSON object with the keys `correct`, `attempted`, `failed`,
`metrics` and `device`, where `metrics` gives each metric of this
workload and trace mode as its value and unit, and `device` gives
`platform`, `kind`, `count`, `memory_peak_bytes` and, in a traced run,
`window_s` and `busy_s` (above 0, at most `window_s`).
"""

from __future__ import annotations

import math
from typing import List

from benchmark.manifest import Manifest

TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def _number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def line_faults(line: dict, manifest: Manifest, cell: str,
                trace: int) -> List[str]:
    """What is wrong with a result line; empty when the driver would take
    it. Names every metric that is missing."""
    faults: List[str] = []
    if not isinstance(line, dict):
        return ["the line is not a JSON object"]
    for k in TOP_KEYS:
        if k not in line:
            faults.append(f"lacks the key {k}")
    if faults:
        return faults
    if not isinstance(line["correct"], bool):
        faults.append("correct is not true or false")
    for k in ("attempted", "failed"):
        if not _number(line[k]) or line[k] < 0:
            faults.append(f"{k} is not a count")
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        return faults + ["metrics is not an object"]
    for m in manifest.declared(cell, trace):
        got = metrics.get(m["name"])
        if not isinstance(got, dict):
            faults.append(f"metrics lacks {m['name']}")
        elif not _number(got.get("value")):
            faults.append(f"metrics.{m['name']}.value is not a number")
        elif got.get("unit") != m["unit"]:
            faults.append(f"metrics.{m['name']}.unit is {got.get('unit')!r}, "
                          f"the manifest says {m['unit']!r}")
    dev = line["device"]
    if not isinstance(dev, dict):
        return faults + ["device is not an object"]
    for k in DEVICE_KEYS:
        if k not in dev:
            faults.append(f"device lacks {k}")
    if not _number(dev.get("count", 0)) or not _number(
            dev.get("memory_peak_bytes", 0)):
        faults.append("device.count / memory_peak_bytes is not a number")
    if trace:
        w, b = dev.get("window_s"), dev.get("busy_s")
        if not _number(w) or not _number(b):
            faults.append("device lacks window_s / busy_s of the traced span")
        elif not 0 < b <= w:
            faults.append(f"device.busy_s {b} is not in (0, window_s {w}]")
    return faults
