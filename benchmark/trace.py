"""From the profiler's trace to device busy time, kernel time and the
breakdown. Pure functions over a compact event list, so the reduction is
checked in tier-1 on a small recorded trace (benchmark/testdata/).

An event is [plane, line, name, start_ns, dur_ns, text]. On a TPU v5e the
name of a device op is its whole HLO instruction,

    %sweep_rowmax.4 = (f32[6,8,32]{...}, ...) custom-call(...)
    %vmap_jit_knn_int8_window_topc__.1 = (...) custom-call(..., %fmasks.1)

(my chip run 1, PR 26): a Pallas kernel is a custom call named after the
jitted function that holds the `pallas_call`, with a numeric suffix per
call site (one per partition in a fused program, masked and unmasked
variants alike). A kernel is therefore matched on the instruction's OWN
name, the part before ` = `: the ops that consume its result name it as
an operand and must not be counted. Names are cut to NAME_CHARS; `text`
holds the event's string stats, lower-cased.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Sequence   # [plane, line, name, start_ns, dur_ns, text]

DEVICE_PLANE = "/device:"
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
TEXT_STATS = ("tf_op", "hlo_op", "hlo_module")
NAME_CHARS = 120


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path: str, host_events: int = 20000) -> List[list]:
    """Device events (all of them) and the longest `host_events` host
    events of an .xplane.pb, as compact events."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device: List[list] = []
    host: List[list] = []
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            for e in line.events:
                dur = float(e.duration_ns)
                if on_device:
                    text = ""
                    try:
                        text = " ".join(
                            str(v)[:NAME_CHARS] for k, v in e.stats
                            if k in TEXT_STATS and isinstance(v, str))
                    except Exception:   # noqa: BLE001 — stats are optional
                        pass
                    device.append([plane.name, line.name,
                                   e.name[:NAME_CHARS], float(e.start_ns),
                                   dur, text.lower()])
                elif dur > 0:
                    host.append([plane.name, line.name, e.name[:NAME_CHARS],
                                 float(e.start_ns), dur, ""])
    host.sort(key=lambda ev: -ev[4])
    return device + host[:host_events]


def save_events(events: Iterable[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([list(e) for e in events], f)


def load_events(path: str) -> List[list]:
    with open(path) as f:
        return json.load(f)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def _span_ns(intervals) -> float:
    return float(sum(hi - lo for lo, hi in intervals))


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e[0] for e in events if e[0].startswith(DEVICE_PLANE)})


def _op_events(events: Sequence[Event], plane: str) -> List[Event]:
    """The op-level events of one device plane; where a trace has no op
    line, its module-level events."""
    for lines in (OPS_LINES, MODULE_LINES):
        got = [e for e in events
               if e[0] == plane and e[1] in lines and e[4] > 0]
        if got:
            return got
    return [e for e in events if e[0] == plane and e[4] > 0]


def busy_by_plane(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds in which an operation ran on each device: the union of
    that plane's op intervals."""
    return {p: _span_ns(_union([(e[3], e[3] + e[4])
                                for e in _op_events(events, p)])) / 1e9
            for p in device_planes(events)}


def busy_seconds(events: Sequence[Event]) -> float:
    """`busy_by_plane`, averaged over the device planes. 0.0 = no device
    event."""
    busy = busy_by_plane(events)
    return sum(busy.values()) / len(busy) if busy else 0.0


def own_name(name: str) -> str:
    """`%sweep_rowmax.4 = (f32[...` -> `%sweep_rowmax.4`."""
    return name.split(" = ", 1)[0]


def kernel_seconds(events: Sequence[Event], match: str) -> Tuple[float, int]:
    """(seconds, events matched) of every device op whose own name holds
    `match` (masked and unmasked variants, solo and fused programs
    alike); nested or repeated matches are counted once (interval union).
    Averaged over the device planes."""
    planes = device_planes(events)
    needle = match.lower()
    total, n = 0.0, 0
    for p in planes:
        hit = [e for e in _op_events(events, p)
               if needle in own_name(e[2]).lower()]
        n += len(hit)
        total += _span_ns(_union([(e[3], e[3] + e[4]) for e in hit]))
    return (total / len(planes) / 1e9 if planes else 0.0), n


def module_seconds(events: Sequence[Event], match: str) -> Tuple[float, int]:
    """(seconds, runs) of the device programs (`XLA Modules` line: one
    event a run, named `jit_<function>(<fingerprint>)`) whose name holds
    `match`, both averaged over the device planes that ran it."""
    by_plane: Dict[str, List[float]] = {}
    for e in events:
        if e[0].startswith(DEVICE_PLANE) and e[1] in MODULE_LINES \
                and match in e[2]:
            by_plane.setdefault(e[0], []).append(e[4])
    n = len(by_plane)
    if not n:
        return 0.0, 0
    return (sum(sum(d) for d in by_plane.values()) / n / 1e9,
            round(sum(len(d) for d in by_plane.values()) / n))


def count_host_events(events: Sequence[Event], match: str) -> int:
    """Host-side events (off the device planes) whose name holds `match`:
    `lower_sharding_computation` counts the jitted programs first
    instantiated in the span (compiles_in_window.search's file)."""
    return sum(1 for e in events
               if not e[0].startswith(DEVICE_PLANE) and match in e[2])


def seen_names(events: Sequence[Event], top: int = 40) -> List[list]:
    """[name, text, count, seconds] of the device ops, longest first:
    what a run writes down when its kernel's name matched nothing."""
    agg: Dict[Tuple[str, str], List[float]] = {}
    for p in device_planes(events):
        for e in _op_events(events, p):
            a = agg.setdefault((e[2], e[5]), [0, 0.0])
            a[0] += 1
            a[1] += e[4] / 1e9
    rows = [[k[0], k[1], int(v[0]), v[1]] for k, v in agg.items()]
    rows.sort(key=lambda r: -r[3])
    return rows[:top]


def breakdown(events: Sequence[Event], top: int = 10) -> dict:
    """The contract's `breakdown`: the device operations that took most
    time, and the longest idle gaps by what the host was doing (the
    longest host event that covers the gap's middle)."""
    ops: Dict[str, float] = {}
    planes = device_planes(events)
    gaps: List[Tuple[float, float]] = []
    for p in planes:
        evs = _op_events(events, p)
        for e in evs:
            ops[own_name(e[2])] = ops.get(own_name(e[2]), 0.0) + e[4] / 1e9
        u = _union([(e[3], e[3] + e[4]) for e in evs])
        gaps += [(u[i + 1][0] - u[i][1], (u[i][1] + u[i + 1][0]) / 2)
                 for i in range(len(u) - 1)]
    gaps.sort(reverse=True)
    host = [e for e in events if not e[0].startswith(DEVICE_PLANE)]
    by_host: Dict[str, float] = {}
    for dur, mid in gaps[:200]:
        cover = [e for e in host if e[3] <= mid <= e[3] + e[4]]
        name = (min(cover, key=lambda e: e[4])[2] if cover
                else "no traced host span")
        by_host[name] = by_host.get(name, 0.0) + dur / 1e9
    n = max(1, len(planes))
    return {
        "device_ops": [[k, v / n] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n] for k, v in sorted(
            by_host.items(), key=lambda kv: -kv[1])[:top]],
    }
