"""Corpus kind `logs`: a configuration's `corpus` block as seeded arrays of
web-server log lines (Rally's `http_logs` mapping: `@timestamp` date at
one-second resolution, `status` integer, `size` integer), one
`LogsSegment` a segment, and each as the program's `Segment` for
`install.install`.

Arrivals are an inhomogeneous Poisson process over `days` days from
`start`: the intensity of hour h of day d is `hour_cycle[h]` x a linear
growth from `day_growth[0]` (first day) to `day_growth[1]` (last) x, on
the days of `match_days` between the hours of `match_hours`, a peak that
grows linearly from `match_peak[0]` to `match_peak[1]` over those days.
Logs are appended: the documents are in time order, cut into segments at
equal document counts, so a segment is a contiguous stretch of time.
`status` is drawn from `status_share`; `size` is log-normal
(`size_median`, `size_sigma`) and 0 where the status is 304.

Drawing imports nothing of the program; `segment` is the one function
that does (the segment format is the program's). A program whose
`GET /_nodes/stats` lacks a counter that the configuration's `must_rise`
/ `must_stay` name cannot state this deployment's guarantee: `make_parts`
ends such a run before it draws anything (`refuse_without`).
"""

from __future__ import annotations

import calendar
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import List

import numpy as np

from benchmark.datagen import n_parts, rng_for, segment_bounds
from benchmark.install import doc_ids, sources
from benchmark.manifest import ManifestError

STREAM_LOGS = 5         # child stream of the run's seed (datagen.STREAM_*)

# what the CPU tests cut `corpus` to (tests/bench_harness/bench_tiny.py)
TINY = {"docs": 6000}


@dataclass
class LogsSegment:
    """One segment's log lines, in time order."""
    doc0: int               # global ordinal of the segment's first doc
    ts: np.ndarray          # [n] i64 epoch milliseconds, ascending
    status: np.ndarray      # [n] i32
    size: np.ndarray        # [n] i32 bytes

    @property
    def n(self) -> int:
        return len(self.ts)


def start_ms(spec: dict) -> int:
    return 1000 * calendar.timegm(
        time.strptime(spec["start"], "%Y-%m-%dT%H:%M:%SZ"))


def hourly_intensity(spec: dict) -> np.ndarray:
    """[days * 24] relative arrival intensity of every hour of the span."""
    days = int(spec["days"])
    d = np.arange(days, dtype=np.float64)
    g0, g1 = spec["day_growth"]
    day = g0 + (g1 - g0) * d / max(days - 1, 1)
    w = day[:, None] * np.asarray(spec["hour_cycle"], np.float64)[None, :]
    m0, m1 = spec["match_days"]
    h0, h1 = spec["match_hours"]
    p0, p1 = spec["match_peak"]
    peak = p0 + (p1 - p0) * (d[m0:m1 + 1] - m0) / max(m1 - m0, 1)
    w[m0:m1 + 1, h0:h1] *= peak[:, None]
    return w.ravel()


def _draw_segment(spec: dict, seed: int, part: int, doc0: int, n: int,
                  total: int) -> LogsSegment:
    rng = rng_for(seed, STREAM_LOGS, part)
    w = hourly_intensity(spec)
    cdf = np.concatenate([[0.0], np.cumsum(w) / w.sum()])
    # this segment's stretch of the whole span's arrivals: its share of
    # the intensity's mass, the order statistics of n uniform draws in it
    u = np.sort(doc0 / total + rng.random(n) * (n / total))
    secs = np.interp(u, cdf, 3600.0 * np.arange(len(cdf)))
    secs = np.minimum(np.floor(secs), 3600.0 * len(w) - 1)
    ts = start_ms(spec) + 1000 * secs.astype(np.int64)
    codes = np.asarray([int(c) for c in spec["status_share"]], np.int32)
    share = np.asarray(list(spec["status_share"].values()), np.float64)
    status = codes[rng.choice(len(codes), size=n, p=share / share.sum())]
    size = np.minimum(rng.lognormal(
        np.log(float(spec["size_median"])), float(spec["size_sigma"]),
        size=n), 2.0 ** 31 - 1).astype(np.int32)
    size[status == 304] = 0
    return LogsSegment(doc0, ts, status, size)


def refuse_without(config: dict) -> None:
    """ManifestError where the program has no counter of the name the
    configuration's `must_rise` / `must_stay` give (all of `tpu_agg`)."""
    from elasticsearch_tpu.search.agg_device import agg_stats

    have = agg_stats()
    lacks = [c for c in config.get("must_rise", []) + config.get(
        "must_stay", []) if c.split(".", 1)[1] not in have]
    if lacks:
        raise ManifestError(
            f"{config['name']}: GET /_nodes/stats of this program has no "
            f"{lacks}: it cannot run this configuration")


def make_parts(config: dict, seed: int) -> List[LogsSegment]:
    """The seeded corpus of a configuration, one part a segment, in
    global ordinal (= time) order."""
    refuse_without(config)
    spec = config["corpus"]
    total = int(spec["docs"])
    n_seg = n_parts(config)
    b = segment_bounds(total, n_seg)
    with ThreadPoolExecutor(n_seg) as pool:
        return list(pool.map(
            lambda p: _draw_segment(spec, seed, p, int(b[p]),
                                    int(b[p + 1] - b[p]), total),
            range(n_seg)))


def _column(values: np.ndarray):
    from elasticsearch_tpu.index.segment import NumericColumn

    v = values.astype(np.float64)
    return NumericColumn(
        values=v, max_values=v, exists=np.ones(len(v), bool),
        value_start=np.arange(len(v) + 1, dtype=np.int64), all_values=v)


def segment(config: dict, seg: LogsSegment, seg_id: int, seq0: int = 0):
    """`seq0` = the global ordinal of the shard's first document: sequence
    numbers are the shard's own, ids and `pid` the index's."""
    from elasticsearch_tpu.index.segment import Segment

    idx = config["index"]
    return Segment(
        seg_id=seg_id, doc_ids=doc_ids(seg.doc0, seg.n),
        sources=sources(seg.doc0, seg.n), postings={},
        numeric={idx["field"]: _column(seg.ts),
                 idx["status_field"]: _column(seg.status),
                 idx["size_field"]: _column(seg.size)},
        keyword={}, vectors={},
        seq_nos=np.arange(seg.doc0 - seq0, seg.doc0 - seq0 + seg.n,
                          dtype=np.int64))
