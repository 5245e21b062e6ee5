"""Request kind `filter_agg`: what Kibana's Discover view and every
dashboard panel over a logs index send, in the shapes of Rally's
`http_logs` operations: `size` 0, `track_total_hits` true, a filter in
`bool.filter` (a `range` on the time field, optionally a `term` on the
status field) or `match_all`, and ONE `date_histogram` on the time field.

The mix's `request` block gives `shapes` (name -> `range_s`: the length
of the time range in seconds or null for `match_all`; `interval`: the
histogram's `fixed_interval`; `status`: the status a `term` clause keeps,
or null) and `cycle` (request j has shape cycle[j % len(cycle)]); `agg`
names the aggregation. A range's start is drawn uniformly over the part
of the corpus' span that holds the whole range, to the millisecond, from
the mix's `plan_seed` and j.

Its plain reference is numpy over the corpus kind's arrays and imports
nothing of the program: mask = the conjunction of the bounds and the
equality in int64; key = floor((ts - offset) / interval) * interval +
offset; counts by `np.unique`; the empty buckets between the first and
the last key filled (`min_doc_count` 0); `hits.total` = the mask's sum.
`numbers` decides `correct` by exact equality of every bucket key, every
`doc_count` and `hits.total`: three counts of disagreements, limits 0.

Two controls put the reference in the program's place: "float32_time"
(the timestamps rounded to float32 before they are bucketed: one
precision below the int64 milliseconds the configuration states; at 9e11
ms a float32 step is 65.5 s, so documents cross bucket edges) and
"drop_clause" (the status clause left out, or for a range-only shape the
upper bound moved out by one interval: another request's answer); a
sound comparison calls both wrong.
"""

from __future__ import annotations

import calendar
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmark.datagen import STREAM_TRAFFIC, rng_for
from benchmark.traffic import Request

_UNIT_MS = {"ms": 1, "s": 1000, "m": 60_000, "h": 3_600_000,
            "d": 86_400_000}


def interval_ms(spec: str) -> int:
    for unit in sorted(_UNIT_MS, key=len, reverse=True):
        if spec.endswith(unit):
            return int(spec[: -len(unit)]) * _UNIT_MS[unit]
    raise ValueError(f"interval {spec!r}")


def iso_ms(ms: int) -> str:
    """Epoch milliseconds as Kibana writes a bound:
    1998-05-05T12:00:00.123Z."""
    return time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(ms // 1000)) \
        + ".%03dZ" % (ms % 1000)


@dataclass
class FilterAggRequest(Request):
    shape: str = ""
    lo: Optional[int] = None        # the range keeps lo <= ts < hi (ms)
    hi: Optional[int] = None
    status: Optional[int] = None    # the term clause's status, or None
    interval: int = 3_600_000       # the histogram's, ms
    offset: int = 0


class Requests:
    """The canonical requests of a mix: request j comes from the mix's
    `plan_seed` and j, not from the run's seed."""

    def __init__(self, req: dict, config: dict, traffic: dict, pool: int,
                 rng: np.random.Generator, corpus):
        self.req = req
        idx, spec = config["index"], config["corpus"]
        self.field = idx["field"]
        self.status_field = idx["status_field"]
        self.t0 = 1000 * calendar.timegm(
            time.strptime(spec["start"], "%Y-%m-%dT%H:%M:%SZ"))
        self.span = int(spec["days"]) * 86_400_000
        self._u = rng_for(int(traffic["plan_seed"]),
                          STREAM_TRAFFIC).random(pool)

    def _shape(self, j: int) -> str:
        cycle = self.req["cycle"]
        return cycle[j % len(cycle)]

    def _program(self, name: str):
        """What makes a shape another set of programs on the device: the
        layout's granularity follows the interval, the number of filter
        columns the clauses."""
        s = self.req["shapes"][name]
        return (s["interval"], s.get("status") is not None)

    def variants(self) -> list:
        seen = []
        for name in self.req["cycle"]:
            if self._program(name) not in seen:
                seen.append(self._program(name))
        return seen

    def is_variant(self, j: int, variant) -> bool:
        return variant is None \
            or self._program(self._shape(j)) == tuple(variant)

    def request(self, j: int) -> FilterAggRequest:
        name = self._shape(j)
        s = self.req["shapes"][name]
        step = interval_ms(s["interval"])
        lo = hi = None
        clauses = []
        if s.get("range_s") is not None:
            length = 1000 * int(s["range_s"])
            lo = self.t0 + int(self._u[j] * (self.span - length))
            hi = lo + length
            clauses.append({"range": {self.field: {
                "gte": iso_ms(lo), "lt": iso_ms(hi),
                "format": "strict_date_optional_time"}}})
        if s.get("status") is not None:
            clauses.append({"term": {self.status_field: int(s["status"])}})
        query = {"bool": {"filter": clauses}} if clauses \
            else {"match_all": {}}
        return FilterAggRequest(
            body={"size": 0, "track_total_hits": True, "query": query,
                  "aggs": {self.req["agg"]: {"date_histogram": {
                      "field": self.field,
                      "fixed_interval": s["interval"]}}}},
            shape=name, lo=lo, hi=hi, status=s.get("status"),
            interval=step)


def top_k(req: dict) -> int:
    return 0        # size 0: a response carries no hit


def well_formed(r: dict) -> bool:
    """A response that reports no time-out and no failed shard and holds
    an exact total and one bucket list."""
    if r.get("timed_out") or r["_shards"]["failed"]:
        return False
    (agg,) = r["aggregations"].values()
    return isinstance(agg["buckets"], list) \
        and isinstance(r["hits"]["total"]["value"], int)


def numbers(pairs: Sequence, limits: dict, k: int) -> Dict[str, dict]:
    """`pairs` = (served response, reference answer) of the sampled
    requests. Every bucket key, every `doc_count` and every `hits.total`
    has to be the reference's own: `keys_wrong` (responses whose list of
    bucket keys differs), `counts_wrong` (buckets, in responses whose
    keys agree, whose `doc_count` differs), `totals_wrong` (responses
    whose `hits.total` is not {the mask's sum, "eq"}); limits 0."""
    keys_wrong = counts_wrong = totals_wrong = 0
    for resp, ref in pairs:
        (agg,) = resp["aggregations"].values()
        keys = np.asarray([b["key"] for b in agg["buckets"]], np.int64)
        counts = np.asarray([b["doc_count"] for b in agg["buckets"]],
                            np.int64)
        if len(keys) != len(ref["keys"]) or np.any(keys != ref["keys"]):
            keys_wrong += 1
        else:
            counts_wrong += int(np.count_nonzero(counts != ref["counts"]))
        totals_wrong += int(resp["hits"]["total"] != {
            "value": ref["total"], "relation": "eq"})
    return {name: {"value": v, "limit": 0, "ok": v == 0}
            for name, v in (("keys_wrong", keys_wrong),
                            ("counts_wrong", counts_wrong),
                            ("totals_wrong", totals_wrong))}


class FilterAggReference:
    """The filter's mask and the histogram's buckets over one index
    (module docstring)."""

    def __init__(self, segments: Sequence,
                 precision: Optional[str] = None):
        if precision not in (None, "float32_time", "drop_clause"):
            raise ValueError(precision)
        self.ts = np.concatenate([s.ts for s in segments]).astype(np.int64)
        self.status = np.concatenate([s.status for s in segments])
        self.drop = precision == "drop_clause"
        # the control: the time a bucket is made from, one precision below
        self.bucketed = (self.ts.astype(np.float32).astype(np.int64)
                         if precision == "float32_time" else self.ts)

    def answer(self, req: FilterAggRequest) -> dict:
        lo, hi, status = req.lo, req.hi, req.status
        if self.drop:
            if status is not None:
                status = None
            elif hi is not None:
                hi += req.interval
        mask = np.ones(len(self.ts), bool)
        if lo is not None:
            mask &= (self.ts >= lo) & (self.ts < hi)
        if status is not None:
            mask &= self.status == status
        key = (self.bucketed[mask] - req.offset) // req.interval \
            * req.interval + req.offset
        keys, counts = np.unique(key, return_counts=True)
        if len(keys):       # min_doc_count 0: the empty buckets between
            full = np.arange(keys[0], keys[-1] + 1, req.interval,
                             dtype=np.int64)
            filled = np.zeros(len(full), np.int64)
            filled[(keys - keys[0]) // req.interval] = counts
            keys, counts = full, filled
        return {"keys": keys.astype(np.int64),
                "counts": counts.astype(np.int64),
                "total": int(np.count_nonzero(mask))}

    def answers(self, reqs: Sequence, k: int) -> List[dict]:
        with ThreadPoolExecutor(8) as pool:
            return list(pool.map(self.answer, reqs))


def reference(config: dict, parts: Sequence,
              precision: Optional[str] = None) -> FilterAggReference:
    return FilterAggReference(parts, precision=precision)
