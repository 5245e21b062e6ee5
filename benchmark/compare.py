"""The comparison that decides `correct`: what the timed requests
themselves returned (ids, order, scores, totals) against the plain
reference, plus the counters that say a host tier answered in the
device's place. Every number compared is reported beside its limit.

Numbers (limits in the configuration file's `limits`, readings in PERF.md):

    score_err   widest relative gap between a served hit's score and the
                reference's score of the SAME document
    rank_gap    widest relative gap by which the reference's score of the
                document served at position i lies below the reference's
                i-th best score. 0 when the served list is a best list in
                the reference's eyes, whatever the order among exact ties
    order_err   widest relative amount by which a served list's own scores
                rise from one position to the next (a sorted list reads 0)
    hits_wrong  responses whose hit count, duplicate-free ids or
                hits.total disagree with the reference
    host_tier_answers  rise of the nine fallback / fault / reject counters,
                and of the configuration's `must_stay` counters, since the
                node started
    device_dispatches  smallest rise over the window among the
                configuration's `device_counter` and its `must_rise`
                counters (every one must move: at least 1)
    compared    responses compared (at least `min_compared`)
"""

from __future__ import annotations

import json
from typing import Dict, List, Sequence

import numpy as np

from benchmark.reference import TOTAL_CAP

# each one counts a request that a host tier answered in the device's
# place, or a device error (chip_smoke.py's ZERO_COUNTERS)
ZERO_COUNTERS = (
    "tpu_health.device_faults", "tpu_health.fallback_queries",
    "tpu_health.fastpath_reject_error", "tpu_health.fastpath_device_fault",
    "tpu_health.fastpath_timed_out", "tpu_health.open_circuits",
    "tpu_turbo.sparse_fallbacks", "tpu_knn.knn_host_fallbacks",
    "tpu_agg.agg_host_fallbacks",
)


def dotted(stats: dict, path: str):
    """`tpu_knn.knn_queries` -> stats['tpu_knn']['knn_queries']. A key may
    itself hold dots (`tpu_search_latency.queue_wait.search`): at each
    level the longest key that matches is taken."""
    cur, parts = stats, path.split(".")
    while parts:
        for n in range(len(parts), 0, -1):
            key = ".".join(parts[:n])
            if isinstance(cur, dict) and key in cur:
                cur, parts = cur[key], parts[n:]
                break
        else:
            raise KeyError(f"{path}: no key {parts[0]!r}")
    return cur


def well_formed(raw: bytes, n: int):
    """The n search responses of one 200 body, or None."""
    try:
        doc = json.loads(raw)
        resps = doc["responses"] if n > 1 or "responses" in doc else [doc]
        if len(resps) != n:
            return None
        for r in resps:
            if r.get("timed_out") or r["_shards"]["failed"] \
                    or not isinstance(r["hits"]["hits"], list):
                return None
        return resps
    except (ValueError, KeyError, TypeError):
        return None


def compare_one(resp: dict, ref: dict, k: int) -> Dict[str, float]:
    """One served response against the reference's answer to the same
    request."""
    hits = resp["hits"]["hits"]
    ids = [int(h["_id"]) for h in hits]
    served = np.asarray([float(h["_score"]) for h in hits], np.float64)
    want = min(k, len(ref["ords"]))
    total = resp["hits"]["total"]
    ref_total = ({"value": TOTAL_CAP, "relation": "gte"}
                 if ref["total"] > TOTAL_CAP
                 else {"value": ref["total"], "relation": "eq"})
    wrong = int(len(ids) != want or len(set(ids)) != len(ids)
                or total != ref_total)
    out = {"score_err": 0.0, "rank_gap": 0.0, "order_err": 0.0,
           "hits_wrong": wrong}
    m = min(len(ids), want)
    if m:
        mine = ref["scores"][ids[:m]]
        best = ref["top"][:m]
        scale = np.maximum(np.abs(best), 1e-30)
        out["score_err"] = float(np.max(np.abs(served[:m] - mine) / scale))
        out["rank_gap"] = float(np.max((best - mine) / scale))
        if m > 1:
            out["order_err"] = float(max(0.0, np.max(
                (served[1:m] - served[:m - 1]) / scale[1:])))
    return out


def verdict(pairs: Sequence, config: dict, stats0: dict, stats1: dict,
            stats2: dict, k: int) -> Dict[str, dict]:
    """`pairs` = (served response, reference answer) of the sampled
    requests. stats0/1/2 = node stats at node start, window start and
    window end. `config` gives the `limits`, the `device_counter` and,
    where the deployment has more than one thing that must have run on
    the device or may not have run on the host, `must_rise` and
    `must_stay`. Returns name -> {value, limit, ok}."""
    limits = config["limits"]
    worst = {"score_err": 0.0, "rank_gap": 0.0, "order_err": 0.0,
             "hits_wrong": 0}
    for resp, ref in pairs:
        one = compare_one(resp, ref, k)
        for name in ("score_err", "rank_gap", "order_err"):
            worst[name] = max(worst[name], one[name])
        worst["hits_wrong"] += one["hits_wrong"]
    host = sum(dotted(stats2, c) - dotted(stats0, c)
               for c in ZERO_COUNTERS + tuple(config.get("must_stay", ())))
    moved = min(dotted(stats2, c) - dotted(stats1, c)
                for c in [config["device_counter"]]
                + list(config.get("must_rise", ())))
    out = {}
    for name in ("score_err", "rank_gap", "order_err"):
        out[name] = {"value": worst[name], "limit": float(limits[name]),
                     "ok": bool(worst[name] <= float(limits[name]))}
    out["hits_wrong"] = {"value": worst["hits_wrong"], "limit": 0,
                         "ok": worst["hits_wrong"] == 0}
    out["host_tier_answers"] = {"value": host, "limit": 0, "ok": host == 0}
    out["device_dispatches"] = {"value": moved, "limit": 1, "ok": moved >= 1}
    need = int(limits["min_compared"])
    out["compared"] = {"value": len(pairs), "limit": need,
                       "ok": len(pairs) >= need}
    return out


def is_correct(checked: Dict[str, dict]) -> bool:
    return all(v["ok"] for v in checked.values())


def lines(checked: Dict[str, dict]) -> List[str]:
    """One plain line per number compared, for standard error."""
    return ["%s %r limit %r %s" % (n, v["value"], v["limit"],
                                   "ok" if v["ok"] else "FAILED")
            for n, v in checked.items()]
