#!/usr/bin/env python3
"""chip_smoke.py — does the served path really run on the chip?

One process starts the node the way `python -m elasticsearch_tpu` does,
binds a real port and talks to it over HTTP: `_bulk` a seeded corpus, then
`_search` / `_msearch` / kNN / aggregations, every answer compared with the
dense reference executor (`IndexService._search_dense`).

Comparing answers is not enough here. The serving layer contains device
failures by design: a kernel the chip's compiler refuses, or a runtime
device error, is caught, counted and answered by a host tier with a 200
and correct hits. So every phase also reads `GET /_nodes/stats`: the
device counters it names must move, and every fallback / fault / reject
counter must stay 0.

    python chip_smoke.py             # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   # the sharded path on one 4-chip host

The first act is `jax.devices()`; without a TPU the script says why and
exits non-zero — it never continues on the CPU. tests/test_chip_smoke.py
rehearses the same phase functions at a tiny size on the CPU mesh. Times
printed here are bring-up notes, not benchmark numbers.
The last line of stdout is the verdict the driver reads.
"""

from __future__ import annotations

import argparse
import http.client
import json
import logging
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

N_DOCS = 1_000_000       # `smoke`: spread over --chips shards
N_VECTORS = 100_000      # `smoke_vec`
VOCAB = 50_000           # the corpus's shape: Zipf 1.07 over 50k terms,
ZIPF_S = 1.07            # 8-40 tokens per doc
DOC_LEN = (8, 40)
N_TAGS = 256
BULK_BATCH = 5_000
VEC_DIMS = 768           # BASELINE.json config 4's width
VEC_CLUSTERS = 64
TS0 = 1_700_000_000_000  # epoch millis of doc 0; docs are one minute apart
FRESH_TERM = "zzfresh"   # only the refresh phase's docs carry it
K = 10
# tag values of `smoke_vec` on one shard (a 4-shard index gets a quarter):
# a tag filter then leaves about a dozen docs of each cluster in a
# partition, fewer than the candidates the int8 first pass keeps, so the
# filtered kNN queries are answered by that pass + exact rescore and not
# by the dense re-run an uncertified query takes
VEC_TAGS = 64

# after every phase these must still be zero: each one counts a request
# that a host tier answered in the device's place (or a device error)
ZERO_COUNTERS = (
    "tpu_health.device_faults", "tpu_health.fallback_queries",
    "tpu_health.fastpath_reject_error", "tpu_health.fastpath_device_fault",
    "tpu_health.fastpath_timed_out", "tpu_health.open_circuits",
    "tpu_turbo.sparse_fallbacks", "tpu_knn.knn_host_fallbacks",
    "tpu_agg.agg_host_fallbacks",
)
# deltas reported on every phase line
REPORTED = (
    "tpu_turbo.partition_dispatches", "tpu_turbo.fused_dispatches",
    "tpu_turbo.merge_device", "tpu_turbo.merge_host",
    "tpu_turbo.sparse_queries", "tpu_turbo.bitset_packs",
    "tpu_turbo.bitset_gallop", "tpu_turbo.bool_device",
    "tpu_turbo.bool_cold_lead", "tpu_turbo.bool_host",
    "tpu_turbo.phrase_builds", "tpu_knn.knn_queries",
    "tpu_knn.knn_int8_dispatches", "tpu_knn.knn_uncertified",
    "tpu_agg.agg_device_dispatches",
    "tpu_compile.misses", "tpu_compile.retraces",
)
TURBO_ENGINES = ("turbo", "fused_turbo")


class SmokeFailure(Exception):
    """A phase did not do what it must; the run ends non-zero."""


# --------------------------------------------------------------------------
# seeded data
# --------------------------------------------------------------------------

def _zipf_probs(n: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** s
    return p / p.sum()


@dataclass
class Corpus:
    """The `smoke` index's documents, drawn in bulk from the seed."""
    lens: np.ndarray      # [N] tokens per doc
    bounds: np.ndarray    # [N + 1] token offsets
    tokens: np.ndarray    # [sum(lens)] term ranks (0 = most frequent)
    tags: np.ndarray      # [N] tag ids
    nums: np.ndarray      # [N] the integer field

    @property
    def n(self) -> int:
        return len(self.lens)

    def body(self, i: int) -> str:
        return " ".join(
            f"t{t}" for t in self.tokens[self.bounds[i]:self.bounds[i + 1]])


def make_corpus(n_docs: int, seed: int) -> Corpus:
    rng = np.random.default_rng(seed)
    lens = rng.integers(DOC_LEN[0], DOC_LEN[1], size=n_docs)
    tokens = rng.choice(VOCAB, size=int(lens.sum()),
                        p=_zipf_probs(VOCAB, ZIPF_S)).astype(np.int32)
    tags = rng.choice(N_TAGS, size=n_docs,
                      p=_zipf_probs(N_TAGS, 1.0)).astype(np.int32)
    nums = rng.integers(0, 1000, size=n_docs).astype(np.int32)
    return Corpus(lens, np.concatenate([[0], np.cumsum(lens)]), tokens,
                  tags, nums)


def make_vectors(n: int, seed: int,
                 n_tags: int = VEC_TAGS) -> Tuple[np.ndarray, np.ndarray]:
    """Seeded mixture of Gaussians: (vectors [n, 768] f32, tags [n])."""
    rng = np.random.default_rng(seed + 1)
    centers = rng.standard_normal((VEC_CLUSTERS, VEC_DIMS)).astype(np.float32)
    which = rng.integers(0, VEC_CLUSTERS, size=n)
    vecs = centers[which] + 0.5 * rng.standard_normal(
        (n, VEC_DIMS)).astype(np.float32)
    tags = rng.choice(n_tags, size=n).astype(np.int32)
    return np.round(vecs, 4), tags


# --------------------------------------------------------------------------
# the node under test, seen from a client
# --------------------------------------------------------------------------

class Client:
    """Blocking HTTP client for the node this process started."""

    def __init__(self, node, server):
        self.node = node
        self.server = server
        self._conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                                timeout=600)

    def request(self, method: str, path: str, body=None, ndjson=False):
        data, headers = None, {}
        if body is not None:
            data = body if isinstance(body, (bytes, str)) \
                else json.dumps(body)
            if isinstance(data, str):
                data = data.encode()
            headers["Content-Type"] = ("application/x-ndjson" if ndjson
                                       else "application/json")
        self._conn.request(method, path, body=data, headers=headers)
        resp = self._conn.getresponse()
        raw = resp.read()
        out = json.loads(raw) if raw else {}
        if resp.status >= 300:
            raise SmokeFailure(f"{method} {path} -> HTTP {resp.status}: "
                               f"{raw[:400]!r}")
        return out

    def stats(self) -> dict:
        """This node's sections of GET /_nodes/stats."""
        nodes = self.request("GET", "/_nodes/stats")["nodes"]
        return next(iter(nodes.values()))

    def reference(self, index: str, body: dict,
                  search_type: str = "query_then_fetch") -> dict:
        """The dense reference executor's answer to the same body —
        in-process, never through the serving fast path."""
        return self.node.indices.get(index)._search_dense(
            dict(body), search_type)

    def close(self) -> None:
        self._conn.close()
        self.server.stop()
        self.node.close()


def counter(stats: dict, dotted: str):
    """`tpu_turbo.sparse_queries` -> stats['tpu_turbo']['sparse_queries']."""
    cur = stats
    for part in dotted.split("."):
        cur = cur[part]
    return cur


# --------------------------------------------------------------------------
# comparison with the reference
# --------------------------------------------------------------------------

def _hits(resp: dict) -> List[Tuple[str, float]]:
    return [(h["_id"], float(h["_score"])) for h in resp["hits"]["hits"]]


def compare_hits(got: dict, ref: dict, *, score_rtol: float,
                 tie_rtol: float = 1e-6, totals: str = "equal") -> int:
    """Hold a served response to the reference's: totals equal, same ids
    in the same order, scores within score_rtol. The one licence: hits
    whose REFERENCE scores differ by less than tie_rtol (relative) may
    appear in either order (and trade places across the top-k boundary) —
    a last-ulp difference between two backends' arithmetic cannot order
    them. Returns the number of positions so displaced; raises
    SmokeFailure on any other difference."""
    gt, rt = got["hits"]["total"], ref["hits"]["total"]
    if (totals == "capped" and gt["relation"] == "gte"
            and rt["value"] >= gt["value"]):
        # over several shards the dense executor sums the shards' counts,
        # each capped on its own (4 x 10000 "gte", or 11235 "eq" from four
        # uncapped ones); the served path caps once, like the upstream
        # project. Both say "at least the cap".
        rt = gt
    if totals != "skip" and gt != rt:
        raise SmokeFailure(f"total {gt} != reference {rt}")
    g, r = _hits(got), _hits(ref)
    if len(g) != len(r):
        raise SmokeFailure(f"{len(g)} hits, reference has {len(r)}")
    ref_score = dict(r)
    displaced = 0
    for pos, ((gid, gs), (rid, rs)) in enumerate(zip(g, r)):
        if abs(gs - rs) > score_rtol * max(abs(rs), 1e-30):
            raise SmokeFailure(
                f"hit {pos}: score {gs!r} vs reference {rs!r} "
                f"(rel {abs(gs - rs) / max(abs(rs), 1e-30):.3g})")
        if gid == rid:
            continue
        # a displaced id must tie (within tie_rtol) with what the
        # reference holds here; past the boundary, with the last hit
        other = ref_score.get(gid, r[-1][1])
        if abs(other - rs) > tie_rtol * max(abs(rs), 1e-30):
            raise SmokeFailure(
                f"hit {pos}: id {gid} vs reference {rid} "
                f"(reference scores {other!r} / {rs!r} are no tie)")
        displaced += 1
    return displaced


def engine_of(resp: dict) -> Optional[str]:
    """The `engine=` the DeviceDispatch profile node names."""
    for shard in (resp.get("profile") or {}).get("shards", ()):
        for search in shard.get("searches", ()):
            for node in search.get("query", ()):
                if node.get("type") == "DeviceDispatch":
                    desc = node.get("description", "")
                    return desc.split("engine=")[1].split()[0]
    return None


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

@dataclass
class Smoke:
    """What the phases share."""
    client: Client
    seed: int
    index: str
    vec_index: str
    corpus: Corpus
    vectors: np.ndarray
    vec_tags: np.ndarray
    n_shards: int = 1
    search_type: str = "query_then_fetch"
    routing_reason: str = "fits_hbm_budget"   # what turbo_eligible must say
    first_traceback: List[str] = field(default_factory=list)
    baseline: Optional[dict] = None   # node stats before the first phase
    served_s: float = 0.0       # per phase: time inside served requests
    reference_s: float = 0.0    # per phase: time inside the reference

    def request(self, method: str, path: str, body=None, ndjson=False):
        t0 = time.monotonic()
        try:
            return self.client.request(method, path, body, ndjson)
        finally:
            self.served_s += time.monotonic() - t0

    def search(self, index: str, body: dict) -> dict:
        return self.request(
            "POST", f"/{index}/_search?search_type={self.search_type}", body)

    def reference(self, index: str, body: dict) -> dict:
        body = {k: v for k, v in body.items() if k != "profile"}
        t0 = time.monotonic()
        try:
            return self.client.reference(index, body, self.search_type)
        finally:
            self.reference_s += time.monotonic() - t0


@dataclass
class Phase:
    name: str
    run: Callable[[Smoke], dict]          # returns notes for the phase line
    must_increase: Tuple[str, ...] = ()
    must_stay: Tuple[str, ...] = ()       # over this phase's requests


class _FirstTraceback(logging.Handler):
    """Keeps the first traceback the serving layer logs, for the failure
    message of a non-zero counter."""

    def __init__(self, sink: List[str]):
        super().__init__(level=logging.WARNING)
        self.sink = sink

    def emit(self, record):
        if not self.sink:
            self.sink.append(self.format(record))


def _device_peak_bytes() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_phase(smoke: Smoke, phase: Phase) -> dict:
    """One phase: requests, reference comparison, counter checks. Prints
    the phase's JSON line; raises SmokeFailure if the phase failed."""
    before = smoke.client.stats()
    if smoke.baseline is None:
        # a fresh process reads 0 everywhere; a test worker that ran
        # fault-injection tests before does not, so hold to "unchanged"
        smoke.baseline = before
    smoke.served_s = smoke.reference_s = 0.0
    t0 = time.monotonic()
    notes = phase.run(smoke)
    after = smoke.client.stats()
    line = {"phase": phase.name, "wall_s": round(time.monotonic() - t0, 3),
            "served_s": round(smoke.served_s, 3),
            "reference_s": round(smoke.reference_s, 3)}
    line.update(notes)
    line["counters"] = {
        c: counter(after, c) - counter(before, c) for c in REPORTED
        if counter(after, c) != counter(before, c)}
    line["compile_wall_ms_so_far"] = round(sum(
        e.get("wall_ms") or 0.0
        for e in after["tpu_compile"]["events"]), 1)
    line["hbm_occupancy_bytes"] = after["tpu_hbm"]["occupancy_bytes"]
    line["device_peak_bytes"] = _device_peak_bytes()
    print(json.dumps(line), flush=True)
    for c in phase.must_increase:
        if not counter(after, c) > counter(before, c):
            raise SmokeFailure(
                f"phase {phase.name}: counter {c} did not move "
                f"({counter(before, c)} -> {counter(after, c)}): the device "
                "path this phase exists for did not serve it")
    for c in phase.must_stay:
        if counter(after, c) != counter(before, c):
            raise SmokeFailure(
                f"phase {phase.name}: counter {c} moved "
                f"({counter(before, c)} -> {counter(after, c)}): a route "
                "this phase may not take answered")
    for c in ZERO_COUNTERS:
        moved = counter(after, c) - counter(smoke.baseline, c)
        if moved:
            tb = smoke.first_traceback[0] if smoke.first_traceback \
                else "(nothing logged)"
            raise SmokeFailure(
                f"phase {phase.name}: {c} rose by {moved} — a host tier "
                f"answered for the device. First logged error:\n{tb}")
    return line


def _bulk(s: "Smoke", index: str, lines: List[str]) -> int:
    resp = s.request("POST", f"/{index}/_bulk",
                     "\n".join(lines) + "\n", ndjson=True)
    if resp.get("errors"):
        raise SmokeFailure(f"_bulk into {index} reported errors")
    bad = [it for it in resp["items"]
           if next(iter(it.values())).get("status") != 201]
    if bad:
        raise SmokeFailure(f"_bulk item not 201: {bad[0]}")
    return len(resp["items"])


def _text_doc_lines(c: Corpus, lo: int, hi: int, id0: int = 0,
                    extra: str = "") -> List[str]:
    lines = []
    for i in range(lo, hi):
        lines.append('{"index":{"_id":"%d"}}' % (id0 + i))
        lines.append('{"body":"%s%s","tag":"tag%d","ts":%d,"n":%d}' % (
            extra, c.body(i), c.tags[i], TS0 + 60_000 * (id0 + i),
            c.nums[i]))
    return lines


def phase_ingest(s: Smoke) -> dict:
    cl, c = s.client, s.corpus
    cl.request("PUT", f"/{s.index}", {
        "settings": {"number_of_shards": s.n_shards, "number_of_replicas": 0},
        "mappings": {"properties": {
            "body": {"type": "text"}, "tag": {"type": "keyword"},
            "ts": {"type": "date"}, "n": {"type": "integer"}}}})
    cl.request("PUT", f"/{s.vec_index}", {
        "settings": {"number_of_shards": s.n_shards, "number_of_replicas": 0},
        "mappings": {"properties": {
            "vec": {"type": "dense_vector", "dims": VEC_DIMS,
                    "similarity": "cosine"},
            "tag": {"type": "keyword"}}}})
    # two refreshes on the way, so the snapshot holds three segments
    n_batches = -(-c.n // BULK_BATCH)
    refresh_at = {n_batches // 3, n_batches * 2 // 3}
    n_bulk = 0
    for b, lo in enumerate(range(0, c.n, BULK_BATCH)):
        n_bulk += _bulk(s, s.index,
                        _text_doc_lines(c, lo, min(lo + BULK_BATCH, c.n)))
        if b + 1 in refresh_at:
            s.request("POST", f"/{s.index}/_refresh")
    s.request("POST", f"/{s.index}/_refresh")
    text_s = s.served_s
    nv = len(s.vectors)
    vb = max(1, BULK_BATCH // 10)
    for lo in range(0, nv, vb):
        lines = []
        for i in range(lo, min(lo + vb, nv)):
            lines.append('{"index":{"_id":"%d"}}' % i)
            lines.append('{"vec":%s,"tag":"g%d"}' % (
                json.dumps(s.vectors[i].tolist()), s.vec_tags[i]))
        n_bulk += _bulk(s, s.vec_index, lines)
        if lo // vb == (nv // vb) // 2:
            s.request("POST", f"/{s.vec_index}/_refresh")
    s.request("POST", f"/{s.vec_index}/_refresh")
    for index, n in ((s.index, c.n), (s.vec_index, nv)):
        got = cl.request("GET", f"/{index}/_count")["count"]
        if got != n:
            raise SmokeFailure(f"{index}/_count = {got}, bulk acked {n}")
    # an acknowledged write is read back
    rng = np.random.default_rng(s.seed + 2)
    for i in rng.choice(c.n, size=min(10, c.n), replace=False):
        doc = cl.request("GET", f"/{s.index}/_doc/{int(i)}")
        if not doc.get("found") or doc["_source"]["body"] != c.body(int(i)):
            raise SmokeFailure(f"acked doc {i} not read back")
    segs = cl.node.indices.get(s.index).stats()["segments"]["count"]
    return {"bulk_items": n_bulk, "docs": c.n, "vectors": nv,
            "segments": segs, "text_bulk_s": round(text_s, 1),
            "vector_bulk_s": round(s.served_s - text_s, 1)}


def _term_ranks(c: Corpus):
    """(hot, cold) term-rank pools: hot terms get a dense column in every
    big segment (df well above TurboBM25's COLD_DF), cold ones ride the
    sparse tier but still occur."""
    df = np.bincount(c.tokens, minlength=VOCAB)
    # each third-of-the-corpus segment must still see df >= COLD_DF (16384)
    hot = np.nonzero(df // 3 >= 20_000)[0]
    if len(hot) < 8:                       # tiny rehearsal corpora
        hot = np.argsort(-df)[:16]
    cold = np.nonzero((df >= 3) & (df < max(df[hot].min() // 8, 4)))[0]
    return hot, cold


def _check_served(s: Smoke, index: str, bodies: Sequence[dict],
                  resps: Sequence[dict], *, score_rtol: float,
                  tie_rtol: float = 1e-6,
                  engines: Optional[Tuple[str, ...]],
                  totals: str = "equal") -> dict:
    if s.n_shards > 1 and totals == "equal":
        totals = "capped"
    displaced = 0
    seen = set()
    for body, got in zip(bodies, resps):
        if got.get("timed_out") or got["_shards"]["failed"]:
            raise SmokeFailure(f"{body}: timed_out/_shards {got['_shards']}")
        if engines is not None and body.get("profile"):
            eng = engine_of(got)
            seen.add(eng)
            if eng not in engines:
                raise SmokeFailure(
                    f"{json.dumps(body)[:200]}: profile names engine="
                    f"{eng}, wanted one of {engines}")
        try:
            displaced += compare_hits(got, s.reference(index, body),
                                      score_rtol=score_rtol,
                                      tie_rtol=tie_rtol, totals=totals)
        except SmokeFailure as e:
            raise SmokeFailure(f"{json.dumps(body)[:300]}: {e}") from None
    return {"requests": len(bodies), "near_tie_displacements": displaced,
            "engines": sorted(e for e in seen if e)}


def _match_bodies(s: Smoke, n: int, seed: int) -> List[dict]:
    hot, cold = _term_ranks(s.corpus)
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        terms = [rng.choice(hot)]
        if i % 4 != 3:                      # 3 of 4 mix a cold term in
            terms.append(rng.choice(cold))
        if i % 2:
            terms.append(rng.choice(hot))
        out.append({"query": {"match": {
            "body": " ".join(f"t{t}" for t in terms)}},
            "size": K, "profile": True})
    return out


def phase_match(s: Smoke) -> dict:
    singles = _match_bodies(s, 32, s.seed + 10)
    resps = [s.search(s.index, b) for b in singles]
    batch = _match_bodies(s, 256, s.seed + 11)
    nd = "".join(json.dumps({"index": s.index}) + "\n" + json.dumps(b) + "\n"
                 for b in batch)
    mresp = s.request(
        "POST", f"/_msearch?search_type={s.search_type}", nd, ndjson=True)
    notes = _check_served(s, s.index, singles + batch,
                          resps + mresp["responses"], score_rtol=1e-6,
                          engines=TURBO_ENGINES)
    routing = s.client.stats()["tpu_hbm"]["routing"]["last"]
    if routing.get("reason") != s.routing_reason:
        raise SmokeFailure(f"tpu_hbm.routing.last = {routing}")
    return notes


def phase_bool(s: Smoke) -> dict:
    # TurboBM25 serves a bool whose clauses share ONE postings field
    # (serving._turbo_bool_spec): the conjunction is intersected on the
    # device, a hot clause by its column's presence bits, a cold one
    # (every other request's must term here) by its cold row
    hot, cold = _term_ranks(s.corpus)
    rng = np.random.default_rng(s.seed + 20)
    bodies = []
    for i in range(32):
        a, f, x = rng.choice(hot, size=3, replace=False)
        if i % 2 and len(cold):
            a = rng.choice(cold)
        bodies.append({"query": {"bool": {
            "must": [{"match": {"body": f"t{a}"}}],
            "filter": [{"term": {"body": f"t{f}"}}],
            "must_not": [{"term": {"body": f"t{x}"}}]}},
            "size": K, "profile": True})
    resps = [s.search(s.index, b) for b in bodies]
    return _check_served(s, s.index, bodies, resps, score_rtol=1e-6,
                         engines=TURBO_ENGINES)


def phase_phrase(s: Smoke) -> dict:
    # the 16 commonest adjacent pairs (a pair that is rare in a segment
    # is answered from its conjunction mask, a common one by the
    # mask-gated sweep: both on the device)
    c = s.corpus
    inside = np.ones(len(c.tokens) - 1, bool)
    inside[c.bounds[1:-1] - 1] = False         # pairs spanning two docs
    pairs = (c.tokens[:-1].astype(np.int64) * VOCAB + c.tokens[1:])[inside]
    keys, counts = np.unique(pairs, return_counts=True)
    bodies = []
    for key in keys[np.argsort(-counts, kind="stable")[:16]]:
        a, b = divmod(int(key), VOCAB)
        bodies.append({"query": {"match_phrase": {
            "body": {"query": f"t{a} t{b}", "slop": 0}}},
            "size": K, "profile": True})
    resps = [s.search(s.index, b) for b in bodies]
    return _check_served(s, s.index, bodies, resps, score_rtol=1e-6,
                         engines=TURBO_ENGINES)


def phase_knn(s: Smoke) -> dict:
    rng = np.random.default_rng(s.seed + 40)
    bodies = []
    for j, i in enumerate(rng.choice(len(s.vectors), size=32, replace=False)):
        q = s.vectors[i] + 0.1 * rng.standard_normal(VEC_DIMS)
        knn = {"field": "vec", "k": K, "num_candidates": 100,
               "query_vector": np.round(q, 4).tolist()}
        if j % 2:
            knn["filter"] = {"term": {"tag": f"g{s.vec_tags[i]}"}}
        bodies.append({"knn": knn, "size": K})
    uncertified = counter(s.client.stats(), "tpu_knn.knn_uncertified")
    resps = [s.search(s.vec_index, b) for b in bodies]
    uncertified = counter(s.client.stats(),
                          "tpu_knn.knn_uncertified") - uncertified
    # over several shards the dense executor totals k PER SHARD where the
    # served path (like the upstream project) reports the k it returns
    notes = _check_served(s, s.vec_index, bodies, resps,
                          score_rtol=1e-5, engines=None,
                          totals="equal" if s.n_shards == 1 else "skip")
    # an uncertified (query, partition) pair is re-run on the f32 dense
    # route, which is the reference's own program: only a certified pair
    # holds the int8 pass + rescore to the reference
    parts = s.client.node.indices.get(s.vec_index).stats()["segments"]["count"]
    notes["partitions"] = parts
    notes["certified_pairs"] = len(bodies) * parts - uncertified
    filtered_pairs = sum("filter" in b["knn"] for b in bodies) * parts
    if notes["certified_pairs"] < filtered_pairs // 2:
        raise SmokeFailure(
            f"only {notes['certified_pairs']} of {len(bodies) * parts} "
            "(query, partition) pairs were certified: the int8 pass + "
            "rescore was hardly compared with the reference")
    return notes


def phase_aggs(s: Smoke) -> dict:
    bodies = [
        {"size": 0, "aggs": {"tags": {"terms": {"field": "tag",
                                                "size": 20}}}},
        {"size": 0, "aggs": {"days": {
            "date_histogram": {"field": "ts", "calendar_interval": "day"},
            "aggs": {"n_avg": {"avg": {"field": "n"}}}}}},
    ]
    for body in bodies:
        got = s.search(s.index, body)
        ref = s.reference(s.index, body)
        if got["hits"]["total"] != ref["hits"]["total"] \
                or got.get("aggregations") != ref.get("aggregations"):
            raise SmokeFailure(
                f"{json.dumps(body)}: aggregations differ from the "
                f"reference: {json.dumps(got.get('aggregations'))[:300]} vs "
                f"{json.dumps(ref.get('aggregations'))[:300]}")
    return {"requests": len(bodies)}


def phase_refresh(s: Smoke) -> dict:
    """1,000 more docs become searchable: the engine is rebuilt for the
    new snapshot and the device serves it again."""
    c = s.corpus
    n_new = min(1000, c.n)
    _bulk(s, s.index,
          _text_doc_lines(c, 0, n_new, id0=c.n, extra=FRESH_TERM + " "))
    s.request("POST", f"/{s.index}/_refresh")
    hot, _ = _term_ranks(c)
    body = {"query": {"match": {"body": f"{FRESH_TERM} t{hot[0]}"}},
            "size": K, "profile": True}
    got = s.search(s.index, body)
    notes = _check_served(s, s.index, [body], [got], score_rtol=1e-6,
                          engines=TURBO_ENGINES)
    new_ids = {str(c.n + i) for i in range(n_new)}
    ids = [h["_id"] for h in got["hits"]["hits"]]
    if len(ids) != K or not set(ids) <= new_ids:
        raise SmokeFailure(f"refresh: hits {ids} are not the new docs")
    return notes


PHASES = (
    Phase("ingest", phase_ingest),
    Phase("match", phase_match, ("tpu_turbo.partition_dispatches",
                                 "tpu_turbo.sparse_queries")),
    Phase("bool", phase_bool,
          ("tpu_turbo.bitset_packs", "tpu_turbo.bool_device",
           "tpu_turbo.bool_cold_lead"),
          ("tpu_turbo.bool_host", "tpu_turbo.bitset_gallop")),
    Phase("phrase", phase_phrase,
          ("tpu_turbo.bool_device", "tpu_turbo.phrase_builds"),
          ("tpu_turbo.bool_host", "tpu_turbo.bitset_gallop")),
    Phase("knn", phase_knn, ("tpu_knn.knn_int8_dispatches",
                             "tpu_knn.knn_queries")),
    Phase("aggs", phase_aggs, ("tpu_agg.agg_device_dispatches",)),
    Phase("refresh", phase_refresh, ("tpu_turbo.partition_dispatches",)),
)
# the sharded path and what it is compared with — nothing else
PHASES_4 = tuple(p for p in PHASES
                 if p.name in ("ingest", "match", "bool", "knn"))


def check_spread(stats: dict, n_devices: int) -> dict:
    """--chips 4: the fused path ran, merged on device, and the sharded
    engines' state is spread over all the chips."""
    import jax

    turbo = stats["tpu_turbo"]
    if not (turbo["fused_dispatches"] > 0 and turbo["merge_device"] > 0):
        raise SmokeFailure(f"fused path idle: {turbo}")
    spread = {}
    for name, eng in stats["tpu_hbm"]["engines"].items():
        if eng["kind"] not in ("fused_turbo", "knn"):
            continue                    # solo per-partition engines
        share = eng["occupancy_bytes"] / n_devices
        if eng["devices"] != n_devices or not (
                share / 2 <= eng["per_device_bytes"] <= share * 2):
            raise SmokeFailure(
                f"engine {name} is not spread over {n_devices} devices: "
                f"{ {k: eng[k] for k in ('devices', 'occupancy_bytes', 'per_device_bytes')} }")
        spread[name] = eng["per_device_bytes"]
    if {n.split("-")[0] for n in spread} != {"fused_turbo", "knn"}:
        raise SmokeFailure(f"sharded engines seen: {sorted(spread)}")
    # what each chip really holds (the ledger above is arithmetic)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.local_devices()]
    return {"phase": "spread", "per_device_bytes": spread,
            "device_bytes_in_use": in_use,
            "fused_dispatches": turbo["fused_dispatches"],
            "merge_device": turbo["merge_device"]}


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------

def run(smoke: Smoke, phases: Sequence[Phase]) -> None:
    """Run the phases in order. A failed query phase does not stop the
    ones after it (they are independent, and a chip run is too dear to
    learn one fault at a time); any failure fails the run at the end."""
    handler = _FirstTraceback(smoke.first_traceback)
    logging.getLogger().addHandler(handler)
    failures = []
    try:
        for phase in phases:
            try:
                run_phase(smoke, phase)
            except SmokeFailure as e:
                failures.append(str(e))
                print(f"chip_smoke: phase {phase.name} FAILED: {e}",
                      file=sys.stderr, flush=True)
                if phase.name == "ingest":
                    break               # nothing to query
    finally:
        logging.getLogger().removeHandler(handler)
    if failures:
        msg = "\n".join(failures)
        if smoke.first_traceback and "First logged error" not in msg:
            msg += f"\nFirst logged error:\n{smoke.first_traceback[0]}"
        raise SmokeFailure(msg)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found {dev} — this script proves the TPU "
              "path and does not continue without one", file=sys.stderr)
        return 2
    if dev["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{dev['count']} devices", file=sys.stderr)
        return 2

    from elasticsearch_tpu.__main__ import start_node

    t0 = time.monotonic()
    node, server = start_node(port=0, name="smoke-node")
    client = Client(node, server)
    sharded = args.chips > 1
    try:
        vectors, vec_tags = make_vectors(N_VECTORS, args.seed,
                                         VEC_TAGS // args.chips)
        smoke = Smoke(
            client=client, seed=args.seed,
            index="smoke4" if sharded else "smoke",
            vec_index="smoke4_vec" if sharded else "smoke_vec",
            corpus=make_corpus(N_DOCS, args.seed),
            vectors=vectors, vec_tags=vec_tags,
            n_shards=args.chips,
            # a multi-shard index reaches the fused engine with global
            # (dfs) statistics, which is also how the reference is asked
            search_type="dfs_query_then_fetch" if sharded
            else "query_then_fetch")
        run(smoke, PHASES_4 if sharded else PHASES)
        if sharded:
            print(json.dumps(check_spread(client.stats(), args.chips)),
                  flush=True)
        print(json.dumps({"phase": "total",
                          "wall_s": round(time.monotonic() - t0, 1),
                          "compile": client.stats()["tpu_compile"]}),
              flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        client.close()
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
