"""One general generator for every traffic mix.

A mix is a JSON file of parameters (benchmark/traffic/<name>.json):

    loop        "open": arrivals on a schedule, one POST /{index}/_search
                per arrival.
                "closed": `clients` callers, each posting its next
                `_msearch` of `request.batch` searches when the last one
                is answered (an offline evaluation job); no rate
    rate_per_s  open: requests due per second (fixed, never searched)
    connections open: worker threads, one HTTP connection each
    clients     closed: callers, one HTTP connection each
    plan_seed   the mix's own seed (see below)
    request     what one search asks for:
        kind "match": `terms_cycle` (query lengths, cycled), ranks drawn
             Zipf(`term_zipf_s`) over the configuration's vocabulary, so
             stop-word-like and rare terms both occur; `size`
        kind "knn": a corpus vector + `noise` * N(0, 1); `k`,
             `num_candidates`; `filter_cycle` (cycled): 0 = no filter,
             n = a filter on the n adjacent tag values that hold the
             source vector's own (`term` for 1, `terms` for more)
        batch  closed: searches in one `_msearch` body
    warmup      what set-up sends before the window (run.warm_up)

The configuration adds what every search path of its index carries:
`index.search_params` (a multi-shard index reaches the device only with
`search_type=dfs_query_then_fetch`), on `_search` and `_msearch` alike.

The WORK is the same for every `--seed`. A mix has POOL canonical
requests, numbered; query lengths, term ranks and filter widths of
request j come from `plan_seed` and j, not from the run's seed. A window
of n calls sends the canonical requests first .. first + n - 1, each
once, in an order the run's seed draws; its gaps are the n stratified
quantiles of the exponential law at the mix's rate, in another order of
the same seed. The seed also draws the corpus (so the same rank is
another term's postings, the same number another vector). Two seeds
therefore differ in order and in data, never in how much was asked.
Call i of a closed span is canonical requests first + i * batch ..
first + (i + 1) * batch - 1 in that order, for every seed: which calls a
window holds depends only on how many it completes.

Warm-up and lead-in take their requests from the upper half of the pool
(WINDOW .. POOL - 1), a window from the lower (0 .. WINDOW - 1): what
set-up sends, the window never sends.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import List, Optional, Sequence
from urllib.parse import urlencode

import numpy as np

from benchmark.datagen import STREAM_TRAFFIC, rng_for, term_name, zipf_cdf

POOL = 16384         # canonical requests of a mix
WINDOW = POOL // 2   # the lower half is the windows', the upper set-up's


@dataclass
class Request:
    """One search as sent (body) and as the reference needs it."""
    body: dict
    ranks: Optional[np.ndarray] = None     # match: term ranks, in order
    doc: int = -1                          # knn: source vector's ordinal
    vector: Optional[np.ndarray] = None    # knn: the query as sent, f32
    tag_lo: int = -1                       # knn: the filter keeps tags in
    tag_hi: int = -1                       # [tag_lo, tag_hi); -1 = none


@dataclass
class Schedule:
    """The calls of one open-loop span: when each is due (seconds from
    its start, ascending) and which canonical request it sends."""
    due: np.ndarray
    index: np.ndarray


def stratified_gaps(n: int, rate: float) -> np.ndarray:
    """n inter-arrival gaps: the (i + 1/2)/n quantiles of Exp(rate)."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def canonical_ranks(req: dict, vocab: int, plan_seed: int) -> List[np.ndarray]:
    """POOL match queries as arrays of distinct term ranks."""
    rng = rng_for(plan_seed, STREAM_TRAFFIC)
    cdf = zipf_cdf(vocab, float(req["term_zipf_s"]))
    cycle = list(req["terms_cycle"])
    out = []
    for i in range(POOL):
        want = cycle[i % len(cycle)]
        ranks: List[int] = []
        while len(ranks) < want:
            r = int(min(np.searchsorted(cdf, rng.random(), side="right"),
                        vocab - 1))
            if r not in ranks:
                ranks.append(r)
        out.append(np.asarray(ranks, np.int64))
    return out


class Mix:
    """The requests of one run, as one seed draws them."""

    def __init__(self, traffic: dict, config: dict, seed: int, corpus):
        self.t = traffic
        self.req = traffic["request"]
        self.index = config["index"]["name"]
        self.closed = traffic.get("loop", "open") == "closed"
        self.batch = int(self.req.get("batch", 1))
        params = config["index"].get("search_params")
        self.query = "?" + urlencode(params) if params else ""
        self.rng = rng_for(seed, STREAM_TRAFFIC)
        self._warm_next = 0
        kind = self.req["kind"]
        if kind == "match":
            self.field = config["index"]["field"]
            self._ranks = canonical_ranks(
                self.req, int(config["corpus"]["vocab"]),
                int(traffic["plan_seed"]))
        elif kind == "knn":
            self.field = config["index"]["field"]
            self.tag_field = config["index"]["tag_field"]
            self.n_tags = int(config["corpus"]["tags"])
            self.parts = corpus                     # List[VectorSegment]
            n_docs = sum(p.n for p in corpus)
            self._docs = self.rng.integers(0, n_docs, size=POOL)
            self._noise_seed = int(self.rng.integers(0, 2 ** 31))
        else:
            raise ValueError(f"traffic request kind {kind!r}")

    # ---- the canonical requests -------------------------------------------

    def variants(self) -> list:
        """The request variants that are different programs on the
        device: kNN with a filter (True), without (False), and a batch
        that mixes both (None: set-up's requests as they come)."""
        if self.req["kind"] == "knn" and any(self.req.get("filter_cycle", ())):
            return [True, False, None]
        return [None]

    def _filter_width(self, j: int) -> int:
        cycle = self.req.get("filter_cycle") or [0]
        return int(cycle[j % len(cycle)])

    def request(self, j: int) -> Request:
        """Canonical request j (0 <= j < POOL) of this run."""
        if self.req["kind"] == "match":
            ranks = self._ranks[j]
            return Request(
                body={"query": {"match": {self.field: " ".join(
                    term_name(int(r)) for r in ranks)}},
                    "size": int(self.req["size"])},
                ranks=ranks)
        from benchmark.install import tag_name

        doc = int(self._docs[j])
        part = next(p for p in self.parts if p.doc0 <= doc < p.doc0 + p.n)
        noise = np.random.default_rng([self._noise_seed, j]).standard_normal(
            part.vectors.shape[1]).astype(np.float32)
        q = np.round(part.vectors[doc - part.doc0]
                     + np.float32(self.req["noise"]) * noise, 4)
        knn = {"field": self.field, "k": int(self.req["k"]),
               "num_candidates": int(self.req["num_candidates"]),
               "query_vector": q.astype(np.float64).round(4).tolist()}
        lo = hi = -1
        width = self._filter_width(j)
        if width:
            lo = int(part.tags[doc - part.doc0]) // width * width
            hi = min(lo + width, self.n_tags)
            names = [tag_name(t) for t in range(lo, hi)]
            knn["filter"] = ({"term": {self.tag_field: names[0]}}
                             if len(names) == 1
                             else {"terms": {self.tag_field: names}})
        return Request(
            body={"knn": knn, "size": int(self.req["k"])}, doc=doc,
            vector=np.asarray(knn["query_vector"], np.float32),
            tag_lo=lo, tag_hi=hi)

    def call(self, j: int):
        """(path, bytes, Request) of canonical request j on the wire."""
        r = self.request(j)
        return (f"/{self.index}/_search{self.query}",
                json.dumps(r.body).encode(), r)

    def msearch(self, js: Sequence[int]):
        """(path, ndjson bytes, [Request]) of one `_msearch` of the
        canonical requests `js`."""
        reqs = [self.request(int(j)) for j in js]
        head = json.dumps({"index": self.index})
        nd = "".join(f"{head}\n{json.dumps(r.body)}\n" for r in reqs)
        return f"/_msearch{self.query}", nd.encode(), reqs

    def closed_call(self, i: int, first: int = 0) -> np.ndarray:
        """The canonical requests of call i of a closed span."""
        js = first + i * self.batch + np.arange(self.batch)
        if js[-1] >= WINDOW:
            raise ValueError(
                f"call {i} of {self.batch} from request {first} leaves the "
                f"{WINDOW} requests that windows may send")
        return js

    # ---- which of them a span sends, and when -----------------------------

    def _due(self, seconds: float) -> np.ndarray:
        rate = float(self.t["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        due = np.cumsum(self.rng.permutation(stratified_gaps(n, rate)))
        return due[due < seconds]

    def window(self, seconds: float, first: int = 0) -> Schedule:
        """A measured span: canonical requests first .. first + n - 1."""
        due = self._due(seconds)
        if first + len(due) > WINDOW:
            raise ValueError(
                f"a window of {len(due)} calls from request {first} leaves "
                f"the {WINDOW} requests that windows may send")
        return Schedule(due, first + self.rng.permutation(len(due)))

    def warm(self, variant=None) -> int:
        """The next of set-up's own requests (of that variant)."""
        while True:
            j = WINDOW + self._warm_next % (POOL - WINDOW)
            self._warm_next += 1
            if variant is None or (self._filter_width(j) > 0) == bool(variant):
                return j

    def lead_in(self, seconds: float) -> Schedule:
        """The mix's own arrivals for `seconds`, on set-up's requests."""
        due = self._due(seconds)
        return Schedule(due, np.asarray([self.warm() for _ in due], np.int64))
