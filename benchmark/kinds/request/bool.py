"""Request kind `bool`: one conjunction over a positional text corpus, in
the shapes Lucene's nightly benchmark (luceneutil) names its tasks by:
the clauses' kinds and the document-frequency band of each term.

The mix's `request` block gives `bands` (name -> [first rank, last rank]
of the configuration's vocabulary, 0 = most frequent; a term is drawn
within its band with weight (rank + 1)^-`term_zipf_s`, as kind `match`
draws over the whole vocabulary), `shapes` (name -> which clause groups
hold a term of which band: `must` and `should` are single-term `match`
clauses, `filter` and `must_not` `term` clauses, `phrase` one
`match_phrase` of slop 0) and `cycle` (request j has shape
cycle[j % len(cycle)]); `size`.

Its plain reference is numpy over the corpus kind's token arrays and
imports nothing of the program: BM25 in float64 with the index's own
statistics (kind `match`'s `BM25Reference` gives postings, lengths and
the formula). A document matches when every `must`, `filter` and phrase
clause occurs in it and no `must_not` term does; its score is the sum of
its `must` terms' and, where they occur, its `should` terms' BM25 terms;
`filter` and `must_not` score nothing. A phrase occurs where its terms
stand side by side inside one document, and scores as ONE term whose tf
is the phrase's frequency in the document and whose idf is the SUM of
its terms' idfs: Lucene's exact PhraseQuery under BM25Similarity
(`idfExplain` over the phrase's terms; `PhraseScorer`'s freq). No
departure is known. `hits.total` is the number of matching documents.

Two controls put the reference in the program's place: "bfloat16" (every
step of the score rounded one precision below the float32 the
configuration states) and "drop_clause" (the last required clause left
out: another conjunction's answer); a sound comparison calls both wrong.
"""

from __future__ import annotations

import functools
import json
import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmark.compare import hit_list_numbers
from benchmark.compare import hits_well_formed as well_formed  # noqa: F401
from benchmark.datagen import STREAM_TRAFFIC, rng_for, term_name
from benchmark.manifest import load_kind
from benchmark.reference import bf16_round, top_hits
from benchmark.traffic import Request

_match = load_kind(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "request", "match")

GROUPS = ("must", "should", "filter", "must_not", "phrase")


@dataclass
class BoolRequest(Request):
    shape: str = ""
    # clause group -> term ranks, in clause order
    ranks: Dict[str, List[int]] = field(default_factory=dict)


def canonical_ranks(req: dict, vocab: int, plan_seed: int,
                    pool: int) -> List[Dict[str, List[int]]]:
    """`pool` requests as {group: [distinct term ranks]} (one stream of
    draws: a larger pool begins with a smaller one's requests)."""
    rng = rng_for(plan_seed, STREAM_TRAFFIC)
    s = float(req["term_zipf_s"])
    cdfs = {}
    for name, (lo, hi) in req["bands"].items():
        lo, hi = int(lo), min(int(hi), vocab - 1)
        w = 1.0 / np.arange(lo + 1, hi + 2, dtype=np.float64) ** s
        cdfs[name] = (lo, np.cumsum(w) / w.sum())
    cycle = list(req["cycle"])
    out = []
    for i in range(pool):
        shape = req["shapes"][cycle[i % len(cycle)]]
        used: List[int] = []
        ranks: Dict[str, List[int]] = {}
        for group in GROUPS:
            for band in shape.get(group, ()):
                lo, cdf = cdfs[band]
                while True:
                    r = lo + int(min(np.searchsorted(
                        cdf, rng.random(), side="right"), len(cdf) - 1))
                    if r not in used:
                        break
                used.append(r)
                ranks.setdefault(group, []).append(r)
        out.append(ranks)
    return out


@functools.lru_cache(maxsize=4)
def _ranks_of(req_json: str, vocab: int, plan_seed: int, pool: int):
    # a run makes its mix twice (set-up, window): the same draws, once
    return canonical_ranks(json.loads(req_json), vocab, plan_seed, pool)


class Requests:
    """The canonical requests of a mix: request j comes from the mix's
    `plan_seed` and j, not from the run's seed."""

    def __init__(self, req: dict, config: dict, traffic: dict, pool: int,
                 rng: np.random.Generator, corpus):
        self.req = req
        self.field = config["index"]["field"]
        self._ranks = _ranks_of(
            json.dumps(req, sort_keys=True), int(config["corpus"]["vocab"]),
            int(traffic["plan_seed"]), pool)

    def variants(self) -> list:
        """One set of programs on the device, whatever the shape."""
        return [None]

    def is_variant(self, j: int, variant) -> bool:
        return True

    def request(self, j: int) -> BoolRequest:
        ranks = self._ranks[j]
        f = self.field
        if "phrase" in ranks:
            query = {"match_phrase": {f: {"query": " ".join(
                term_name(r) for r in ranks["phrase"]), "slop": 0}}}
        else:
            query = {"bool": {
                g: [{kind: {f: term_name(r)}} for r in ranks[g]]
                for g, kind in (("must", "match"), ("should", "match"),
                                ("filter", "term"), ("must_not", "term"))
                if g in ranks}}
        cycle = self.req["cycle"]
        return BoolRequest(
            body={"query": query, "size": int(self.req["size"])},
            shape=cycle[j % len(cycle)], ranks=ranks)


def top_k(req: dict) -> int:
    return int(req["size"])


def numbers(pairs: Sequence, limits: dict, k: int) -> Dict[str, dict]:
    """The shared hit-list comparison (ids, order, scores, `hits.total`
    exact up to the cap), under this kind's own name: what else decides
    `correct` here is the configuration's (`must_rise`: the device bool
    route and its cold lead answered; `must_stay`: no host intersection
    did), and tests/bench_harness/test_bench_bool.py holds both."""
    return hit_list_numbers(pairs, limits, k)


class BoolReference(_match.BM25Reference):
    """The conjunction's answer over one index (module docstring)."""

    def __init__(self, segments: Sequence, k1: float, b: float,
                 precision: Optional[str] = None):
        if precision not in (None, "bfloat16", "drop_clause"):
            raise ValueError(precision)
        super().__init__(segments, k1, b,
                         "bfloat16" if precision == "bfloat16" else None)
        self.drop = precision == "drop_clause"

    def _rnd(self, a):
        return (bf16_round(np.asarray(a, np.float32)).astype(np.float64)
                if self.low else a)

    def _idf(self, df: int) -> float:
        return self._rnd(np.log(1.0 + (self.n - df + 0.5) / (df + 0.5)))

    def _part(self, idf, docs, tf):
        """The BM25 term of (idf, tf) at docs, rounded as `scores` does."""
        rnd = self._rnd
        norm = rnd(self.k1 * (1.0 - self.b
                              + self.b * self.dl[docs] / self.avgdl))
        return rnd(idf * rnd(tf * (self.k1 + 1.0) / rnd(tf + norm)))

    def phrase(self, ranks: Sequence[int]):
        """(docs ascending, phrase frequency f64) of the terms side by
        side inside one document."""
        docs = []
        for s in self.segments:
            tok, n = s.tokens, len(ranks)
            at = np.flatnonzero(tok[: len(tok) - n + 1] == ranks[0])
            for i, r in enumerate(ranks[1:], 1):
                at = at[tok[at + i] == r]
            doc = np.searchsorted(s.bounds, at, side="right") - 1
            docs.append(doc[at + n <= s.bounds[doc + 1]] + s.doc0)
        docs, pf = np.unique(np.concatenate(docs), return_counts=True)
        return docs, pf.astype(np.float64)

    def answer(self, req: BoolRequest, k: int) -> dict:
        g = {name: list(req.ranks.get(name, ())) for name in GROUPS}
        if self.drop:       # the control: the last required clause goes
            for name in ("filter", "phrase", "must"):
                if g[name] and len(g["must"] + g["filter"]
                                   + g["phrase"]) > 1:
                    g[name].pop()
                    break
        if len(g["phrase"]) == 1:          # a phrase of one term is a term
            g["must"], g["phrase"] = g["must"] + g["phrase"], []
        total = np.zeros(self.n, np.float64)
        match: Optional[np.ndarray] = None
        for r in g["must"] + g["filter"]:
            docs = self._post[int(r)][0]
            match = docs if match is None else match[
                np.isin(match, docs, assume_unique=True)]
        if g["phrase"]:
            pdocs, pf = self.phrase(g["phrase"])
            keep = np.ones(len(pdocs), bool) if match is None else np.isin(
                pdocs, match, assume_unique=True)
            match, pf = pdocs[keep], pf[keep]
            idf = self._rnd(sum(self._idf(len(self._post[int(r)][0]))
                                for r in g["phrase"]))
            total[match] = self._part(idf, match, pf)
        for r in g["must_not"]:
            match = match[~np.isin(match, self._post[int(r)][0],
                                   assume_unique=True)]
        for r in g["must"] + g["should"]:
            docs, tf = self._post[int(r)]
            at = np.isin(docs, match, assume_unique=True)
            docs, tf = docs[at], tf[at]
            if len(docs):
                total[docs] = self._rnd(total[docs] + self._part(
                    self._idf(len(self._post[int(r)][0])), docs, tf))
        s = np.zeros(self.n, np.float64)
        s[match] = total[match]
        ords, top = top_hits(s, k)
        return {"scores": s, "ords": ords, "top": top,
                "total": int(len(match))}

    def answers(self, reqs: Sequence, k: int) -> List[dict]:
        self.prepare([r for q in reqs for rs in q.ranks.values()
                      for r in rs])
        with ThreadPoolExecutor(8) as pool:
            return list(pool.map(lambda q: self.answer(q, k), reqs))


def reference(config: dict, parts: Sequence,
              precision: Optional[str] = None) -> BoolReference:
    return BoolReference(parts, config["bm25"]["k1"], config["bm25"]["b"],
                         precision=precision)
