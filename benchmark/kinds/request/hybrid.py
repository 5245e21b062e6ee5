"""Request kind `hybrid`: one search body with a `query` AND a top-level
`knn` section over a corpus of kind `text_vectors` (Elasticsearch's hybrid
retrieval: the hits are the query's matches united with the `k` nearest
vectors, a document's score the SUM of its query score and, where it is
among the `k` nearest, its vector score).

The `query` side is request kind `match`'s (`terms_cycle`, `term_zipf_s`,
`size`), the `knn` side request kind `knn`'s without a filter (`k`,
`num_candidates`, `noise`), both made by those kinds themselves. What
ties them: `terms_from_doc` of a query's terms (rounded down, at least
one) are distinct tokens of the passage the query vector starts from, the
rest the match kind's own Zipf ranks: a user's words overlap the passage
they are after, so the nearest passage usually ALSO matches and the sum
is worked.

The plain reference is the two kinds' references, summed: float64 BM25
(`BM25Reference`) plus the exact cosine over bfloat16-rounded operands
(`KnnReference`) for the `k` best rows; `total` = documents with a
positive sum. Its controls put ONE side a precision step down:
"bfloat16" the BM25 side, "int8" the vector side.

The comparison is the hit list's with two additions (`numbers`): the cut
of the `k` nearest is held with a band, and `knn_part_err` reads the
vector addend at its own size.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmark.compare import hit_list_numbers
from benchmark.compare import hits_well_formed as well_formed  # noqa: F401
from benchmark.datagen import term_name
from benchmark.manifest import load_kind
from benchmark.reference import TOTAL_CAP, top_hits
from benchmark.traffic import Request

_BDIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_match = load_kind(_BDIR, "request", "match")
_knn = load_kind(_BDIR, "request", "knn")


@dataclass
class HybridRequest(Request):
    ranks: Optional[np.ndarray] = None     # the query's term ranks, in order
    doc: int = -1                          # source passage's ordinal
    vector: Optional[np.ndarray] = None    # the query vector as sent, f32
    tag_lo: int = -1                       # no filter (KnnReference reads
    tag_hi: int = -1                       # the pair)


def _vector_view(config: dict) -> dict:
    """The configuration as kind `knn` reads it: `index.field` is the
    vector field there."""
    return dict(config, index=dict(config["index"],
                                   field=config["index"]["vector_field"]))


class Requests:
    """The canonical requests of a mix: lengths and the Zipf-drawn ranks
    of request j are the mix's (`plan_seed`, as kind `match` draws them),
    its source passage the run's draw (as kind `knn` draws it), and the
    passage's own tokens among its terms follow from the two."""

    def __init__(self, req: dict, config: dict, traffic: dict, pool: int,
                 rng: np.random.Generator, corpus):
        self.req = req
        self.field = config["index"]["field"]
        self.parts = corpus
        self._knn = _knn.Requests(req, _vector_view(config), traffic, pool,
                                  rng, corpus)
        self._match = _match.Requests(req, config, traffic, pool, rng,
                                      corpus)
        self._token_seed = int(rng.integers(0, 2 ** 31))

    def variants(self) -> list:
        """One pair of programs on the device, whatever the request."""
        return [None]

    def is_variant(self, j: int, variant) -> bool:
        return True

    def _ranks(self, j: int, doc: int) -> np.ndarray:
        zipf = self._match.request(j).ranks
        part = next(p for p in self.parts if p.doc0 <= doc < p.doc0 + p.n)
        i = doc - part.doc0
        own = np.unique(part.tokens[part.bounds[i]:part.bounds[i + 1]])
        n_own = min(len(own), max(1, int(
            len(zipf) * float(self.req["terms_from_doc"]))))
        own = np.random.default_rng([self._token_seed, j]).choice(
            own, size=n_own, replace=False).astype(np.int64)
        rest = [int(r) for r in zipf if r not in set(own.tolist())]
        return np.concatenate([own, np.asarray(
            rest[:len(zipf) - n_own], np.int64)])

    def request(self, j: int) -> HybridRequest:
        near = self._knn.request(j)
        ranks = self._ranks(j, near.doc)
        return HybridRequest(
            body={"query": {"match": {self.field: " ".join(
                term_name(int(r)) for r in ranks)}},
                "knn": near.body["knn"], "size": int(self.req["size"])},
            ranks=ranks, doc=near.doc, vector=near.vector)


def top_k(req: dict) -> int:
    return int(req["size"])


class HybridReference:
    """BM25 of the `query` plus, for the `k` nearest rows, the vector
    score of the `knn` section; a hit needs a positive sum; ties go to
    the lower ordinal. An answer holds, beside the hit list's members,
    what the band and `knn_part_err` read: `vec` (the request's vector
    scores of every row), `nn` (the `k` nearest) and `kth` (the least
    vector score among them)."""

    def __init__(self, config: dict, parts: Sequence,
                 precision: Optional[str] = None):
        if precision not in (None, "bfloat16", "int8"):
            raise ValueError(precision)
        self.bm25 = _match.reference(
            config, parts, "bfloat16" if precision == "bfloat16" else None)
        self.knn = _knn.reference(
            config, parts, "int8" if precision == "int8" else None)

    def answers(self, reqs: Sequence, k: int) -> List[dict]:
        self.bm25.prepare([r for q in reqs for r in q.ranks])
        vec = self.knn.all_scores(reqs)
        out = []
        for i, q in enumerate(reqs):
            nn, near = top_hits(vec[i], int(q.body["knn"]["k"]))
            s = self.bm25.scores(q.ranks)
            s[nn] += near.astype(np.float64)
            ords, top = top_hits(s, k)
            out.append({"scores": s, "ords": ords, "top": top,
                        "total": int(np.count_nonzero(s > 0)),
                        "vec": vec[i], "nn": nn,
                        "kth": float(near[-1]) if len(near) else 0.0})
        return out


def reference(config: dict, parts: Sequence,
              precision: Optional[str] = None) -> HybridReference:
    return HybridReference(config, parts, precision)


def _total_of(n: int) -> dict:
    return ({"value": TOTAL_CAP, "relation": "gte"} if n > TOTAL_CAP
            else {"value": n, "relation": "eq"})


def banded(resp: dict, ref: dict, gap: float) -> dict:
    """`ref` with the cut of the `k` nearest held with a band.

    Sound runs may swap the k-th and the (k+1)-th nearest where their
    vector scores differ by float32 summation order (the kNN cell
    tolerates that as `rank_gap`); here such a swap moves a whole addend,
    about a twentieth of a score. So where rows on BOTH sides of the cut
    lie within `gap` (relative) of the reference's k-th vector score,
    each of them is accepted on either side: a served one is held
    against the reference's score with the addend or without, whichever
    is nearer; one not served counts without; and `total` may be any
    count between none and all of them matching. Outside the band, and
    where no row stands on the other side of the cut, nothing is
    forgiven. The answer also says which rows then count among the
    nearest (`nn`)."""
    vec, nn, kth = ref["vec"], ref["nn"], ref["kth"]
    near = np.flatnonzero(np.abs(vec - np.float32(kth)) <= gap * kth)
    inside = np.isin(near, nn)
    if inside.all() or not inside.any():
        return ref
    served = {int(h["_id"]): float(h["_score"])
              for h in resp["hits"]["hits"]}
    scores = ref["scores"].copy()
    alone = scores[near] - np.where(inside, vec[near], 0.0)
    outside = int(np.count_nonzero(scores > 0)
                  - np.count_nonzero(scores[near] > 0))
    with_addend = []
    for d, b, v in zip(near.tolist(), alone.tolist(), vec[near].tolist()):
        s = served.get(d)
        scores[d] = b
        if s is not None and abs(s - (b + v)) < abs(s - b):
            scores[d] = b + v
            with_addend.append(d)
    ords, top = top_hits(scores, len(ref["ords"]))
    low = outside + int(np.count_nonzero(alone > 0))
    total = next((t for t in range(low, outside + len(near) + 1)
                  if _total_of(t) == resp["hits"]["total"]), ref["total"])
    return dict(ref, scores=scores, ords=ords, top=top, total=total,
                nn=np.asarray(sorted(set(nn.tolist()) - set(near.tolist())
                                     | set(with_addend)), np.int64))


def knn_part_err(resp: dict, ref: dict) -> float:
    """For every served hit the reference counts among the `k` nearest:
    |(served score - reference BM25 of that document) - reference vector
    score| / reference vector score. The vector addend is a twentieth of
    a sum, so `score_err` alone would let a vector side one precision
    step down pass by a factor of two or less; this number reads it at
    its own size."""
    worst = 0.0
    nearest = set(ref["nn"].tolist())
    for h in resp["hits"]["hits"]:
        d = int(h["_id"])
        if d in nearest:
            v = float(ref["vec"][d])
            bm25 = float(ref["scores"][d]) - v
            worst = max(worst, abs((float(h["_score"]) - bm25) - v) / v)
    return worst


def numbers(pairs: Sequence, limits: dict, k: int) -> Dict[str, dict]:
    """The hit list's numbers over the banded references, then
    `knn_part_err` beside its limit."""
    held = [(resp, banded(resp, ref, float(limits["rank_gap"])))
            for resp, ref in pairs]
    out = hit_list_numbers(held, limits, k)
    worst = max((knn_part_err(resp, ref) for resp, ref in held),
                default=0.0)
    limit = float(limits["knn_part_err"])
    out["knn_part_err"] = {"value": worst, "limit": limit,
                           "ok": bool(worst <= limit)}
    return out
