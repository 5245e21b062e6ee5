"""Plain references: the same semantics in straightforward numpy.

Nothing here imports the program or takes anything the program made. The
inputs are the seeded arrays of `datagen` and the requests as sent.

`precision` selects the arithmetic. `None` is the reference proper
(float64 BM25; kNN over bfloat16-rounded operands, exact products summed
in float32, which is what the configuration states). The controls put the reference
in the program's place one step below what the configuration states:
"bfloat16" for the float32 BM25 scores, "int8" for the bfloat16 vectors.
A sound comparison has to call those wrong (tests/bench_harness).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark.datagen import TextSegment, VectorSegment

TOTAL_CAP = 10_000      # hits.total is exact up to here, then "gte"


def bf16_round(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even),
    returned as float32."""
    x = np.ascontiguousarray(x, np.float32)
    bits = x.view(np.uint32)
    rounded = (bits + (np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                            & np.uint32(1)))
               ) & np.uint32(0xFFFF0000)
    return rounded.view(np.float32)


def top_hits(scores: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """(ordinals, scores) of the k best positive scores, ties by ordinal."""
    pos = np.flatnonzero(scores > 0)
    if len(pos) > 4 * k:
        kth = np.partition(scores[pos], len(pos) - k)[len(pos) - k]
        pos = pos[scores[pos] >= kth]
    sel = pos[np.lexsort((pos, -scores[pos]))][:k]
    return sel, scores[sel]


class BM25Reference:
    """BM25 (Lucene's form, exact lengths as norms) over one index:

        idf(t)  = ln(1 + (N - df + 0.5) / (df + 0.5))
        s(t, d) = idf(t) * tf * (k1 + 1) / (tf + k1 * (1 - b + b*dl/avgdl))

    summed over a query's distinct terms; a hit needs a positive score;
    ties go to the lower ordinal. Statistics are the index's own."""

    def __init__(self, segments: Sequence[TextSegment], k1: float, b: float,
                 precision: Optional[str] = None):
        if precision not in (None, "bfloat16"):
            raise ValueError(precision)
        self.segments = segments
        self.k1, self.b = float(k1), float(b)
        self.low = precision == "bfloat16"
        self.dl = np.concatenate([s.lens for s in segments]).astype(np.float64)
        self.n = len(self.dl)
        self.avgdl = float(self.dl.sum() / max(1, np.count_nonzero(self.dl)))
        self._post: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

    def prepare(self, ranks: Sequence[int]) -> None:
        """Postings (docs, tf) of the given term ranks, in one pass over
        the tokens."""
        need = sorted({int(r) for r in ranks} - set(self._post))
        if not need:
            return
        top = max(need) + 1
        slot = np.full(top, -1, np.int64)
        slot[need] = np.arange(len(need))

        def keys_of(s: TextSegment) -> np.ndarray:
            tok = s.tokens
            idx = np.flatnonzero(slot[np.minimum(tok, top - 1)] >= 0)
            idx = idx[tok[idx] < top]
            docs = np.searchsorted(s.bounds, idx, side="right") - 1 + s.doc0
            return slot[tok[idx]] * self.n + docs

        with ThreadPoolExecutor(len(self.segments)) as pool:
            keys = list(pool.map(keys_of, self.segments))
        key, tf = np.unique(np.concatenate(keys), return_counts=True)
        term, doc = key // self.n, key % self.n
        cut = np.searchsorted(term, np.arange(len(need) + 1))
        for j, r in enumerate(need):
            lo, hi = cut[j], cut[j + 1]
            self._post[r] = (doc[lo:hi], tf[lo:hi].astype(np.float64))

    def scores(self, ranks: Sequence[int]) -> np.ndarray:
        """[N] scores of one query; float64, or bfloat16-rounded at every
        step for the control."""
        self.prepare(ranks)
        rnd = (lambda a: bf16_round(np.asarray(a, np.float32))
               .astype(np.float64)) if self.low else (lambda a: a)
        total = np.zeros(self.n, np.float64)
        for r in ranks:
            docs, tf = self._post[int(r)]
            if not len(docs):
                continue
            df = len(docs)
            idf = rnd(np.log(1.0 + (self.n - df + 0.5) / (df + 0.5)))
            norm = rnd(self.k1 * (1.0 - self.b
                                  + self.b * self.dl[docs] / self.avgdl))
            part = rnd(idf * rnd(tf * (self.k1 + 1.0) / rnd(tf + norm)))
            total[docs] = rnd(total[docs] + part)
        return total

    def answer(self, req, k: int) -> dict:
        s = self.scores(req.ranks)
        ords, top = top_hits(s, k)
        return {"scores": s, "ords": ords, "top": top,
                "total": int(np.count_nonzero(s > 0))}


def _int8_rows(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    scale = (np.maximum(np.abs(x).max(axis=1), 1e-30) / 127.0).astype(
        np.float32)
    return np.rint(x / scale[:, None]).astype(np.int8), scale


class KnnReference:
    """Exact cosine kNN, score (1 + cos) / 2, over rows stored as
    bfloat16 unit vectors (the configuration's stated precision): rows
    are normalised in float32 and rounded to bfloat16, the query is
    rounded to bfloat16 and divided by its float32 norm afterwards.
    A filter keeps the rows whose tag lies in the request's range."""

    BLOCK = 32768

    def __init__(self, segments: Sequence[VectorSegment],
                 precision: Optional[str] = None):
        if precision not in (None, "int8"):
            raise ValueError(precision)
        self.segments = segments
        self.low = precision == "int8"
        self.n = sum(s.n for s in segments)

    def all_scores(self, reqs: Sequence) -> np.ndarray:
        """[Q, N] scores of a batch of requests, rows in blocks, one
        thread per segment; filtered-out rows score 0. The operands are
        exact bfloat16 values, so their float32 products are exact and
        only the sum is rounded (about 1e-7 of the score)."""
        q = np.stack([r.vector for r in reqs]).astype(np.float32)
        qn = np.maximum(np.linalg.norm(q.astype(np.float64), axis=1),
                        1e-20).astype(np.float32)
        lo_t = np.asarray([r.tag_lo for r in reqs])[:, None]
        hi_t = np.asarray([r.tag_hi for r in reqs])[:, None]
        if self.low:
            q8, qs = _int8_rows(q)
            qop = q8.astype(np.float32)
        else:
            qop = bf16_round(q)
        out = np.zeros((len(reqs), self.n), np.float32)

        def one_segment(s: VectorSegment) -> None:
            for lo in range(0, s.n, self.BLOCK):
                v = s.vectors[lo:lo + self.BLOCK]
                unit = v / np.maximum(np.linalg.norm(v, axis=1),
                                      np.float32(1e-20))[:, None]
                if self.low:
                    u8, us = _int8_rows(unit)
                    dots = (qop @ u8.astype(np.float32).T) \
                        * (qs[:, None] * us[None, :]).astype(np.float32)
                else:
                    dots = qop @ bf16_round(unit).T
                sc = (np.float32(1.0) + dots / qn[:, None]) / np.float32(2.0)
                t = s.tags[None, lo:lo + self.BLOCK]
                keep = (lo_t < 0) | ((t >= lo_t) & (t < hi_t))
                g = s.doc0 + lo
                out[:, g:g + len(v)] = np.where(keep, sc, np.float32(0.0))

        with ThreadPoolExecutor(len(self.segments)) as pool:
            list(pool.map(one_segment, self.segments))
        return out

    def answers(self, reqs: Sequence, k: int) -> List[dict]:
        scores = self.all_scores(reqs)
        out = []
        for i in range(len(reqs)):
            ords, top = top_hits(scores[i], k)
            out.append({"scores": scores[i], "ords": ords, "top": top,
                        "total": int(min(k, np.count_nonzero(scores[i] > 0)))})
        return out
