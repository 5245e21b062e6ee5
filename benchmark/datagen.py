"""Seeded data for the benchmark's configurations, in bulk numpy.

Nothing here imports the program. A configuration file's `corpus` block
says what to draw; `--seed` says which draw. The same seed gives the same
arrays, whatever the thread count: every segment draws from its own child
of `SeedSequence([seed, stream])`.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import List

import numpy as np

# child streams of a run's seed: one number per kind of draw, so that a
# new kind never shifts an old one
STREAM_TEXT, STREAM_VECTORS, STREAM_TRAFFIC, STREAM_SAMPLE = 1, 2, 3, 4


def rng_for(seed: int, stream: int, part: int = 0) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), int(stream), int(part)]))


def segment_bounds(n_docs: int, n_segments: int) -> np.ndarray:
    """[n_segments + 1] doc offsets of near-equal contiguous segments."""
    return np.linspace(0, n_docs, n_segments + 1).astype(np.int64)


def term_name(rank: int) -> str:
    """Term of Zipf rank `rank`; zero-padded, so sorted names = rank order."""
    return "t%07d" % rank


def zipf_cdf(n: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    c = np.cumsum(p)
    return c / c[-1]


@dataclass
class TextSegment:
    """One segment's tokens: doc `i` owns tokens[bounds[i]:bounds[i + 1]]."""
    doc0: int               # global ordinal of the segment's first doc
    lens: np.ndarray        # [n] i32 tokens per doc
    bounds: np.ndarray      # [n + 1] i64
    tokens: np.ndarray      # [sum(lens)] i32 term ranks (0 = most frequent)

    @property
    def n(self) -> int:
        return len(self.lens)


def _draw_text_segment(spec: dict, seed: int, part: int, doc0: int,
                       n: int) -> TextSegment:
    rng = rng_for(seed, STREAM_TEXT, part)
    # passage lengths: log-normal body with a heavy right tail, clipped
    sigma = float(spec["len_sigma"])
    mu = np.log(float(spec["len_mean"])) - sigma * sigma / 2.0
    lens = np.clip(np.rint(rng.lognormal(mu, sigma, size=n)),
                   spec["len_min"], spec["len_max"]).astype(np.int32)
    total = int(lens.sum())
    cdf = zipf_cdf(int(spec["vocab"]), float(spec["zipf_s"]))
    tokens = np.searchsorted(
        cdf, rng.random(total), side="right").astype(np.int32)
    np.minimum(tokens, len(cdf) - 1, out=tokens)
    bounds = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=bounds[1:])
    return TextSegment(doc0, lens, bounds, tokens)


def make_text(spec: dict, seed: int, n_segments: int) -> List[TextSegment]:
    """The text corpus of a configuration, one TextSegment per segment."""
    b = segment_bounds(int(spec["docs"]), n_segments)
    with ThreadPoolExecutor(n_segments) as pool:
        return list(pool.map(
            lambda p: _draw_text_segment(spec, seed, p, int(b[p]),
                                         int(b[p + 1] - b[p])),
            range(n_segments)))


@dataclass
class VectorSegment:
    doc0: int
    vectors: np.ndarray     # [n, dims] f32
    tags: np.ndarray        # [n] i32 value of the keyword filter field

    @property
    def n(self) -> int:
        return len(self.tags)


def vector_centers(spec: dict, seed: int) -> np.ndarray:
    return rng_for(seed, STREAM_VECTORS, 10_000).standard_normal(
        (int(spec["clusters"]), int(spec["dims"])), dtype=np.float32)


def _draw_vector_segment(spec: dict, seed: int, part: int, doc0: int, n: int,
                         centers: np.ndarray) -> VectorSegment:
    rng = rng_for(seed, STREAM_VECTORS, part)
    which = rng.integers(0, len(centers), size=n)
    tags = rng.integers(0, int(spec["tags"]), size=n).astype(np.int32)
    vecs = rng.standard_normal((n, centers.shape[1]), dtype=np.float32)
    vecs *= np.float32(spec["spread"])
    # add the centre in blocks: a fancy-indexed [n, dims] copy of the
    # centres would double the peak of the largest array of set-up
    for lo in range(0, n, 65536):
        vecs[lo:lo + 65536] += centers[which[lo:lo + 65536]]
    return VectorSegment(doc0, vecs, tags)


def make_vectors(spec: dict, seed: int, n_segments: int) -> List[VectorSegment]:
    """Seeded mixture of Gaussians: `clusters` unit-normal centres, rows at
    centre + `spread` * N(0, 1); one uniform tag per row."""
    b = segment_bounds(int(spec["docs"]), n_segments)
    centers = vector_centers(spec, seed)
    with ThreadPoolExecutor(n_segments) as pool:
        return list(pool.map(
            lambda p: _draw_vector_segment(spec, seed, p, int(b[p]),
                                           int(b[p + 1] - b[p]), centers),
            range(n_segments)))


def n_parts(config: dict) -> int:
    """Segments of a configuration's index in all: `index.segments` is
    per shard, `index.shards` absent = 1."""
    idx = config["index"]
    return int(idx.get("shards", 1)) * int(idx["segments"])


def make_parts(config: dict, seed: int) -> list:
    """The seeded corpus of a configuration, one part a segment, in
    global ordinal order."""
    make = {"text": make_text, "vectors": make_vectors}[config["kind"]]
    return make(config["corpus"], seed, n_parts(config))
