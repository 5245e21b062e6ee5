"""Corpus kind `text_vectors`: one index whose every document has BOTH a
text field and a dense vector (the hybrid deployment): corpus kind
`text`'s tokens and corpus kind `vectors`' rows and tags, each drawn by
its own kind (the same streams of the seed: the same text as the cells
over `text`, the same vectors as the cell over `vectors`), over the same
segment bounds. A part carries what both references read.

Text and vector of a document are independent draws; what ties the two
sides of a request together is the request (request kind `hybrid`).

`segment` builds one `Segment` a part with the text field's postings,
the vector column and the vectors kind's keyword tag (the vectors kind
draws it anyway, `KnnReference` reads it, and a filtered mix then needs
no second configuration).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List

import numpy as np

from benchmark.datagen import n_parts
from benchmark.manifest import ManifestError, load_kind

_BDIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_text = load_kind(_BDIR, "corpus", "text")
_vectors = load_kind(_BDIR, "corpus", "vectors")

# what the CPU tests cut `corpus` to (tests/bench_harness/bench_tiny.py):
# the two kinds' own, the text's document count for both
TINY = {**_vectors.TINY, **_text.TINY}


@dataclass
class TextVectorSegment:
    """One segment of both: doc `i` owns tokens[bounds[i]:bounds[i + 1]],
    row `i` of `vectors` and `tags[i]`."""
    doc0: int               # global ordinal of the segment's first doc
    lens: np.ndarray        # [n] i32 tokens per doc
    bounds: np.ndarray      # [n + 1] i64
    tokens: np.ndarray      # [sum(lens)] i32 term ranks (0 = most frequent)
    vectors: np.ndarray     # [n, dims] f32
    tags: np.ndarray        # [n] i32 value of the keyword filter field

    @property
    def n(self) -> int:
        return len(self.lens)


def _vector_view(config: dict) -> dict:
    """The configuration as kind `vectors` reads it: `index.field` is the
    vector field there."""
    return dict(config, index=dict(config["index"],
                                   field=config["index"]["vector_field"]))


def _require_route(config: dict) -> None:
    """End the run at once, with a `ManifestError` (exit 2), on a program
    that has no hybrid route. The benchmark's files are also laid over
    the PARENT of the PR that brought them, to see whether it can run the
    cell: that program answers every such body from its dense executor,
    a minute a call of 256 at this size, so five warm-up calls alone
    outlast the time a run may take, and a run that is killed is not a
    clean refusal. The one look at the program outside `segment`."""
    from elasticsearch_tpu.search import serving

    if not hasattr(serving, "extract_hybrid_plan"):
        raise ManifestError(
            f"{config['name']}: this program has no route for a body with "
            "`query` and `knn`: it cannot run this configuration")


def make_parts(config: dict, seed: int) -> List[TextVectorSegment]:
    """The seeded corpus of a configuration, one part a segment, in
    global ordinal order."""
    _require_route(config)
    spec, n = config["corpus"], n_parts(config)
    text = _text.make_text(spec, seed, n)
    vecs = _vectors.make_vectors(spec, seed, n)
    return [TextVectorSegment(t.doc0, t.lens, t.bounds, t.tokens,
                              v.vectors, v.tags)
            for t, v in zip(text, vecs)]


def segment(config: dict, seg: TextVectorSegment, seg_id: int,
            seq0: int = 0):
    """The text kind's `Segment` of the part with the vectors kind's
    fields beside its own. The second `Segment` is built for its columns
    and dropped (its ids and sources are the first's: 0.2 s a part of
    368,409 documents; the kind offers its columns no other way, and an
    accepted kind is not edited here)."""
    s = _text.segment(config, seg, seg_id, seq0)
    v = _vectors.segment(_vector_view(config), seg, seg_id, seq0)
    s.postings.update(v.postings)
    s.keyword.update(v.keyword)
    s.vectors.update(v.vectors)
    return s
