"""Set-up of the system under test: the seeded corpus becomes the
program's own `Segment`s, installed through `Engine.install_segment` (the
peer-recovery file phase) — never through `_bulk`, which spends five
minutes of host time on a million documents (PERF.md, PR 22).

This is the one module of the benchmark, besides `run.py`'s node start,
that imports the program: the segment format is the program's, and
building it is part of `setup_s`.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, Sequence

import numpy as np

from benchmark.datagen import TextSegment, VectorSegment, term_name


def tag_name(tag: int) -> str:
    return "g%04d" % tag


def _doc_ids(doc0: int, n: int) -> List[str]:
    return [str(i) for i in range(doc0, doc0 + n)]


def _sources(doc0: int, n: int) -> List[dict]:
    # the stored source is cut to the passage id: the body is indexed, not
    # stored (listed in the configuration's `reduced`)
    return [{"pid": i} for i in range(doc0, doc0 + n)]


def text_segment(seg: TextSegment, field: str, seg_id: int):
    from elasticsearch_tpu.index.segment import Segment, build_field_postings

    present = np.flatnonzero(np.bincount(seg.tokens))
    remap = np.zeros(int(present[-1]) + 1, np.int32)
    remap[present] = np.arange(len(present), dtype=np.int32)
    tok_docs = np.repeat(np.arange(seg.n, dtype=np.int32), seg.lens)
    fp = build_field_postings(
        field, seg.lens, tok_docs, remap[seg.tokens],
        [term_name(int(r)) for r in present])
    return Segment(
        seg_id=seg_id, doc_ids=_doc_ids(seg.doc0, seg.n),
        sources=_sources(seg.doc0, seg.n), postings={field: fp},
        numeric={}, keyword={}, vectors={},
        seq_nos=np.arange(seg.doc0, seg.doc0 + seg.n, dtype=np.int64))


def vector_segment(seg: VectorSegment, field: str, tag_field: str,
                   n_tags: int, seg_id: int):
    from elasticsearch_tpu.index.segment import (
        KeywordColumn, Segment, VectorColumn, build_field_postings)

    n = seg.n
    names = [tag_name(t) for t in range(n_tags)]
    ords = seg.tags.astype(np.int32)
    # a keyword field is inverted too (term filters), with no norms
    fp = build_field_postings(
        tag_field, np.zeros(n, np.int32), np.arange(n, dtype=np.int32),
        ords, names)
    kw = KeywordColumn(
        terms=names, term_to_ord={t: i for i, t in enumerate(names)},
        ords=ords, max_ords=ords.copy(), exists=np.ones(n, bool),
        ord_start=np.arange(n + 1, dtype=np.int64), all_ords=ords.copy())
    vc = VectorColumn(
        vectors=seg.vectors,
        norms=np.linalg.norm(seg.vectors, axis=1).astype(np.float32),
        exists=np.ones(n, bool), dims=seg.vectors.shape[1],
        similarity="cosine")
    return Segment(
        seg_id=seg_id, doc_ids=_doc_ids(seg.doc0, n),
        sources=_sources(seg.doc0, n), postings={tag_field: fp},
        numeric={}, keyword={tag_field: kw}, vectors={field: vc},
        seq_nos=np.arange(seg.doc0, seg.doc0 + n, dtype=np.int64))


def install(node, index: str, build, parts: Sequence) -> int:
    """Build each part into a Segment (in threads: numpy's sorts release
    the GIL), serialise it and install it into shard 0 of `index`.
    Returns the number of documents installed."""
    from elasticsearch_tpu.index.segment_io import segment_to_blob

    def blob_of(item):
        i, part = item
        return segment_to_blob(build(part, i)), part.n

    engine = node.indices.get(index).shards[0]
    total = 0
    with ThreadPoolExecutor(len(parts)) as pool:
        for blob, n in pool.map(blob_of, enumerate(parts)):
            engine.install_segment(blob, np.ones(n, bool))
            total += n
    engine.fill_seqno_gaps(total - 1)
    return total
