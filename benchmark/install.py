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


def text_segment(seg: TextSegment, field: str, seg_id: int, seq0: int = 0):
    """`seq0` = the global ordinal of the shard's first document: sequence
    numbers are the shard's own, ids and `pid` the index's."""
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
        seq_nos=np.arange(seg.doc0 - seq0, seg.doc0 - seq0 + seg.n,
                          dtype=np.int64))


def vector_segment(seg: VectorSegment, field: str, tag_field: str,
                   n_tags: int, seg_id: int, seq0: int = 0):
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
        seq_nos=np.arange(seg.doc0 - seq0, seg.doc0 - seq0 + n,
                          dtype=np.int64))


def install(node, config: dict, parts: Sequence) -> int:
    """Build each part of the configuration's corpus into a Segment (in
    threads: numpy's sorts release the GIL), serialise it and install it
    into the configuration's index, which exists. The parts are in global
    ordinal order and go to the index's shards by contiguous range: with
    `per` segments a shard, part i is segment i % per of shard i // per.
    Returns the number of documents installed."""
    from elasticsearch_tpu.index.segment_io import segment_to_blob

    idx = config["index"]
    per = int(idx["segments"])
    engines = node.indices.get(idx["name"]).shards
    if len(engines) * per != len(parts):
        raise RuntimeError(f"index {idx['name']} has {len(engines)} shards "
                           f"of {per} segments, the corpus {len(parts)} parts")

    def blob_of(i: int) -> bytes:
        seq0 = parts[i // per * per].doc0
        if config["kind"] == "text":
            seg = text_segment(parts[i], idx["field"], i % per, seq0)
        else:
            seg = vector_segment(parts[i], idx["field"], idx["tag_field"],
                                 int(config["corpus"]["tags"]), i % per, seq0)
        return segment_to_blob(seg)

    def fill(s: int) -> int:
        """Shard s takes its segments in ordinal order, as they are
        built; the shards fill side by side (an engine a shard)."""
        total = 0
        for i in range(s * per, (s + 1) * per):
            engines[s].install_segment(blobs[i].result(),
                                       np.ones(parts[i].n, bool))
            blobs[i] = None         # a blob is as large as its segment
            total += parts[i].n
        engines[s].fill_seqno_gaps(total - 1)
        return total

    with ThreadPoolExecutor(len(parts)) as build_pool, \
            ThreadPoolExecutor(len(engines)) as shard_pool:
        blobs = [build_pool.submit(blob_of, i) for i in range(len(parts))]
        return sum(shard_pool.map(fill, range(len(engines))))
