"""Corpus kind `text_positions`: corpus kind `text`'s tokens, drawn by
`text` itself (the same stream: the same documents, lengths and document
frequencies as the cells over `text`), with every segment built WITH
positions: a token's position is its offset in its document, so two
tokens side by side are a slop-0 phrase occurrence. `text` builds its
segments without them, and no phrase can be served from those.

Drawing imports nothing of the program; `segment` is the one function
that does (the segment format is the program's).
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.datagen import term_name
from benchmark.install import doc_ids, sources
from benchmark.manifest import ManifestError, load_kind

_text = load_kind(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "corpus", "text")

TINY = _text.TINY
TextSegment = _text.TextSegment
make_parts = _text.make_parts


def segment(config: dict, seg, seg_id: int, seq0: int = 0):
    """`text.segment` with `token_pos`. A program without the bool
    route's node-wide counters cannot state this deployment's guarantee
    (the configuration's `must_rise` / `must_stay` read them): that ends
    the run here, before the corpus is built, and not in the comparison
    after the window."""
    from elasticsearch_tpu.index.segment import Segment, build_field_postings
    from elasticsearch_tpu.search.serving import turbo_node_stats

    lacks = [c for c in config.get("must_rise", []) + config.get(
        "must_stay", []) if c.split(".", 1)[1] not in turbo_node_stats()]
    if lacks:
        raise ManifestError(
            f"{config['name']}: GET /_nodes/stats of this program has no "
            f"{lacks}: it cannot run this configuration")
    field = config["index"]["field"]
    present = np.flatnonzero(np.bincount(seg.tokens))
    remap = np.zeros(int(present[-1]) + 1, np.int32)
    remap[present] = np.arange(len(present), dtype=np.int32)
    tok_docs = np.repeat(np.arange(seg.n, dtype=np.int32), seg.lens)
    tok_pos = (np.arange(len(seg.tokens), dtype=np.int64)
               - np.repeat(seg.bounds[:-1], seg.lens)).astype(np.int32)
    fp = build_field_postings(
        field, seg.lens, tok_docs, remap[seg.tokens],
        [term_name(int(r)) for r in present], token_pos=tok_pos)
    return Segment(
        seg_id=seg_id, doc_ids=doc_ids(seg.doc0, seg.n),
        sources=sources(seg.doc0, seg.n), postings={field: fp},
        numeric={}, keyword={}, vectors={},
        seq_nos=np.arange(seg.doc0 - seq0, seg.doc0 - seq0 + seg.n,
                          dtype=np.int64))
