"""Kernel cost `agg_segment_counts`: (operations, bytes, peak) of ONE
dispatch of the aggregation engine's filter + bucket route at width `qc`
(every segment of the index reduced once), from the engine's HBM regions
(`tpu_hbm.engines.<name>.regions`, bytes; one device's share of them).
`peak` names the compute peak of benchmark/peaks.json the operations run
against."""

from __future__ import annotations

from collections import Counter
from typing import Dict, Tuple


def cost(regions: Dict[str, int], qc: int) -> Tuple[float, float, str]:
    """What ANY implementation of the reduction must move and do, so
    that a later kernel (one that skips the chunks outside a range, one
    that multiplies in bfloat16, one that serves hour buckets from the
    minute layout) reads a higher share and not another yardstick.

    A dispatch counts, for each of `qc` requests and each segment, the
    documents that pass the request's filter, by the bucket rank their
    value of the histogram's field has. For every segment it has to read
    ONE layout's (doc, rank) pairs (region `aggcol<n>_uniq`: 8 bytes a
    pair and the chunks' tile ranges; a segment's layouts are as long as
    each other) and at least ONE filter column (`aggflt<n>_<field>`: 4
    bytes a document), once, whatever the width: the requests of a batch
    share both. The program as it stands runs a reduction a (segment,
    layout), so a batch that mixes hour and minute layouts reads two
    layouts a segment, and a batch that also filters on a second field
    two columns: the count errs low, never high. The segments are
    counted from the columns' names (as many as hold the commonest
    field). The operations are a comparison and an addition a (request,
    pair), which the kernel as it stands spends in f32 one-hot products;
    counted against the bfloat16 peak they are a thousandth of the
    bytes' time at any width a lane dispatches, so HBM bytes bound it."""
    layouts = [v for k, v in regions.items() if k.startswith("aggcol")]
    columns = {k: v for k, v in regions.items() if k.startswith("aggflt")}
    if not layouts:
        return 0.0, 0.0, "bf16_flops_per_s"
    fields = Counter(k.split("_", 1)[1] for k in columns)
    segments = max(fields.values()) if fields else len(layouts)
    layout = sum(layouts) / len(layouts)
    column = sum(columns.values()) / len(columns) if columns else 0.0
    return (2.0 * qc * segments * layout / 8.0,
            segments * (layout + column), "bf16_flops_per_s")
