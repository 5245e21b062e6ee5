"""Kernel cost `sweep_rowmax_bitset`: (operations, bytes, peak) of ONE pass of
the mask-gated sweep over the whole index, from the engine's HBM regions
(`tpu_hbm.engines.<name>.regions`, bytes; one device's share of them) and
the dispatch width. `peak` names the compute peak of benchmark/peaks.json
the operations run against."""

from __future__ import annotations

from typing import Dict, Tuple


def cost(regions: Dict[str, int], qc: int) -> Tuple[float, float, str]:
    """The bool route's sweep (`kernels._sweep_bitset_kernel`) is the
    disjunctive sweep gated by the conjunction's mask. Every grid step
    fetches its chunk's int8 cells of the hi and lo column planes and its
    live rows whether or not the mask skips it (the blocks are fetched
    ahead of the body), and once a superwindow the `qc` queries' mask
    words: one u32 a (query, 32 docs) = qc x live bytes / 32 (the live
    rows are one f32 a doc). A chunk with a surviving bit runs the four
    s8 x s8 -> s32 products a cell of `sweep_rowmax`; a chunk without
    one runs none, so the operations counted here, every chunk's, are
    the most a pass can run. They do not set the bound at the widths a
    lane dispatches: by the v5e's peaks bytes bound the pass up to width
    64 (the two meet near 120)."""
    cells = float(regions["cols_hi"] + regions["cols_lo"])
    live = float(regions.get("live", 0))
    return (2.0 * 2.0 * qc * cells, cells + live + qc * live / 32.0,
            "int8_ops_per_s")
