"""Operations and bytes a kernel pass needs, from the cell's shapes.

One function per kernel, named in the metric's file (`cost`). Each takes
the engine's HBM regions as the program's ledger reports them
(`tpu_hbm.engines.<name>.regions`, bytes) and the dispatch width, and
returns (operations, bytes, peak) for ONE pass over the whole index:
`peak` names the compute peak the operations run against.

The least time the chip could take for the pass is
max(operations / peak ops per second, bytes / HBM bytes per second);
the roofline share is that over the traced kernel time.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peak table's row for a device; an unknown kind is an error,
    never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json (has {sorted(table)})")
    return table[device_kind]


def sweep_rowmax(regions: Dict[str, int], qc: int) -> Tuple[float, float, str]:
    """The disjunctive sweep reads every int8 cell of the hi and lo
    column planes once and multiplies it with BOTH int8 halves (hi, lo)
    of each of `qc` query weights: four s8 x s8 -> s32 products a chunk
    (`kernels._sweep_kernel`: hi.hi, hi.lo, lo.hi, lo.lo), one
    multiply-add = 2 operations; it also reads the live rows. By the
    v5e's peaks bytes bound it up to width 64 (the two meet at 120),
    the int8 peak at 256."""
    cells = float(regions["cols_hi"] + regions["cols_lo"])
    return 2.0 * 2.0 * qc * cells, cells + float(regions.get("live", 0)), \
        "int8_ops_per_s"


def knn_int8_window_topc(regions: Dict[str, int],
                         qc: int) -> Tuple[float, float, str]:
    """The int8 first pass reads every stored int8 row once (plus the
    per-row meta) and takes `qc` dot products with it."""
    cells = float(regions["knn_shards"])
    return 2.0 * qc * cells, cells + float(regions.get("knn_meta", 0)), \
        "int8_ops_per_s"


COSTS = {"sweep_rowmax": sweep_rowmax,
         "knn_int8_window_topc": knn_int8_window_topc}


def least_seconds(cost: str, regions: Dict[str, int], qc: int,
                  device_kind: str) -> Tuple[float, str]:
    """(seconds, which bound) of one pass at the device's peaks."""
    ops, nbytes, peak = COSTS[cost](regions, qc)
    p = peaks_for(device_kind)
    by_ops, by_bytes = ops / p[peak], nbytes / p["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops > by_bytes
                                   else "memory")
