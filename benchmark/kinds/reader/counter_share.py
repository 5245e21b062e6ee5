"""Reader kind `counter_share` (a metric file names it under `kind`): the
rise of the `numerator` counters over the rise of the `denominator`
counters, times `scale`, from a run's `readers.Window`.

`counter_ratio` with one difference: a counter the program's stats do not
hold counts as 0. A tree from before the counter existed runs none of
the path it counts, so its share of that path IS 0 (a ratio over 0 reads
0.0, as every total reader's does); `counter_ratio` ends such a run with a
KeyError, and a traced run of the parent commit has to print a line.
"""

from __future__ import annotations

from benchmark.compare import dotted


def _rise(w, paths) -> float:
    total = 0.0
    for p in paths:
        try:
            total += dotted(w.stats_after, p) - dotted(w.stats_before, p)
        except KeyError:
            pass
    return total


def read(spec: dict, w) -> float:
    den = _rise(w, spec["denominator"])
    return (float(spec.get("scale", 1.0)) * _rise(w, spec["numerator"]) / den
            if den > 0 else 0.0)
