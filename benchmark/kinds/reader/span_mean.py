"""Reader kind `span_mean` (a metric file names it under `kind`): the mean
of a `tracing.phase` histogram over the window, from a run's
`readers.Window`.

`histogram_mean` with one difference: a histogram the program's stats do
not hold reads 0.0. A tree from before the span existed runs none of the
step it times (as `counter_share` reads a counter the stats lack);
`histogram_mean` ends such a run with a KeyError.
"""

from __future__ import annotations

from benchmark.readers import hist_delta


def read(spec: dict, w) -> float:
    try:
        count, total = hist_delta(w, spec["path"])
    except KeyError:
        return 0.0
    return total / count if count > 0 else 0.0
