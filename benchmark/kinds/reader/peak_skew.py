"""Reader kind `peak_skew` (a metric file names it under `kind`): one
number from a run's `readers.Window`."""

from __future__ import annotations

from benchmark.readers import NothingToRead


def read(spec: dict, w) -> float:
    """The fullest device's `peak_bytes_in_use` over the mean of the
    devices' (the result line's `memory_peak_bytes_per_device`): 1.0 =
    even, the device count = one chip held everything. It is what
    `memory_peak` (the fullest chip alone) cannot say. A backend that
    reports no peak (the CPU's) has nothing to read."""
    peaks = w.notes.get("memory_peaks", ())
    if sum(peaks) <= 0:
        raise NothingToRead("no device reported a peak_bytes_in_use")
    return max(peaks) * len(peaks) / sum(peaks)
