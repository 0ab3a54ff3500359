"""Percentile arithmetic of the benchmark (numpy only)."""
import numpy as np


def percentile(values, q):
    """The q-th percentile (0..100) by the nearest-rank rule on the sorted
    sample: the smallest value with at least q% of the sample at or below
    it. No interpolation, so a tail is always a reading that happened."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        raise ValueError("percentile of an empty sample")
    rank = int(np.ceil(q / 100.0 * v.size))
    return float(v[min(max(rank, 1), v.size) - 1])
