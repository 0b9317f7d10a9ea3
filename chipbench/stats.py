"""Exact order statistics of raw samples (no buckets, no interpolation)."""
import math

import numpy as np


def percentile(values, q):
    """Nearest-rank percentile: the smallest sample with at least q% of the
    samples at or below it."""
    x = np.sort(np.asarray(values, np.float64).ravel())
    if x.size == 0:
        raise ValueError("percentile of no samples")
    return float(x[max(math.ceil(q / 100.0 * x.size), 1) - 1])
