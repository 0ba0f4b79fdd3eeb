"""Order statistics the benchmark reports: numpy's, with ``None`` for an
empty sample (a cell in which nothing finished has no latency)."""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100), linear interpolation between the
    two nearest order statistics."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)
