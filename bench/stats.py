"""The arithmetic of the end-to-end metrics."""
from __future__ import annotations

import numpy as np


def rate(units: int, unit_size: int, window_s: float) -> float:
    """Work per second over the whole window: every unit completed in it,
    over all of its time."""
    return units * unit_size / window_s


def tail(values, q: float) -> float:
    """The ``q``-th percentile of every value (linear between ranks)."""
    values = np.asarray(values, np.float64)
    if values.size == 0:
        raise ValueError("no values to take a tail of")
    return float(np.percentile(values, q))
