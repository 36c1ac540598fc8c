"""Statistics over every request of a window: tails and rates."""
from __future__ import annotations

import numpy as np

__all__ = ["percentile", "rate"]


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation) of all ``values``."""
    v = np.asarray(values, np.float64)
    if v.size == 0:
        raise ValueError("no values")
    return float(np.percentile(v, q))


def rate(count: int, seconds: float) -> float:
    """``count`` over the whole window of ``seconds``."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return count / seconds
