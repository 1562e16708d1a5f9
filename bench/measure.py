"""Rate and percentile arithmetic shared by the harness and the metric
readers."""
from __future__ import annotations

from typing import Iterable, Optional

import numpy as np


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile by linear interpolation between closest
    ranks (numpy's default), or ``None`` for no values."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def rate(count: float, seconds: float) -> Optional[float]:
    """``count`` per second over a window of ``seconds``."""
    if seconds <= 0:
        return None
    return float(count) / float(seconds)


def in_window(t, t0: float, t1: float) -> np.ndarray:
    """Mask of timestamps within ``[t0, t1]``."""
    t = np.asarray(t, np.float64)
    return (t >= t0) & (t <= t1)

