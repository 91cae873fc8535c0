"""End-to-end arithmetic, as pure functions of what a window recorded.

A rate is taken over all the work and all the time of the window, the last
unit of work that ends after the nominal close included; a tail is the
tail of every request the window completed.
"""

from __future__ import annotations

import numpy as np


def per_second(work: float, window_s: float) -> float:
    """All the work of the window over all of its seconds."""
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return work / window_s


def p95(latencies) -> float:
    """The 95th percentile (linear interpolation) over every request."""
    if len(latencies) == 0:
        raise ValueError("no request completed")
    return float(np.percentile(np.asarray(latencies, np.float64), 95))
