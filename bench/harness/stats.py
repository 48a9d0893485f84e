"""Statistics shared by the metric readers."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (linear interpolation), over all values."""
    return float(np.percentile(np.asarray(values, np.float64), q))

