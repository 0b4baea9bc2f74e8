"""Small statistics shared by the metric readers."""
from __future__ import annotations

import numpy as np


def p95(values) -> float:
    """95th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), 95))


def delta(run, key: str, span: str = "window"):
    """Change of an in-process counter over the window (or the trace)."""
    a, b = run.counters[span]
    if key not in a or key not in b:
        return None
    return b[key] - a[key]


def done_in(records, lo: float, hi: float) -> list:
    """Successful replies received in [lo, hi)."""
    return [r for r in records if r.ok and lo <= r.done < hi]
