"""Helpers shared by the metric readers (not a metric)."""
from __future__ import annotations

import math
from typing import List, Optional, Sequence


def p95(values: Sequence[float]) -> Optional[float]:
    """Nearest-rank 95th percentile."""
    if not values:
        return None
    v = sorted(values)
    return float(v[max(0, math.ceil(0.95 * len(v)) - 1)])


def traced_steps(ctx) -> List[dict]:
    return [s for s in ctx["rec"].get("steps", []) if s.get("traced")]
