"""Tail arithmetic over all requests due in the window.  A request that
failed or never finished is infinitely late, so it sits at the top of
every latency distribution and can only push a percentile up."""
from __future__ import annotations

import math
from typing import Iterable, Optional

INF = math.inf


def percentile(values: Iterable[Optional[float]], p: float) -> float:
    """The ``p``-th percentile (0-100), linear between closest ranks.
    ``None`` and ``inf`` count as missing: infinitely late."""
    xs = sorted(INF if v is None else float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no requests")
    pos = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(xs[hi]):
        return INF
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values: Iterable[Optional[float]]) -> float:
    """The mean; one missing (``None`` or ``inf``) request makes it
    infinite."""
    xs = [INF if v is None else float(v) for v in values]
    if not xs:
        raise ValueError("mean of no requests")
    return math.fsum(xs) / len(xs)
