"""Percentile and due-time arithmetic of the load generator."""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100), linear between the two nearest ranks;
    None of nothing."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def latencies_from_due(records: List[Dict], give_up_at: float) -> List[float]:
    """Seconds from when each request was *due* to its decoded reply.  A
    request that failed, was shed or never came back counts as the worst:
    it waited until the harness gave up."""
    out = []
    for r in records:
        done = r["done"] if r.get("ok") else give_up_at
        out.append(done - r["due"])
    return out


def lateness(records: List[Dict]) -> List[float]:
    """Seconds by which the generator sent each request after it was due."""
    return [r["sent"] - r["due"] for r in records if r.get("sent") is not None]
