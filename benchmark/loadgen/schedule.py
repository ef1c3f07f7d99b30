"""Seeded arrival schedules.

The Poisson shape of the program's ``loadgen/trace.py``, copied so that the
yardstick cannot move, with one change: every seed gets
the *same cyclic sequence* of gaps, started at another point.  A free Poisson
draw changes the number of requests in the window by several percent from
seed to seed, and even the same gaps in a freely shuffled order moved the
95th percentile of ``default_serve_rate80`` by 20 % from seed to seed while
two runs of one seed agreed within 1 % (PR 25, PERF.md): where the short gaps
cluster decides the tail.  So the order is the cell's (``pattern_seed`` in
its file) and the run's seed only turns the wheel.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def _exponential_gaps(n: int) -> np.ndarray:
    """n gaps with mean 1 at the exponential distribution's quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u)


def arrivals(params: Dict, seed: int, seconds: float) -> List[float]:
    """Offsets in [0, seconds) at which requests are due: ``rate * seconds``
    of them, whatever the seed, exponentially distributed gaps
    (``shape: poisson``) whose order is drawn from ``pattern_seed`` and
    rotated by a step drawn from ``seed``."""
    shape = params.get("shape", "poisson")
    if shape != "poisson":
        raise ValueError(f"unknown arrival shape {shape!r}")
    n = int(round(float(params["rate"]) * seconds))
    if n <= 0:
        return []
    pattern = np.random.default_rng(
        [int(params.get("pattern_seed", 0)), 0xA221])
    turn = int(np.random.default_rng([int(seed), 0xA221]).integers(1 << 30))
    gaps = np.roll(pattern.permutation(_exponential_gaps(n)), turn % n)
    gaps *= seconds / gaps.sum()
    return [float(t) for t in np.cumsum(gaps) - gaps]
