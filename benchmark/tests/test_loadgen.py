"""Percentile, due-time and schedule arithmetic on hand-made cases."""

import numpy as np
import pytest

from benchmark.loadgen.schedule import arrivals
from benchmark.loadgen.stats import (lateness, latencies_from_due,
                                     percentile)


def test_percentile_linear_between_ranks():
    v = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert percentile(v, 0) == 10.0
    assert percentile(v, 50) == 30.0
    assert percentile(v, 100) == 50.0
    assert percentile(v, 95) == pytest.approx(48.0)
    assert percentile([], 50) is None


def test_latency_counts_from_due_through_a_stall():
    """Four requests due every 100 ms; the generator stalls 250 ms before
    the second.  Timed from send the stall would vanish; timed from due the
    later requests carry it."""
    service = 0.05
    due = [0.0, 0.1, 0.2, 0.3]
    sent = [0.0, 0.35, 0.35, 0.35]
    recs = [{"due": d, "sent": s, "done": s + service, "ok": True}
            for d, s in zip(due, sent)]
    lat = latencies_from_due(recs, give_up_at=9.9)
    assert lat == pytest.approx([0.05, 0.30, 0.20, 0.10])
    assert lateness(recs) == pytest.approx([0.0, 0.25, 0.15, 0.05])


def test_failed_request_counts_as_the_worst():
    recs = [{"due": 1.0, "sent": 1.0, "done": 1.2, "ok": True},
            {"due": 2.0, "sent": 2.0, "done": 2.1, "ok": False, "status": 503}]
    assert latencies_from_due(recs, give_up_at=62.0) == pytest.approx(
        [0.2, 60.0])


def test_every_seed_gets_the_same_gaps_in_another_order():
    p = {"shape": "poisson", "rate": 8.0}
    a = arrivals(p, 1, 30.0)
    b = arrivals(p, 2**31 + 5, 30.0)
    assert len(a) == len(b) == 240
    assert all(0 <= t < 30.0 for t in a + b)
    assert a == sorted(a) and a != b
    ga, gb = np.diff(a + [30.0]), np.diff(b + [30.0])
    assert np.allclose(np.sort(ga), np.sort(gb))
    # the same wheel of gaps, turned: some rotation of one is the other
    k = int(np.argmin([np.abs(np.roll(ga, r) - gb).max()
                       for r in range(len(ga))]))
    assert np.allclose(np.roll(ga, k), gb)
    other = arrivals(dict(p, pattern_seed=1), 1, 30.0)
    assert not np.allclose(np.sort(np.diff(other))[:5], 0) and other != a
    # exponential gaps: the spread is about the mean
    assert 0.8 < np.std(ga) / np.mean(ga) < 1.2
    assert arrivals(p, 1, 30.0) == a
    with pytest.raises(ValueError):
        arrivals({"shape": "burst", "rate": 8.0}, 1, 30.0)
