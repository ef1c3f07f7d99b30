"""What a run holds itself to besides the reference: a program that reached
the cache for the first time inside the window fails the run even where the
program's own counter saw nothing (its eager per-occupancy staging programs
are invisible to ``serve_compile_cache_misses_total``), and a cell whose
clients cannot fill a batch is refused before anything starts."""

from types import SimpleNamespace

import pytest

from benchmark import serving
from benchmark.child import BenchFailure
from benchmark.run import _passes


def _result(entries):
    recs = [{"due": 1.0, "sent": 1.0, "done": 1.5, "ok": True}] * 3
    return {"mode": "closed", "records": recs, "cache_entries": entries,
            "compiles_in_window": 0.0, "setup_s": 12.0, "ready_s": 10.0}


@pytest.mark.parametrize("entries, ok", [((990, 999, 999), True),
                                         ((990, 995, 999), False)])
def test_a_new_cache_entry_inside_the_window_is_not_correct(entries, ok):
    r = _result(entries)
    serving.report(SimpleNamespace(), r)
    c = r["checks"]["cache_entries_new_in_window"]
    assert c == {"value": entries[2] - entries[1], "limit": 0}
    assert _passes(c) is ok
    assert _passes(r["checks"]["compiles_in_window"])   # the counter is blind


def test_clients_that_cannot_fill_a_batch_are_refused():
    ctx = SimpleNamespace(cell={"image_hw": [64, 96], "clients": 12,
                                "max_batch_size": 32}, run_dir="/nonexistent")
    with pytest.raises(BenchFailure, match="more clients than rows"):
        serving.run_serve(ctx, "closed", lambda c, n: {})
