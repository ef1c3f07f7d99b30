"""Median duration of one of the program's spans, in ms.  params: span
(its name), per ("request": every span; "dispatch": one per batch — the
batcher records a batch's dispatch and device_compute once per request in
it, with the same start)."""

from benchmark.loadgen.stats import percentile
from benchmark.readers import spans_in_window


def read(ctx, run, params):
    spans = spans_in_window(run, params["span"],
                            params.get("per") == "dispatch")
    p = percentile([s["dur"] for s in spans], 50)
    return None if p is None else p * 1e-3
