"""Median host time between two dispatches, in ms: from the end of one batch's
``device_wait`` phase to the start of the next batch's ``launch`` (both
recorded once per dispatch under the batch's trace, ``batch:<n>``): host
fetch, reply hand-off, batch forming, staging.  A ring without these phases
gives nothing."""

from benchmark.loadgen.stats import percentile
from benchmark.readers import spans_in_window


def read(ctx, run, params):
    def batch(name):
        return sorted((s for s in spans_in_window(run, name)
                       if str(s["args"].get("trace_id", ""))
                       .startswith("batch:")), key=lambda s: s["ts"])

    waits, launches = batch("device_wait"), batch("launch")
    gaps = []
    for w in waits:
        end = w["ts"] + w["dur"]
        nxt = next((s["ts"] for s in launches if s["ts"] >= end), None)
        if nxt is not None:
            gaps.append(nxt - end)
    p = percentile(gaps, 50)
    return None if p is None else p * 1e-3
