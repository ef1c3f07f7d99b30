"""A percentile of every request of the window, each timed from when it was
due to the decoded disparity in the client; failed, shed or lost requests
count as having waited until the harness gave up.  params: q (0..100)."""

from benchmark.loadgen.stats import latencies_from_due, percentile


def read(ctx, run, params):
    give_up = max([run["t_end"]] + [r["done"] for r in run["records"]])
    lat = latencies_from_due(run["records"], give_up)
    p = percentile(lat, float(params["q"]))
    return None if p is None else p * 1e3
