"""How late the generator sent requests: send time minus due time over all
requests of the window.  params: q (0..100)."""

from benchmark.loadgen.stats import lateness, percentile


def read(ctx, run, params):
    p = percentile(lateness(run["records"]), float(params["q"]))
    return None if p is None else p * 1e3
