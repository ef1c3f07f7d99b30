"""Median, over answered requests, of the client's round trip minus the
server's own ``request`` span of the same X-Request-Id: wire encode and
decode in the client, HTTP, and the reply's write, none of which a
server-side timer sees."""

from benchmark.loadgen.stats import percentile


def read(ctx, run, params):
    spans = {s["args"].get("trace_id"): s["dur"] * 1e-6
             for s in run.get("spans", []) if s["name"] == "request"}
    out = [(r["done"] - r["sent"]) - spans[r["rid"]]
           for r in run["records"] if r.get("ok") and r.get("rid") in spans]
    p = percentile(out, 50)
    return None if p is None else p * 1e3
