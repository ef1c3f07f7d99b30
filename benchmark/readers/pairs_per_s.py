"""Pairs answered correctly inside the window over the window's seconds:
all the work over all the time (host clock, the clients' own)."""


def read(ctx, run, params):
    n = sum(1 for r in run["records"]
            if r.get("ok") and run["t0"] <= r["done"] <= run["t_end"])
    return n / ctx.seconds
