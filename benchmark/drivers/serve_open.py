"""Open loop against ``cli.serve``: requests fall due on a seeded schedule at
the cell's fixed rate whether or not earlier ones have been answered, as
independent callers send them, and each is timed from when it was due."""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmark.loadgen.schedule import arrivals
from benchmark.serving import check, run_serve  # noqa: F401


def _plan(ctx, n_workers: int) -> Dict:
    due = arrivals(ctx.cell["arrivals"], ctx.seed, ctx.seconds)
    rng = np.random.default_rng([int(ctx.seed), 0x0BE7])
    keep = set(int(i) for i in rng.permutation(len(due))[
        :int(ctx.cell["check_samples"])])
    pool = int(ctx.cell["pair_pool"])
    return {"tasks": [(i, t, int(rng.integers(0, pool)), i in keep)
                      for i, t in enumerate(due)]}


def run(ctx) -> Dict:
    return run_serve(ctx, "open", _plan)
