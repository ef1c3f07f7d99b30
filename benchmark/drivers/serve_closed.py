"""Closed loop against ``cli.serve``: ``clients`` callers that each send the
next pair when the last reply is decoded.  Callers that wait for replies make
a closed loop; it saturates the server and reads its throughput."""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmark.serving import check, run_serve  # noqa: F401

SAMPLE_FIRST_N = 6      # a kept reply is one of a client's first replies


def _plan(ctx, n_workers: int) -> Dict:
    """Which replies are kept for the reference: ``check_samples`` clients
    drawn from the seed, each keeping one of its first replies."""
    rng = np.random.default_rng([int(ctx.seed), 0xC105ED])
    n = min(int(ctx.cell["check_samples"]), n_workers)
    return {int(wid): [int(rng.integers(1, SAMPLE_FIRST_N))]
            for wid in rng.permutation(n_workers)[:n]}


def run(ctx) -> Dict:
    return run_serve(ctx, "closed", _plan)
