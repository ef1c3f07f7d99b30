"""``serve_closed`` for a cell whose pairs the plain reference cannot hold
whole: the same closed loop, and ``check`` runs the comparison over the
row-blocked reference (``reference/check_rows.py``)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict

from benchmark import child
from benchmark.child import BenchFailure, say
from benchmark.drivers.serve_closed import run  # noqa: F401
from benchmark.serving import pick_kept

CHECK_TIMEOUT_S = 900       # the harness's, as serving.check


def check(ctx, result: Dict) -> Dict:
    """``serving.check`` with ``reference.check_rows`` as the child."""
    cell = ctx.cell
    samples = pick_kept(result["records"], ctx.seed,
                        int(cell["check_samples"]))
    checks: Dict[str, Dict] = {}
    if not samples:
        checks["replies_compared"] = {"value": 0, "at_least": 1}
        return checks
    job = {"model": ctx.config["model"], "seed": ctx.seed,
           "hw": cell["image_hw"], "iters": ctx.config["iters"],
           "divis_by": cell.get("divis_by", 32),
           "bucket_multiple": cell.get("bucket_multiple", 64),
           "control_dtype": ctx.config["check"]["control_dtype"],
           "samples": [{"i": r["i"], "pair": r["pair"], "reply": r["kept"]}
                       for r in samples]}
    job_path = os.path.join(ctx.run_dir, "check_job.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    t = time.time()
    try:
        r = subprocess.run(
            [sys.executable, "-m", "benchmark.reference.check_rows",
             job_path], capture_output=True, text=True, cwd=child.ROOT,
            timeout=CHECK_TIMEOUT_S, env=child.child_env())
    except subprocess.TimeoutExpired:
        raise BenchFailure("the row-blocked reference did not finish in "
                           f"{CHECK_TIMEOUT_S}s")
    if r.returncode != 0:
        raise BenchFailure("the reference check failed to run:\n"
                           + r.stderr[-3000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    say(f"[check] row-blocked reference over {len(samples)} replies on "
        f"{out['device']} in {time.time() - t:.1f}s (its own "
        f"{out['seconds']:.1f}s; a pass {out['reference_s']} s, a control "
        f"pass {out['control_s']} s): "
        + ", ".join(f"#{s['i']}: served {s['rel_l1']:.5f} / control "
                    f"{s['control_rel_l1']:.5f}" for s in out["samples"]))
    checks["replies_compared"] = {"value": len(out["samples"]),
                                  "at_least": 1}
    worst = max(s["gap_over_control"] for s in out["samples"])
    checks["gap_over_control_max"] = {
        "value": worst if worst == worst else None,       # NaN fails
        "limit": ctx.config["check"]["gap_over_control_max"]}
    return checks
