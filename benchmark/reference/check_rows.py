"""``check.py``'s comparison for pairs the plain reference cannot hold whole.

    python -m benchmark.reference.check_rows <job.json>

The same job, the same numbers and the same last line as ``check.py``
(reference, float8 control, ``rel_l1`` of each, ``gap_over_control``), with
``raft_stereo_rows.serve_reference`` in the place of
``raft_stereo.serve_reference``: the same equations with the lookup taken a
block of rows at a time and the feature encoder an image at a time (that
module lists every departure and a test holds the two equal).  One program
is compiled and run at a time, and each field leaves the device before the
next is made: at 1988x2964 a float32 forward pass takes most of a 16 GB chip.
"""

from __future__ import annotations

import json
import sys
import time


def main(argv) -> int:
    job = json.load(open(argv[0]))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.loadgen.pairs import make_pair
    from benchmark.reference import raft_stereo_rows as R
    from benchmark.weights import make_weights

    t0 = time.time()
    cfg = job["model"]
    hw = tuple(job["hw"])
    p = {k: jnp.asarray(v) for k, v in make_weights(cfg, job["seed"]).items()}
    pairs = {s["pair"]: make_pair(job["seed"], s["pair"], hw)
             for s in job["samples"]}

    def run_all(dt):
        fn = jax.jit(lambda p, l, r: R.serve_reference(
            p, cfg, l, r, job["iters"], job["divis_by"],
            job["bucket_multiple"], dt))
        out, secs = {}, []
        for k, (left, right) in pairs.items():
            t1 = time.time()
            out[k] = np.asarray(fn(p, left, right))
            secs.append(round(time.time() - t1, 2))
        return out, secs

    out = {"device": jax.devices()[0].platform, "samples": []}
    refs, out["reference_s"] = run_all(None)
    ctls, out["control_s"] = run_all(job["control_dtype"])
    for s in job["samples"]:
        ref, ctl = refs[s["pair"]], ctls[s["pair"]]
        scale = float(np.abs(ref).mean())
        row = {"i": s["i"], "pair": s["pair"], "ref_mean_abs": scale}
        if s.get("reply"):
            served = np.load(s["reply"])
            row["rel_l1"] = float(np.abs(served - ref).mean() / scale)
            row["max_abs_px"] = float(np.abs(served - ref).max())
        row["control_rel_l1"] = float(np.abs(ctl - ref).mean() / scale)
        if "rel_l1" in row:
            row["gap_over_control"] = row["rel_l1"] / row["control_rel_l1"]
        out["samples"].append(row)
    out["seconds"] = time.time() - t0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
