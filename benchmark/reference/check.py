"""The comparison that decides ``correct`` for a serve cell.

Run as a process of its own once the window has closed, the peak has been
read and the server has exited (so the chip is free, and the reference's
memory is not in the cell's peak):

    python -m benchmark.reference.check <job.json>

The job names the configuration, the seed, the pad policy and the sampled
replies of the timed window (each with the number of the pair it answers).
The weights and the pairs are made again from the seed; nothing the program
made is read but its replies.  For each sample the plain float32 reference
is run over the pair, and so is the control: the same reference computed one
precision step below the configuration's (float8 operands for bfloat16).
With

    rel_l1(x) = mean |x - reference| / mean |reference|

over the full-resolution disparity, the number compared is

    gap_over_control = rel_l1(served) / rel_l1(control)

How strongly 32 iterations amplify a rounding difference depends on the
seed's draw of weights (rel_l1 of sound runs read 0.005 to 0.024 from seed to
seed on the chip, PERF.md §2), and the control's gap moves with it; their
ratio does not.  The control put in the program's place reads 1 by
construction; a sound bfloat16 run reads a small fraction of it.  It prints
one JSON object as its last line.
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
    from benchmark.reference import raft_stereo as R
    from benchmark.weights import make_weights

    t0 = time.time()
    cfg = job["model"]
    hw = tuple(job["hw"])
    p = {k: jnp.asarray(v) for k, v in make_weights(cfg, job["seed"]).items()}

    def run(dt):
        return jax.jit(lambda p, l, r: R.serve_reference(
            p, cfg, l, r, job["iters"], job["divis_by"],
            job["bucket_multiple"], dt))

    ref_fn = run(None)
    ctl_fn = run(job["control_dtype"])
    out = {"device": jax.devices()[0].platform, "samples": []}
    for s in job["samples"]:
        left, right = make_pair(job["seed"], s["pair"], hw)
        t1 = time.time()
        ref = np.asarray(ref_fn(p, left, right))
        out.setdefault("reference_s", []).append(round(time.time() - t1, 2))
        scale = float(np.abs(ref).mean())
        row = {"i": s["i"], "pair": s["pair"], "ref_mean_abs": scale}
        if s.get("reply"):
            served = np.load(s["reply"])
            row["rel_l1"] = float(np.abs(served - ref).mean() / scale)
            row["max_abs_px"] = float(np.abs(served - ref).max())
        ctl = np.asarray(ctl_fn(p, left, right))
        row["control_rel_l1"] = float(np.abs(ctl - ref).mean() / scale)
        if "rel_l1" in row:
            row["gap_over_control"] = row["rel_l1"] / row["control_rel_l1"]
        out["samples"].append(row)
    out["seconds"] = time.time() - t0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
