"""A/B the corr matmul precision (VERDICT r2 item 3): HIGHEST vs HIGH vs
DEFAULT in one process, same methodology as bench.py, plus the disparity
deviation each lower precision introduces against the HIGHEST reference.

Usage: python scripts/ab_corr_precision.py [--corr pallas_alt] [--reps 10]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--height", type=int, default=540)
    p.add_argument("--width", type=int, default=960)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--iters", type=int, default=32)
    p.add_argument("--corr", default="pallas_alt")
    p.add_argument("--reps", type=int, default=10)
    args = p.parse_args()


    import jax
    import jax.numpy as jnp
    import numpy as np

    from raftstereo_tpu.config import RAFTStereoConfig
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.ops.image import InputPadder

    rng = np.random.default_rng(0)
    img1 = rng.integers(0, 255, (args.batch, args.height, args.width, 3))
    img2 = rng.integers(0, 255, (args.batch, args.height, args.width, 3))
    img1 = jnp.asarray(img1.astype(np.float32))
    img2 = jnp.asarray(img2.astype(np.float32))
    padder = InputPadder(img1.shape, divis_by=32)
    img1, img2 = padder.pad(img1, img2)

    results = {}
    disp_ref = None
    variables = None
    for precision in ("highest", "high", "default"):
        cfg = RAFTStereoConfig(corr_implementation=args.corr,
                               compute_dtype="bfloat16",
                               corr_precision=precision)
        model = RAFTStereo(cfg)
        if variables is None:
            variables = model.init(jax.random.key(0), (64, 96))

        def run_reps(v, a, b, n):
            def body(i, acc):
                lo, up = model.forward(v, a + i.astype(a.dtype) * 0, b,
                                       iters=args.iters, test_mode=True)
                return acc + up.sum().astype(jnp.float32)
            return jax.lax.fori_loop(0, n, body, jnp.float32(0))

        fn = jax.jit(run_reps, static_argnums=(3,))
        float(fn(variables, img1, img2, args.reps))
        t0 = time.perf_counter()
        float(fn(variables, img1, img2, args.reps))
        dt = time.perf_counter() - t0
        pps = args.batch * args.reps / dt

        one = jax.jit(lambda v, a, b: model.forward(v, a, b, iters=args.iters,
                                                    test_mode=True))
        _, up = one(variables, img1, img2)
        up = np.asarray(up)
        if disp_ref is None:
            disp_ref = up
            dev = 0.0
        else:
            dev = float(np.abs(up - disp_ref).max())
        results[precision] = (pps, dev)
        print(f"{precision:8s}: {pps:7.3f} pairs/sec   "
              f"max |disp - disp_highest| = {dev:.3e} px", flush=True)

    base = results["highest"][0]
    for k, (pps, dev) in results.items():
        print(f"{k:8s}: {pps/base:6.3f}x vs highest")


if __name__ == "__main__":
    main()
