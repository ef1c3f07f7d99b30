"""A/B the frozen-BN fused layer2 stage (the context encoder's layer2 /
realtime trunk) against the shipped instance-only state: both arms keep
the instance-norm fnet layer2 fused; the toggle is ONLY the cnet/BN
branch (pallas_layer2._fused_layer2_bn_enabled).  Alternating
same-process pairs, reps inside one device loop.

Usage: python scripts/ab_layer2_bn.py [--batch 1] [--reps 10] [--pairs 3]
       [--realtime]
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
    p.add_argument("--reps", type=int, default=10)
    p.add_argument("--pairs", type=int, default=3)
    p.add_argument("--realtime", action="store_true")
    args = p.parse_args()


    import jax
    import jax.numpy as jnp
    import numpy as np

    from raftstereo_tpu.config import RAFTStereoConfig
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.ops import pallas_layer2 as pl2
    from raftstereo_tpu.ops.image import InputPadder

    model_kw = {}
    if args.realtime:
        model_kw = dict(shared_backbone=True, n_downsample=3, n_gru_layers=2,
                        hidden_dims=(128, 128), slow_fast_gru=True)
        args.iters = 7
    cfg = RAFTStereoConfig(corr_implementation="pallas_alt",
                           compute_dtype="bfloat16", **model_kw)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))
    rng = np.random.default_rng(0)
    img = rng.integers(0, 255, (args.batch, args.height, args.width, 3))
    img1 = jnp.asarray(img.astype(np.float32))
    img2 = jnp.asarray(img.astype(np.float32))
    padder = InputPadder(img1.shape, divis_by=32)
    img1, img2 = padder.pad(img1, img2)

    def make_fn():
        def run_reps(v, a, b, n):
            def body(i, acc):
                lo, up = model.forward(v, a + i.astype(a.dtype) * 0, b,
                                       iters=args.iters, test_mode=True)
                return acc + up.sum().astype(jnp.float32)
            return jax.lax.fori_loop(0, n, body, jnp.float32(0))
        return jax.jit(run_reps, static_argnums=(3,))

    fns = {}
    disps = {}
    for flag in (False, True):
        pl2._fused_layer2_bn_enabled = flag
        fns[flag] = make_fn()
        float(fns[flag](variables, img1, img2, args.reps))
        one = jax.jit(lambda v, a, b: model.forward(
            v, a, b, iters=args.iters, test_mode=True))
        disps[flag] = np.asarray(one(variables, img1, img2)[1])

    dev = float(np.abs(disps[True] - disps[False]).max())
    print(f"max |disp_bn_fused - disp_plain| = {dev:.3e} px (GRU-amplified "
          f"bf16 rounding on random weights)", flush=True)

    results = {False: [], True: []}
    for _ in range(args.pairs):
        for flag in (False, True):
            t0 = time.perf_counter()
            float(fns[flag](variables, img1, img2, args.reps))
            dt = time.perf_counter() - t0
            pps = args.batch * args.reps / dt
            results[flag].append(pps)
            print(f"bn_layer2={flag}: {pps:8.3f} pairs/sec", flush=True)

    for flag in (False, True):
        print(f"bn_layer2={flag}: {[round(x, 2) for x in results[flag]]}")
    deltas = [b / a for a, b in zip(results[False], results[True])]
    print(f"per-pair bn/plain ratios: {[round(d, 4) for d in deltas]}")


if __name__ == "__main__":
    main()
