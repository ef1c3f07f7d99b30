"""A/B the fused encoder stage under TRAINING (VERDICT r4 item 4): with
the saved-residual backward (_stage_bwd_xla) the stage no longer pays the
old re-linearized XLA forward; this measures whether fused_encoder on now
beats off at the reference recipe and by how much.  Alternating
same-process pairs.

Usage: python scripts/ab_train_fused_encoder.py [--reps 6] [--pairs 2]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--height", type=int, default=320)
    p.add_argument("--width", type=int, default=720)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--iters", type=int, default=16)
    p.add_argument("--reps", type=int, default=6)
    p.add_argument("--pairs", type=int, default=2)
    args = p.parse_args()


    import jax
    import jax.numpy as jnp
    import numpy as np

    from raftstereo_tpu.config import RAFTStereoConfig, TrainConfig
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.train import (create_train_state, make_optimizer,
                                      make_train_step)

    rng = np.random.default_rng(0)
    batch_data = (
        jnp.asarray(rng.integers(0, 255,
                                 (args.batch, args.height, args.width, 3))
                    .astype(np.float32)),
        jnp.asarray(rng.integers(0, 255,
                                 (args.batch, args.height, args.width, 3))
                    .astype(np.float32)),
        jnp.asarray(-np.abs(rng.normal(
            size=(args.batch, args.height, args.width, 1)))
            .astype(np.float32) * 8),
        jnp.ones((args.batch, args.height, args.width), jnp.float32),
    )

    # The two variants cannot coexist on the chip (two compiled remat'd
    # programs + states exhaust HBM — measured), so each variant runs as
    # its own block with everything freed in between; the False block runs
    # twice (bracketing) so chip drift across blocks is visible.
    results = {False: [], True: []}

    def run_variant(fused):
        cfg = RAFTStereoConfig(corr_implementation="pallas_alt",
                               compute_dtype="bfloat16", remat=True,
                               fused_encoder=fused)
        tcfg = TrainConfig(batch_size=args.batch, train_iters=args.iters,
                           image_size=(args.height, args.width))
        model = RAFTStereo(cfg)
        tx, sched = make_optimizer(tcfg)
        state = create_train_state(model, jax.random.key(0), tx,
                                   (args.height, args.width))
        step = make_train_step(model, tx, tcfg, lr_schedule=sched)

        def run_reps(st, data, n):
            def body(i, s):
                s, _ = step(s, data)
                return s
            return jax.lax.fori_loop(0, n, body, st)

        fn = jax.jit(run_reps, static_argnums=(2,), donate_argnums=(0,))
        state = fn(state, batch_data, 1)  # compile + warm
        _ = float(jax.tree.leaves(state.params)[0].sum())
        for _i in range(args.pairs):
            t0 = time.perf_counter()
            state = fn(state, batch_data, args.reps)
            _ = float(jax.tree.leaves(state.params)[0].sum())
            dt = time.perf_counter() - t0
            sps = args.reps / dt
            results[fused].append(sps)
            print(f"fused_encoder={fused}: {sps:7.4f} steps/sec", flush=True)
        del state, fn
        jax.clear_caches()

    run_variant(False)
    run_variant(True)
    run_variant(False)

    for fused in (False, True):
        print(f"fused_encoder={fused}: "
              f"{[round(x, 4) for x in results[fused]]}")
    base = sum(results[False]) / len(results[False])
    best = sum(results[True]) / len(results[True])
    print(f"mean fused/plain ratio: {best / base:.4f} "
          f"(plain bracket spread: {min(results[False]):.4f}-"
          f"{max(results[False]):.4f})")


if __name__ == "__main__":
    main()
