"""Worker process for the 2-process distributed test (NOT a test module).

Each invocation is one JAX process in a real multi-process group (CPU
backend, local coordinator).  The worker builds the same tiny model and
deterministic global batch on every process, feeds only its own slice
through ``global_batch_from_local`` (the multi-host input path,
parallel/distributed.py:95-107), runs one sharded train step, and prints
the resulting loss as JSON.  The test asserts both processes agree and
that the loss matches a single-process run — proving the per-host feeding
path and the XLA gradient all-reduce across process boundaries.

Run: python tests/distributed_worker.py --coordinator 127.0.0.1:PORT \
        --num_processes 2 --process_id 0
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--coordinator", required=True)
    p.add_argument("--num_processes", type=int, required=True)
    p.add_argument("--process_id", type=int, required=True)
    p.add_argument("--global_batch", type=int, default=4)
    args = p.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")  # before any backend use

    from raftstereo_tpu.parallel import distributed as dist

    if args.num_processes > 1:
        dist.initialize(coordinator_address=args.coordinator,
                        num_processes=args.num_processes,
                        process_id=args.process_id)
        assert jax.process_count() == args.num_processes, jax.process_count()

    import numpy as np

    from raftstereo_tpu.config import RAFTStereoConfig, TrainConfig
    from raftstereo_tpu.models import RAFTStereo
    from raftstereo_tpu.parallel import make_mesh
    from raftstereo_tpu.train import (create_train_state, make_optimizer,
                                      make_train_step)
    from raftstereo_tpu.train.step import jit_train_step

    cfg = RAFTStereoConfig(corr_implementation="reg", n_gru_layers=1,
                           hidden_dims=(32,), corr_levels=2, corr_radius=2)
    hw = (32, 48)
    tcfg = TrainConfig(batch_size=args.global_batch, train_iters=2,
                       image_size=hw, num_steps=10, lr=1e-4)

    model = RAFTStereo(cfg)
    tx, sched = make_optimizer(tcfg)
    # Same seed everywhere -> identical initial params on every process.
    state = create_train_state(model, jax.random.key(0), tx, image_hw=hw)

    # The full deterministic global batch, then this process's slice only
    # (the per-host loader protocol, parallel/distributed.py:80-92).
    rng = np.random.default_rng(7)
    h, w = hw
    g = args.global_batch
    img1 = rng.uniform(0, 255, (g, h, w, 3)).astype(np.float32)
    img2 = rng.uniform(0, 255, (g, h, w, 3)).astype(np.float32)
    flow = -np.abs(rng.normal(size=(g, h, w, 1))).astype(np.float32) * 4
    valid = np.ones((g, h, w), np.float32)
    local_n, offset = dist.process_local_batch(g)
    local = tuple(x[offset:offset + local_n]
                  for x in (img1, img2, flow, valid))

    mesh = make_mesh()  # all global devices on the data axis
    batch = dist.global_batch_from_local(mesh, local)
    step_fn = jit_train_step(make_train_step(model, tx, tcfg, sched), mesh)
    state, metrics = step_fn(state, batch)
    print(json.dumps({"process": jax.process_index(),
                      "devices": jax.device_count(),
                      "loss": float(metrics["loss"]),
                      "epe": float(metrics["epe"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
