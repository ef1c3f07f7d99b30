"""The plain reference against the program, on the CPU at 64x96, both
configurations (float32 both sides, the program on its plain XLA path)."""

import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("name", ["raftstereo_default", "raftstereo_realtime"])
def test_forward_matches_the_program(name):
    import jax
    import jax.numpy as jnp

    from benchmark.loadgen.pairs import make_pair
    from benchmark.reference import raft_stereo as R
    from benchmark.weights import make_weights
    from raftstereo_tpu.config import RAFTStereoConfig
    from raftstereo_tpu.models import RAFTStereo
    from raftstereo_tpu.utils.convert import torch_to_variables

    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        cfg = json.load(f)["model"]
    w = make_weights(cfg, 7)
    pc = RAFTStereoConfig(
        n_downsample=cfg["n_downsample"], n_gru_layers=cfg["n_gru_layers"],
        shared_backbone=cfg["shared_backbone"],
        slow_fast_gru=cfg["slow_fast_gru"], corr_implementation="reg")
    model = RAFTStereo(pc)
    v = torch_to_variables(w, model.init(jax.random.key(0)), pc)
    left, right = make_pair(3, 0, (64, 96), max_disp=8)
    l, r = left[None].astype(np.float32), right[None].astype(np.float32)
    lo, up = model.forward(v, l, r, iters=4, test_mode=True)
    rlo, rup = R.forward({k: jnp.asarray(x) for k, x in w.items()}, cfg,
                         l, r, 4)
    scale = float(jnp.abs(rup).mean())
    assert scale > 0.01                      # the field moved
    assert float(jnp.abs(up - rup).mean()) / scale < 1e-3
    assert float(jnp.abs(lo - rlo).mean()) / scale < 1e-3


def test_bucket_pad_is_the_servers_policy():
    from benchmark.reference.raft_stereo import bucket_pad
    from raftstereo_tpu.ops.image import BucketPadder

    for hw in ((540, 960), (375, 1242), (64, 96)):
        t, b, l, r = bucket_pad(hw, 32, 64)
        bp = BucketPadder(hw, divis_by=32, bucket_multiple=64)
        assert (hw[0] + t + b, hw[1] + l + r) == bp.bucket_hw
        x = np.arange(hw[0] * hw[1], dtype=np.float32).reshape(1, *hw, 1)
        ours = np.pad(x, ((0, 0), (t, b), (l, r), (0, 0)), mode="edge")
        assert np.array_equal(np.asarray(bp.pad(x)), ours)
