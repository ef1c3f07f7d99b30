"""Seeded weights for a configuration, under the published state-dict names.

Both sides get the same numbers: the program as a ``.pth`` checkpoint in the
published layout (what ``--restore_ckpt`` of every entry point accepts), the
plain reference as the dict itself.  No JAX here, so the runner stays off the
chip.

The draw is the published initialisation (normal, fan-out scaled, zero
biases, unit frozen batch-norm) except for the factors a configuration's
``weights`` block names.  Untrained RAFT-Stereo is chaotic: the recurrence
feeds each disparity step back through the correlation lookup and at the
published scale a rounding difference grows about tenfold an iteration, so
after 32 iterations bfloat16 and float32 share nothing and no comparison
could tell a sound run from a broken one.  A trained model contracts
instead.  ``flow_head_scale`` shrinks the last convolution of the flow head,
which is the loop gain, until the seeded model contracts too; the work per
pair does not depend on it.
"""

from __future__ import annotations

import zlib
from typing import Dict

import numpy as np

from benchmark.reference.raft_stereo import param_spec


def make_weights(cfg: Dict, seed: int) -> Dict[str, np.ndarray]:
    scales = cfg.get("weights", {})
    out = {}
    for name, shape in param_spec(cfg).items():
        leaf = name.rsplit(".", 1)[1]
        if len(shape) == 4:
            rng = np.random.default_rng(
                [int(seed), zlib.crc32(name.encode())])
            o, _, kh, kw = shape
            w = rng.standard_normal(shape, dtype=np.float32)
            w *= np.float32((2.0 / (o * kh * kw)) ** 0.5)
            if name == "update_block.flow_head.conv2.weight":
                w *= np.float32(scales.get("flow_head_scale", 1.0))
            out[name] = w
        elif leaf in ("bias", "running_mean"):
            out[name] = np.zeros(shape, np.float32)
        else:                       # norm weight, running_var
            out[name] = np.ones(shape, np.float32)
    return out


def write_pth(weights: Dict[str, np.ndarray], path: str) -> None:
    """The published checkpoints are ``torch.save``d state dicts with the
    ``module.`` prefix of ``DataParallel``."""
    import torch

    torch.save({"module." + k: torch.from_numpy(v)
                for k, v in weights.items()}, path)
