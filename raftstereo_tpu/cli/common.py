"""Shared CLI plumbing: logging setup and weight loading.

One weight loader covers both checkpoint families so every entry point can
restore from either (the reference is .pth-only and strict,
reference: evaluate_stereo.py:215-220, demo.py:25):

* ``*.pth``          — released/reference torch checkpoints, converted on
                       load (utils/convert.py)
* anything else      — this framework's Orbax weight directories
"""

from __future__ import annotations

import logging
from typing import Dict

from ..config import RAFTStereoConfig


def setup_logging(level=logging.INFO) -> None:
    """Logging + compile-cache placement shared by every CLI entry point."""
    from ..utils.platform import setup_compile_cache
    setup_compile_cache()
    # force=True: importing jax/absl can leave a handler on the root
    # logger — without force, basicConfig would silently no-op and
    # INFO-level progress ("Mesh", "Resumed from step N") would never
    # reach stderr in non-tty/subprocess runs.
    logging.basicConfig(
        level=level, force=True,
        format="%(asctime)s %(levelname)-8s [%(filename)s:%(lineno)d] %(message)s")


def load_variables(path: str, config: RAFTStereoConfig, model=None) -> Dict:
    """Restore model variables from a .pth file or an Orbax weights dir."""
    if path.endswith(".pth"):
        from ..utils.convert import convert_checkpoint
        return convert_checkpoint(path, config)
    from ..models import RAFTStereo
    from ..train.checkpoint import load_weights
    model = model or RAFTStereo(config)
    import jax
    template = model.init(jax.random.key(0))
    return load_weights(path, template)
