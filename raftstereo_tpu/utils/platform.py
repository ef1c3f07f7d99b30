"""Process-level JAX set-up shared by the entry points: where the persistent
compile cache lives, and one description of the device a process runs on and
of what its backend-keyed kernel gates resolved to.

Platform selection itself needs no help: ``JAX_PLATFORMS`` in the environment
(or ``jax.config.update("jax_platforms", ...)`` before the first backend use)
is all the installed JAX needs.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> Optional[str]:
    """The persistent compile cache this process would use, if any."""
    import jax

    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or jax.config.jax_compilation_cache_dir)


def setup_compile_cache() -> Optional[str]:
    """Place JAX's persistent compile cache; returns its directory or None.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is touched.  Where it is not, a process that was not pinned to
    the CPU caches under ``<checkout>/.jax_cache`` — a fixed path, because
    the path is part of what makes a later process find the entries — and
    caches every program, however quick its compile.  CPU runs (the test
    suite: ``JAX_PLATFORMS=cpu``) stay cache-free: executables deserialized
    from a CPU cache crashed the train loop in PR 2 (CHANGES.md).

    Reads the *requested* platform and never initialises a backend, so
    model-free processes (router, load generator, chip_smoke's parent) may
    call it without taking the chip.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    requested = (jax.config.jax_platforms or "").split(",")[0]
    if requested == "cpu":
        return None
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def describe_program(config, batch: int, hw: Sequence[int]) -> Dict:
    """What the shapes of one compiled program — ``config`` at ``batch``
    padded images of ``hw`` — resolve to where the kernels choose from
    shapes: ``corr_block``, the rows and pixels one grid step of the
    on-demand lookup takes (None where another backend serves), and
    ``fused_stages``, the encoder gates' answers as ``models/encoders.py``
    asks them (the context encoder sees the batch, the feature encoder
    both images of every pair).  Pure in the backend's name, the device
    count and the override scopes of the calling thread."""
    from ..ops.corr import resolve_implementation
    from ..ops.pallas_corr import _BLOCK_ROWS, _block_w1
    from ..ops.pallas_encoder import use_fused_stem
    from ..ops.pallas_layer2 import use_fused_layer2

    stride = 1 + (config.n_downsample > 2)
    h, w = -(-hw[0] // stride), -(-hw[1] // stride)
    stride2 = 1 + (config.n_downsample > 1)
    corr = resolve_implementation(config.corr_implementation,
                                  config.corr_quant)
    stages = {}
    for tag, norm, n in (("cnet", config.context_norm, batch),
                         ("fnet", "instance", 2 * batch)):
        stages[f"stem_{tag}"] = bool(use_fused_stem(
            norm, (n, h, w, 64), config.fused_encoder))
        stages[f"layer2_{tag}"] = bool(use_fused_layer2(
            norm, stride2, (n, h, w, 64), override=config.fused_encoder))
    return {
        "corr_block": ({"rows": _BLOCK_ROWS,
                        "pixels": _block_w1(hw[1] // 2 ** config.n_downsample)}
                       if corr == "pallas_alt" else None),
        "fused_stages": stages,
    }


def describe_runtime(config, batch: int, hw: Sequence[int]) -> Dict:
    """The device this process runs on and what each ``auto`` kernel gate
    resolves to here for ``config`` at ``batch`` padded images of ``hw`` —
    the one line the serving engine and the trainer log at start-up, and what
    ``chip_smoke.py`` holds a chip run to.  Initialises the backend."""
    import jax

    from ..ops.corr import resolve_implementation
    from ..ops.pallas_alt import resolve_corr_matmul
    from ..ops.pallas_corr import _interpret

    devices = jax.devices()
    stages = describe_program(config, batch, hw)["fused_stages"]
    corr = resolve_implementation(config.corr_implementation,
                                  config.corr_quant)
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "corr": corr,
        "corr_auto": resolve_implementation("auto"),
        # the pallas_alt lookup's matmul form, from the dtype the encoder
        # hands the features over in (the kernel wrapper asks the same
        # resolver); the other backends build their volume otherwise
        "corr_matmul": (resolve_corr_matmul(config.compute_dtype,
                                            config.corr_dtype,
                                            config.corr_precision)
                        if corr == "pallas_alt" else None),
        "fused_stem_cnet": stages["stem_cnet"],
        "fused_stem_fnet": stages["stem_fnet"],
        "pallas_interpret": bool(_interpret()),
        "compile_cache": compile_cache_dir(),
    }
