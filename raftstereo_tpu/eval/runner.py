"""Compiled inference runner for evaluation and demo.

Wraps the model's test-mode forward behind the shared pad-and-bucket shape
policy (``ops/image.BucketPadder``); ``jax.jit`` caches one executable per
distinct padded shape, so a dataset with varying image sizes (e.g. ETH3D)
compiles once per shape instead of per image (SURVEY.md §7 hard-part 4:
dynamic shapes vs XLA recompilation).  ``bucket_multiple`` optionally rounds
the padded shape up to a coarser grid to share compiles across
near-identical sizes — the same policy the serving engine
(serve/engine.py) uses, so their outputs agree bitwise.

Replaces the per-image boilerplate of the reference evaluators
(reference: evaluate_stereo.py:28-36,70-83): pad -> forward(test_mode) ->
unpad, plus wall-clock timing of the compiled step.  Timing spans the host
fetch of the output: a host fetch proves execution finished.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.image import BucketPadder
from ..utils.profiling import LatencyHistogram


class Evaluator:
    """Stateful wrapper: (H, W, 3) numpy image pair -> (H, W) x-flow field.

    Predictions follow the dataset sign convention (negative disparity,
    reference: core/stereo_datasets.py:77), so they compare directly against
    the ``flow`` channel produced by the data layer.

    ``last_runtime`` is the wall-clock of the latest call (forward + host
    fetch); ``last_included_compile`` flags calls whose padded shape had not
    been executed before, i.e. whose runtime contains an XLA compile — FPS
    protocols should drop those samples.  ``cache_stats`` aggregates the
    same signal (compile-cache hits/misses over the Evaluator's lifetime),
    and ``latency`` accumulates per-call runtimes in a fixed-bucket
    histogram with p50/p90/p99 summaries.
    """

    def __init__(self, model, variables, iters: int = 32,
                 divis_by: int = 32, bucket_multiple: Optional[int] = None,
                 batch_pad: Optional[int] = None, mesh=None):
        self.model = model
        self.variables = variables
        self.iters = iters
        self.divis_by = divis_by
        self.bucket_multiple = bucket_multiple
        # Serving-parity mode: zero-pad the batch axis to this size so the
        # pair executes at the serving engine's padded-batch program shape
        # (serve/engine.py: a plain dispatch holds the rows that came, at
        # row count 1 or max_batch_size, so a pair served alone is
        # batch_pad=1 and one of a full batch is max_batch_size; only the
        # warm-start path still pads to max_batch_size).  XLA tiles
        # reductions differently per program shape, so only identical
        # shapes guarantee bitwise-identical per-sample results.
        self.batch_pad = batch_pad
        self._fn = model.jitted_infer(iters=iters)
        # Optional multi-chip spatial parallelism: shard image height over
        # the mesh's 'space' axis so ONE pair uses several chips' HBM/FLOPs
        # (XLA inserts the conv halo exchanges; the 1-D correlation is along
        # W, so each height shard's epipolar lines are self-contained —
        # numerically transparent, tests/test_parallel.py).
        self._in_sharding = None
        self._mesh = mesh
        if mesh is not None:
            from ..parallel import SPACE_AXIS, replicated, spatial_sharded
            space = mesh.shape.get(SPACE_AXIS, 1)
            # The final padded height is a multiple of bucket_multiple when
            # set, else of divis_by; sharding H over 'space' needs that to be
            # divisible, so fail fast with the fix.
            governing = ("bucket_multiple", self.bucket_multiple) \
                if self.bucket_multiple else ("divis_by", self.divis_by)
            if governing[1] % space:
                raise ValueError(
                    f"mesh '{SPACE_AXIS}' extent {space} must divide "
                    f"{governing[0]}={governing[1]}; pass {governing[0]}="
                    f"{governing[1] * space} (or a multiple of {space})")
            self._in_sharding = spatial_sharded(mesh)
            # Weights restored from a checkpoint arrive committed to one
            # device; jit refuses mixed device sets, so replicate them onto
            # the mesh explicitly.
            self.variables = jax.device_put(self.variables, replicated(mesh))
        self.compiled_shapes: Set[Tuple[int, int]] = set()
        self.cache_hits: int = 0
        self.cache_misses: int = 0
        self.latency = LatencyHistogram()
        self.last_runtime: float = float("nan")
        self.last_included_compile: bool = True

    @property
    def cache_stats(self) -> Dict[str, int]:
        """Compile-cache counters: one miss per padded shape ever executed."""
        return {"hits": self.cache_hits, "misses": self.cache_misses,
                "shapes": len(self.compiled_shapes)}

    def __call__(self, image1: np.ndarray, image2: np.ndarray) -> np.ndarray:
        if image1.ndim == 3:
            image1, image2 = image1[None], image2[None]
        assert image1.shape[0] == 1, (
            f"Evaluator is single-pair; got batch {image1.shape[0]}")
        padder = BucketPadder(image1.shape, divis_by=self.divis_by,
                              bucket_multiple=self.bucket_multiple)
        i1, i2 = padder.pad(jnp.asarray(image1), jnp.asarray(image2))
        if self.batch_pad and self.batch_pad > 1:
            rows = ((0, self.batch_pad - 1), (0, 0), (0, 0), (0, 0))
            i1, i2 = jnp.pad(i1, rows), jnp.pad(i2, rows)
        if self._in_sharding is not None:
            i1 = jax.device_put(i1, self._in_sharding)
            i2 = jax.device_put(i2, self._in_sharding)
        shape = tuple(i1.shape[1:3])
        self.last_included_compile = shape not in self.compiled_shapes
        if self.last_included_compile:
            self.cache_misses += 1
        else:
            self.cache_hits += 1
        start = time.perf_counter()
        from ..parallel.context import use_corr_mesh
        with use_corr_mesh(self._mesh):  # lets Pallas backends shard_map
            _, flow_up = self._fn(self.variables, i1, i2)
        flow_up = np.asarray(flow_up, np.float32)  # host fetch = completion
        self.last_runtime = time.perf_counter() - start
        self.latency.observe(self.last_runtime)
        self.compiled_shapes.add(shape)
        return padder.unpad(flow_up)[0, ..., 0]
