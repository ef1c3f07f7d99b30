"""Shape-bucketed compiled inference engine: a dispatch holds the rows
that came.

The serving analogue of ``eval/runner.Evaluator``: one compiled executable
per (shape bucket, GRU iterations, row count, input mode, precision mode),
reused across requests.
Three shape decisions keep the XLA compile count small and predictable:

* every image is padded with the SAME ``BucketPadder`` policy the Evaluator
  uses (divis_by alignment, then round-up to ``bucket_multiple``), so
  near-identical sizes share a bucket — and per-sample numerics match the
  Evaluator bitwise at the same batch shape (``batch_pad=rows``);
* a plain dispatch holds exactly a compiled ROW COUNT of real rows and no
  zero rows (``row_counts``: 1 and ``max_batch_size``), so a bucket
  compiles once per row count and a lone pair never pays for a full
  batch.  The batcher takes a full batch when one is queued and one row
  when not; ``infer_batch`` runs any other ``n`` as the fewest dispatches
  of compiled counts (3 -> 1 + 1 + 1).  The warm-start (``stream``) path
  keeps one program, zero-padded to ``max_batch_size`` rows;
* configured buckets are compiled eagerly at startup (``warmup``), every
  row count of them, so the first real request never pays the
  multi-second XLA compile.

The engine is lock-serialized: ordering and batching policy live in the
batcher; this layer owns shapes, compiles and device dispatch only.  A
dispatch has two halves — ``_launch`` (compile bookkeeping and the
asynchronous jitted call, under the engine lock, so the device's order is
the order of the launches) and ``_finish`` (wait, fetch, metrics).  The
plain path offers them as ``launch_batch`` / ``finish_batch`` so the
batcher can stage and launch the next full batch while the one before it
runs; ``infer_batch`` is ``finish(launch(...))``, and every other path
(warm-start, ``--sched``, cascade, spatial) runs both halves under the
lock in ``_dispatch``, synchronous as before.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import math
import threading
import time
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ServeConfig
from ..obs.trace import timed_phase
from ..ops.image import BucketPadder
from ..ops.quant import MODES, config_for_mode, default_mode
from .metrics import ServeMetrics

logger = logging.getLogger(__name__)

__all__ = ["BatchEngine"]


def row_counts(max_batch_size: int) -> Tuple[int, ...]:
    """The batch shapes the plain path compiles, from ``max_batch_size``
    alone: one row and a full batch (8 -> 1, 8; 1 -> 1).  A row costs the
    same device time at either on the v5e (PERF.md §5, ``T(rows)``), so a
    count in between would only add a program to every start; the take
    rules below are written over the tuple, for the cell that shows a
    shape where a mid-size batch is cheaper a row than singles."""
    return tuple(sorted({1, max_batch_size}))


def split_rows(n: int, counts: Sequence[int]) -> List[int]:
    """``n`` rows as the fewest dispatches of compiled row counts, largest
    first (counts 1, 8: 8 -> [8]; 3 -> [1, 1, 1]; 11 -> [8, 1, 1, 1]).
    ``counts`` holds 1, so every ``n`` has an answer."""
    out = []
    for c in sorted(counts, reverse=True):
        out += [c] * (n // c)
        n %= c
    return out


@dataclasses.dataclass
class _Launched:
    """One dispatch between its two halves (``_launch`` / ``_finish``):
    the device result nobody has waited for yet, and the phase windows so
    far.  The windows ride here and not on the thread-local
    ``last_segments``: one thread may launch a plain dispatch and another
    finish it (serve/batcher.py)."""

    key: Tuple
    labels: Dict[str, str]
    miss: bool
    out_dev: object
    launch: Tuple[float, float]
    pad: Optional[Tuple[float, float]]
    pad_px: Optional[Dict[str, int]]
    # ``stage_copy`` / ``h2d_put`` windows (the two parts of ``pad``), and
    # every phase's thread times so far (``timed_phase.thread_ms``)
    stage: Dict[str, Tuple[float, float]]
    thread_ms: Dict[str, Dict[str, float]]
    # The dispatch launched before this one on the same engine: until its
    # ``ready_at`` the device is not free to take this one up.
    behind: Optional["_Launched"]
    padders: Sequence[BucketPadder] = ()  # plain path: one per real row
    ready_at: Optional[float] = None  # device_wait's end, set by _finish
    # Every window of the dispatch, as ``last_segments`` documents them;
    # set by ``_finish``.
    segments: Optional[Dict[str, object]] = None


class BatchEngine:
    """Batched test-mode forward behind a shape-bucketed compile cache:
    a plain dispatch runs the program of exactly its row count."""

    def __init__(self, model, variables, config: ServeConfig,
                 metrics: Optional[ServeMetrics] = None, device=None,
                 fault_plan=None, tracer=None):
        self.model = model
        # Where a bucket program's ``compile`` span goes (obs/trace.py
        # Tracer; None records none): the first call of every compiled
        # key, with what its shapes resolved to (``_program_facts``).
        self.tracer = tracer
        # Serving-plane chaos seam (utils/faults.py FaultPlan or None):
        # ``slow_replica@request=N:SECS`` injects dispatch latency at
        # the top of ``_dispatch`` — a replica that is alive but slow,
        # the hedged-request trigger.  Host-side only: the sleep
        # happens before any device work, so chaos runs add ZERO new
        # XLA compiles.
        self.fault_plan = fault_plan
        # ``device`` pins every executable (and the weights) to one chip:
        # the replicated cluster (serve/cluster/) builds one engine per
        # device from parallel.mesh.replica_devices, each with its OWN
        # jit wrappers — so each replica owns an independent compile
        # cache and the replicas never serialize on one another's
        # dispatch lock.  None keeps JAX's default placement (the
        # single-engine path, unchanged).
        self.device = device
        if device is not None:
            variables = jax.device_put(variables, device)
        self.variables = variables
        self.cfg = config
        self.metrics = metrics
        # The row counts a plain dispatch can hold, from max_batch_size
        # alone; the batcher reads them to decide how many queued rows to
        # take (serve/batcher.py ``_take``).
        self.row_counts = row_counts(config.max_batch_size)
        # Precision modes (ops/quant.py): every executable key carries the
        # resolved mode ("fp32"/"bf16"/"int8") as its LAST component — the
        # per-request ``accuracy`` tier compiles a different program with
        # different numerics, so a key that omitted it could serve one
        # tier's executable to another tier's request.  ``default_mode``
        # is the base config's own numeric policy: requests without an
        # ``accuracy`` field resolve to it and run the base model
        # UNCHANGED (same executables, bitwise-identical results).
        self.default_mode = ("fp32" if model is None
                             else default_mode(model.config))
        # Input modality (sl/, docs/structured_light.md): joins every
        # executable cache key right before the precision mode.  A passive
        # and an SL model at the same bucket compile different programs
        # over different input ranks' worth of channels — a key that
        # omitted the modality could hand a 3-channel executable a
        # 12-channel batch.  Fixed per engine: the modality is a model-
        # architecture property (RAFTStereoConfig.input_mode), not a
        # per-request knob.
        self.input_mode = ("passive" if model is None
                           else model.config.input_mode)
        # Channels every raw input image carries (3 passive, 12 sl) —
        # the warmup zero-images and scheduler batch buffers are built at
        # this width.
        self.input_channels = (3 if model is None
                               else model.config.input_channels)
        # mode -> RAFTStereo sharing ``variables`` (tier configs only
        # change numeric-policy fields, so the fp32 weights apply to all;
        # flax casts per-module at apply time).  Built lazily: a server
        # with no tiers never constructs the extra models.
        self._models = {self.default_mode: model}  # guarded_by: _lock
        self._fns: Dict[object, object] = {}  # guarded_by: _lock
        # Spatial sharding (parallel/spatial.py): the resolved space-axis
        # shard count — ServeConfig overrides the model config's default;
        # <= 1 disables the spatial entry points.  Validated eagerly so a
        # misconfigured server fails at build time, not at the first 4K
        # request.  The (1, N) mesh itself is built lazily on first use
        # (guarded_by: _lock) — constructing it pulls device topology,
        # which replica-lifecycle test stubs (model=None) never have.
        self.spatial_shards = int(
            getattr(config, "spatial_shards", 0)
            or (1 if model is None
                else getattr(model.config, "spatial_shards", 1)))
        self._spatial_mesh = None  # guarded_by: _lock
        if self.spatial_shards > 1:
            from ..parallel.spatial import validate_spatial_config
            assert model is not None, "spatial sharding needs a model"
            assert device is None, (
                "spatial sharding splits one request across devices and "
                "cannot run on a device-pinned (cluster replica) engine")
            validate_spatial_config(model.config)
        # (keyed (iters, mode) | ("stream", iters, mode) | sched phases)
        self._lock = threading.RLock()
        # Fine-grained lock for _compiled only: stat readers (/healthz)
        # must not block behind _lock, which is held across a whole device
        # dispatch (seconds) or compile (minutes).
        self._stats_lock = threading.Lock()
        # Compiled keys.  Position 3 names the KIND of program in every
        # key, and nothing reads a kind from a key's length:
        # (h, w, iters, "batch", "rN", input_mode, mode) for the plain
        # forward at N rows (``_batch_key``),
        # (h, w, iters, "stream", input_mode, mode) for the warm-start
        # (flow_init) forward, (h, w, iters, "spatial", "sN", input_mode,
        # mode) for the sharded one — the row and shard counts ride as
        # the STRINGS "rN" / "sN" so the mixed-arity key set stays
        # sortable (ints at 0-2, strings from 3 on; /healthz sorts the
        # whole set for a stable compiled_buckets listing) — and the
        # sched_* / cascade_* phases below.
        self._compiled: Set[Tuple] = set()  # guarded_by: _stats_lock
        self.last_included_compile: bool = True  # guarded_by: _lock
        # The newest launch: the next one lies behind it on the device
        # until its ``ready_at`` (``_finish``'s ``device_queued``).
        self._last_launched: Optional[_Launched] = None  # guarded_by: _lock
        # Per-thread phase timing of the most recent dispatch THIS thread
        # ran (the batcher worker and concurrent stream handlers each read
        # their own): thread-local because an attribute would be overwritten
        # by whichever dispatch finished last.
        self._seg = threading.local()

    def _device_ctx(self):
        """Thread-local placement override for one dispatch: jit traces,
        input STAGING and transfers inside it target this engine's
        device (staging outside it would land on the global default
        device and pay a copy per dispatch).  A context manager (not a
        global config update) because concurrent replicas dispatch from
        different threads at once."""
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    # ----------------------------------------------------------- shape policy

    def _padder(self, shape: Sequence[int]) -> BucketPadder:
        return BucketPadder(shape, divis_by=self.cfg.divis_by,
                            bucket_multiple=self.cfg.bucket_multiple)

    def padder_of(self, shape: Sequence[int]) -> BucketPadder:
        """The padder an image of ``shape`` dispatches through — public for
        callers that unpad engine outputs themselves (the iteration-level
        scheduler unpads per leaving slot, serve/sched/scheduler.py)."""
        return self._padder(shape)

    def bucket_of(self, shape: Sequence[int]) -> Tuple[int, int]:
        """The padded (H, W) an image of ``shape`` executes at."""
        return self._padder(shape).bucket_hw

    @property
    def cache_stats(self) -> Dict[str, int]:
        with self._stats_lock:  # vs a concurrent add() resizing the set
            return {"compiled": len(self._compiled)}

    @property
    def compiled_keys(self) -> Set[Tuple]:
        with self._stats_lock:
            return set(self._compiled)

    def _batch_key(self, hw: Tuple[int, int], iters: int, rows: int,
                   mode: str) -> Tuple:
        return (hw[0], hw[1], iters, "batch", f"r{rows}", self.input_mode,
                mode)

    def _program_facts(self, key: Tuple) -> Dict:
        """What the shapes of a plain batch program resolved to: its
        ``rows`` and, at that row count, ``corr_block`` and
        ``fused_stages`` (utils/platform.describe_program); nothing for
        the other kinds of key, whose programs see other batches (stream,
        sched) or other paths (spatial, cascade)."""
        if key[3] != "batch":
            return {}
        facts = {"rows": int(key[4][1:])}  # "rN", _batch_key
        if self.model is not None:
            from ..utils.platform import describe_program
            facts.update(describe_program(self.model.config, facts["rows"],
                                          key[:2]))
        return facts

    @property
    def compiled_programs(self) -> Dict[str, Dict]:
        """Per compiled plain batch key (as ``h x w x iters x ...``): the
        lookup kernel's blocks and which encoder stages run fused."""
        return {"x".join(str(k) for k in key): self._program_facts(key)
                for key in sorted(k for k in self.compiled_keys
                                  if k[3] == "batch")}

    def is_warm(self, hw: Tuple[int, int], iters: int,
                mode: Optional[str] = None,
                rows: Optional[int] = None) -> bool:
        """Whether (bucket, iters, mode) already has a compiled
        executable at ``rows``; with no ``rows``, at every row count (no
        batch the batcher can form would compile)."""
        m = self._mode(mode)
        with self._stats_lock:
            return all(self._batch_key(hw, iters, r, m) in self._compiled
                       for r in (self.row_counts if rows is None
                                 else (rows,)))

    def is_stream_warm(self, hw: Tuple[int, int], iters: int,
                       mode: Optional[str] = None) -> bool:
        """Whether (bucket, iters, mode) has a compiled WARM-START
        executable."""
        with self._stats_lock:
            return (hw[0], hw[1], iters, "stream", self.input_mode,
                    self._mode(mode)) in self._compiled

    # ------------------------------------------------------ spatial sharding

    def _spatial_shard_count(self, shards: Optional[int]) -> int:
        """Resolve an optional per-call shard count against the engine's
        fixed mesh.  The count is a CACHE-KEY component (a 2-shard and a
        4-shard program differ), but one engine owns one mesh — a
        mismatching request is a caller bug, not a new mesh."""
        n = self.spatial_shards if shards is None else int(shards)
        assert n == self.spatial_shards, (
            f"engine mesh has {self.spatial_shards} spatial shards, "
            f"request asked for {n}")
        assert n > 1, "spatial sharding is disabled (spatial_shards <= 1)"
        return n

    def _spatial_padder(self, shape: Sequence[int]) -> BucketPadder:
        """Spatial shape policy: same BucketPadder family as the plain
        path, with the alignment raised so the padded H splits into
        ``spatial_shards`` equal slabs of whole row-multiples
        (parallel/spatial.check_spatial_shape)."""
        from ..parallel.spatial import spatial_row_multiple
        rows = spatial_row_multiple(self.model.config) * self.spatial_shards
        divis = math.lcm(self.cfg.divis_by, rows)
        return BucketPadder(shape, divis_by=divis,
                            bucket_multiple=math.lcm(
                                self.cfg.bucket_multiple, divis))

    def spatial_bucket_of(self, shape: Sequence[int]) -> Tuple[int, int]:
        """The padded (H, W) an image executes at on the spatial path."""
        return self._spatial_padder(shape).bucket_hw

    def is_spatial_warm(self, hw: Tuple[int, int], iters: int,
                        mode: Optional[str] = None,
                        shards: Optional[int] = None) -> bool:
        """Whether (bucket, iters, mode) has a compiled SPATIAL
        executable at the engine's shard count."""
        n = self._spatial_shard_count(shards)
        with self._stats_lock:
            return (hw[0], hw[1], iters, "spatial", f"s{n}",
                    self.input_mode,
                    self._mode(mode)) in self._compiled

    def low_hw(self, hw: Tuple[int, int]) -> Tuple[int, int]:
        """The 1/factor grid a padded bucket's disparity field lives on —
        the shape of session state and of every ``flow_init``."""
        f = self.model.config.factor
        return hw[0] // f, hw[1] // f

    def session_schema(self) -> Dict[str, object]:
        """The engine-level state-schema fingerprint that gates warm
        session migration (``SessionStore.export_state``/``import_state``):
        two engines may exchange warm-start state only when the 1/f grid
        (``factor``) and the executables that will consume it
        (``input_mode``) agree.  Pure metadata — no device work, no
        compiles."""
        cfg = getattr(self.model, "config", None)
        return {"factor": getattr(cfg, "factor", None),
                "input_mode": self.input_mode}

    # -------------------------------------------------------- precision modes

    def _mode(self, mode: Optional[str]) -> str:
        """Resolve an optional precision mode to the concrete cache-key
        component (None = the base config's own mode — the default path,
        which may be the non-tier ``"base"`` token when the config's
        numeric mix matches no canonical tier config)."""
        if mode is None or mode == self.default_mode:
            return self.default_mode
        assert mode in MODES, f"unknown precision mode {mode!r}"
        return mode

    def _model_for(self, mode: str):  # guarded_by: _lock
        """The model a precision mode traces with.  Tier models are the
        base architecture with only the numeric-policy config fields
        swapped (ops/quant.config_for_mode) and share ``self.variables``
        — construction is pure Python module wiring, done once."""
        model = self._models.get(mode)
        if model is None:
            from ..models.raft_stereo import RAFTStereo
            model = self._models[mode] = RAFTStereo(
                config_for_mode(self.model.config, mode))
        return model

    # -------------------------------------------------------------- execution

    def _fn(self, iters: int, mode: str):  # guarded_by: _lock
        key = (iters, mode)
        if key not in self._fns:
            model = self._model_for(mode)
            self._fns[key] = jax.jit(
                lambda v, a, b, it=iters, m=model: m.forward(
                    v, a, b, iters=it, test_mode=True))
        return self._fns[key]

    def _stream_fn(self, iters: int, mode: str):  # guarded_by: _lock
        """Warm-start forward: takes a (B, H/f, W/f, 1) flow_init.  Cold
        frames pass zeros — bitwise-identical to the plain forward (tested
        in tests/test_model.py / tests/test_stream.py), so one executable
        per (bucket, level, mode) serves every frame of a stream."""
        key = ("stream", iters, mode)
        if key not in self._fns:
            self._fns[key] = self._model_for(mode).jitted_infer_init(iters)
        return self._fns[key]

    def _spatial_fn(self, iters: int, mode: str):  # guarded_by: _lock
        """Sharded warm-start forward over the (1, N) spatial mesh
        (parallel/spatial.jitted_spatial_infer_init).  ONE executable per
        (bucket, iters, mode, shards) serves cold requests AND session
        warm-start frames: zeros ``flow_init`` is bitwise-identical to
        the cold forward, the same property the stream path rests on."""
        key = ("spatial", iters, mode, self.spatial_shards)
        if key not in self._fns:
            from ..parallel.spatial import (jitted_spatial_infer_init,
                                            spatial_mesh)
            if self._spatial_mesh is None:
                self._spatial_mesh = spatial_mesh(self.spatial_shards)
            self._fns[key] = jitted_spatial_infer_init(
                self._model_for(mode), self._spatial_mesh, iters)
        return self._fns[key]

    def _sched_prologue_fn(self, mode: str):  # guarded_by: _lock
        """Compiled phase 1/3 of the split forward (encode + corr build):
        (variables, img1, img2, flow_init) -> carried state.  Cold slots
        pass zero flow_inits — bitwise-identical to flow_init=None, so one
        executable serves plain requests and warm stream frames."""
        key = ("sched", "prologue", mode)
        if key not in self._fns:
            model = self._model_for(mode)
            self._fns[key] = jax.jit(
                lambda v, a, b, f, m=model: m.forward_prologue(
                    v, a, b, flow_init=f))
        return self._fns[key]

    def _sched_step_fn(self, iters_per_step: int,
                       mode: str):  # guarded_by: _lock
        """Compiled single-boundary step: advances the whole running batch
        by ``iters_per_step`` GRU iterations."""
        key = ("sched", "step", iters_per_step, mode)
        if key not in self._fns:
            model = self._model_for(mode)
            self._fns[key] = jax.jit(
                lambda v, s, it=iters_per_step, m=model: m.forward_step(
                    v, s, iters=it))
        return self._fns[key]

    def _sched_epilogue_fn(self, mode: str):  # guarded_by: _lock
        """Compiled phase 3/3: final mask head + convex upsample."""
        key = ("sched", "epilogue", mode)
        if key not in self._fns:
            model = self._model_for(mode)
            self._fns[key] = jax.jit(
                lambda v, s, m=model: m.forward_epilogue(v, s))
        return self._fns[key]

    def _sched_join_fn(self):  # guarded_by: _lock
        """Compiled per-slot merge: leaves of ``incoming`` replace leaves
        of ``running`` where the (B,) mask is True.  Every state leaf is
        batch-leading (models/raft_stereo.forward_prologue), so a join
        touches exactly the joining slots' rows."""
        key = ("sched", "join")
        if key not in self._fns:
            def join(running, incoming, mask):
                def sel(x, y):
                    m = mask.reshape((-1,) + (1,) * (x.ndim - 1))
                    return jnp.where(m, y, x)
                return jax.tree.map(sel, running, incoming)
            self._fns[key] = jax.jit(join)
        return self._fns[key]

    def _cascade_prologue_fn(self, cheap_mode: str,
                             cert_mode: str):  # guarded_by: _lock
        """Compiled cascade phase 1: BOTH tiers' prologues over the same
        images in one dispatch — ``(cheap carried state, staged certified
        state)``.  Staging at the prologue (vs rebuilding at handoff) is
        the builder decision documented in serve/cascade/handoff.py: one
        extra fp32 encode + corr build per cascade join, certified corr
        held in device memory for the cheap leg, and in exchange the
        handoff itself is a cast+swap that never stalls the certified
        batch behind an encode."""
        key = ("cascade", "prologue", cheap_mode, cert_mode)
        if key not in self._fns:
            m_cheap = self._model_for(cheap_mode)
            m_cert = self._model_for(cert_mode)

            def fn(v, a, b, f, mc=m_cheap, mx=m_cert):
                return (mc.forward_prologue(v, a, b, flow_init=f),
                        mx.forward_prologue(v, a, b, flow_init=f))
            self._fns[key] = jax.jit(fn)
        return self._fns[key]

    def _cascade_handoff_fn(self, cheap_mode: str,
                            cert_mode: str):  # guarded_by: _lock
        """Compiled tier handoff: the shared cast+swap expression
        (serve/cascade/handoff.handoff_state — also what the certifier
        compiles) followed by a lane gather, so promoted slots land at
        their assigned slots in the certified batch in one dispatch."""
        key = ("cascade", "handoff", cheap_mode, cert_mode)
        if key not in self._fns:
            from .cascade.handoff import handoff_state

            def fn(s, stage, idx):
                out = handoff_state(s, stage)
                return jax.tree.map(lambda x: jnp.take(x, idx, axis=0),
                                    out)
            self._fns[key] = jax.jit(fn)
        return self._fns[key]

    def _cascade_delta_fn(self):  # guarded_by: _lock
        """Compiled divergence signal: per-slot mean |Δdisparity| on the
        low-res grid between consecutive boundaries — the EMA input of
        the cascade promotion trigger (serve/cascade/policy.py).  The
        body is mode-agnostic (disp is fp32 on every tier) but the cache
        key carries both cascade modes, like the join."""
        key = ("cascade", "delta")
        if key not in self._fns:
            self._fns[key] = jax.jit(
                lambda a, b: jnp.mean(jnp.abs(a - b), axis=(1, 2, 3)))
        return self._fns[key]

    def warmup(self, buckets=None, iters_list=None,
               modes: Optional[Sequence[str]] = None) -> List[Tuple]:
        """Compile the configured buckets before serving traffic.

        Covers both iteration levels (normal + degraded) so flipping into
        graceful degradation under load never stalls the queue behind a
        compile — exactly the moment a compile is least affordable —
        every requested precision mode (``modes``; default = the base
        config's mode only) so a warmed accuracy tier never compiles
        under traffic either, and every row count of each, so no batch
        the batcher can form does.  A row count is warmed by a batch of
        that many zero pairs; staging is host work (``_stage_pairs``) and
        has no program of its own to warm.  Returns the (h, w, iters,
        "batch", "rN", input_mode, mode) keys warmed.
        """
        buckets = list(buckets or self.cfg.buckets)
        # sorted, not set-ordered: the default {iters, degraded_iters} set
        # iterates in hash order, which made compile order and warmup logs
        # vary run to run.
        iters_list = sorted(iters_list
                            or {self.cfg.iters, self.cfg.degraded_iters})
        modes = list(modes or [self.default_mode])
        warmed = []
        for h, w in buckets:
            bh, bw = self.bucket_of((h, w, self.input_channels))
            zero = np.zeros((h, w, self.input_channels), np.float32)
            for iters in iters_list:
                for mode in modes:
                    for rows in self.row_counts:
                        # is_warm, not a bare `in self._compiled`:
                        # membership is guarded by _stats_lock (RSA301).
                        if self.is_warm((bh, bw), iters, mode, rows=rows):
                            continue
                        t0 = time.perf_counter()
                        self.infer_batch([(zero, zero)] * rows, iters,
                                         mode=mode)
                        logger.info("warmup: bucket %dx%d iters=%d rows=%d "
                                    "mode=%s compiled in %.1fs", bh, bw,
                                    iters, rows, mode,
                                    time.perf_counter() - t0)
                        warmed.append(self._batch_key((bh, bw), iters, rows,
                                                      mode))
        return warmed

    def warmup_stream(self, buckets=None, ladder: Sequence[int] = (),
                      modes: Optional[Sequence[str]] = None) -> List[Tuple]:
        """Compile the warm-start executables for every (bucket, ladder
        level, mode) before serving streams, so the adaptive controller
        can move between levels mid-stream without ever stalling a session
        behind an XLA compile.  Returns the (h, w, iters, "stream",
        input_mode, mode) keys warmed."""
        buckets = list(buckets or self.cfg.buckets)
        modes = list(modes or [self.default_mode])
        warmed = []
        for h, w in buckets:
            bh, bw = self.bucket_of((h, w, self.input_channels))
            # sorted for reproducible compile order/logs, same policy as
            # ``warmup`` (the ladder is descending by construction).
            for iters in sorted(ladder):
                for mode in modes:
                    key = (bh, bw, iters, "stream", self.input_mode, mode)
                    if self.is_stream_warm((bh, bw), iters, mode):
                        continue
                    zero = np.zeros((h, w, self.input_channels), np.float32)
                    t0 = time.perf_counter()
                    self.infer_stream_batch([(zero, zero)], iters, [None],
                                            mode=mode)
                    logger.info("stream warmup: bucket %dx%d iters=%d "
                                "mode=%s compiled in %.1fs", bh, bw, iters,
                                mode, time.perf_counter() - t0)
                    warmed.append(key)
        return warmed

    @property
    def last_segments(self) -> Optional[Dict[str, object]]:
        """Phase timing of the last dispatch this thread ran to its end
        (``infer_batch`` and the synchronous paths; a dispatch the
        batcher launches and finishes on two threads carries its own,
        ``_Launched.segments``): ``{"pad", "stage_copy", "h2d_put",
        "launch", "device_queued", "device_wait", "dispatch",
        "host_fetch"}`` as (perf_counter t0, t1) windows (``stage_copy``
        and ``h2d_put`` tile ``pad``; a plain dispatch's) plus
        ``"pad_px"``, ``"compile"`` and ``"thread_ms"`` (per timed phase,
        ``timed_phase.thread_ms``) — the raw material the batcher and
        stream runner turn into trace spans (obs/trace.py).
        ``dispatch`` (the ``device_compute`` span) is
        ``launch`` (the jitted call until it returns) followed by
        ``device_wait`` (``block_until_ready``); behind another dispatch
        it starts where ``device_queued`` ends (``_finish``).  ``launch``,
        ``device_wait`` and ``host_fetch`` are read inside a
        ``timed_phase`` of the same name, so a profile capture shows the
        same phases as host events."""
        return getattr(self._seg, "last", None)

    def _pad_pairs(self, pairs, rows: int):
        """Shared shape policy: per-pair BucketPadder padding, staged as a
        batch of ``rows`` — the dispatch's compiled batch shape: exactly
        ``len(pairs)`` on the plain path (no zero row), ``max_batch_size``
        on the warm-start one (zero rows behind the real ones) — so the
        compile cache is keyed by bucket and row count alone.  All pairs
        must map to one bucket (the batcher groups by bucket before
        dispatching)."""
        assert pairs, "empty batch"
        # what the dispatch was asked for and what it computes: the real
        # pairs' pixels and the staged batch's (equal on the plain path
        # but for the bucket's own border)
        bh, bw = self.bucket_of(pairs[0][0].shape)
        px = {"rows": rows,
              "real_px": sum(p[0].shape[0] * p[0].shape[1] for p in pairs),
              "bucket_px": bh * bw * rows}
        with timed_phase("pad_bucket", batch_size=len(pairs), **px) as ph:
            staged = self._stage_pairs(pairs, rows)
        self._seg.pad = ph.window
        self._seg.pad_px = px
        # the finished phases, for the next _launch on this thread
        self._seg.staged = (ph, *self._seg.staged)
        return staged

    def _stage_pairs(self, pairs, rows: int):
        assert len(pairs) <= rows <= self.cfg.max_batch_size, (
            f"batch {len(pairs)} does not fit {rows} rows of "
            f"max_batch_size {self.cfg.max_batch_size}")
        # Staged on the HOST, row by row into one array a side, then one
        # transfer each: no device program.  The eager form (an expand,
        # two pads and a concatenate per row: ~50 tiny programs for eight
        # rows) blocked in the runtime's bound on enqueued programs
        # whenever a dispatch was running, so nothing could be staged
        # behind it (PERF.md §6, PR 35).  Fresh arrays every time: the
        # transfer may read them after this returns.
        # Two phases that tile pad_bucket, meeting on one clock read:
        # stage_copy (the host copies) and h2d_put (the transfers).
        with timed_phase("stage_copy", rows=rows) as ph_copy:
            padders = [self._padder(p[0].shape) for p in pairs]
            hw = padders[0].bucket_hw
            assert all(p.bucket_hw == hw for p in padders), (
                "mixed buckets in one batch: "
                f"{sorted({p.bucket_hw for p in padders})}")
            pad_rows = rows - len(pairs)
            shape = (rows, *hw, pairs[0][0].shape[2])
            # zeros only on the warm-start path (a plain dispatch is
            # staged at its own length): the rows nobody sent
            left, right = ((np.zeros if pad_rows else np.empty)(
                shape, np.float32) for _ in range(2))
            for i, ((im1, im2), padder) in enumerate(zip(pairs, padders)):
                padder.pad_into(left[i], im1)
                padder.pad_into(right[i], im2)
        # Under _device_ctx: a pinned replica's inputs must land on ITS
        # device — put on the global default they would pay a
        # device-to-device copy per dispatch.
        with timed_phase("h2d_put", ph_copy.t1, rows=rows) as ph_put:
            with self._device_ctx():
                i1, i2 = jnp.asarray(left), jnp.asarray(right)
        self._seg.staged = (ph_copy, ph_put)
        return padders, hw, i1, i2, pad_rows

    def _launch(self, key, call, padders=()) -> _Launched:
        """First half of a dispatch: compile-cache bookkeeping and the
        asynchronous call of the jitted function, under the engine lock —
        the device runs what it is handed in the order of the launches.
        Returns at once (a first call compiles, synchronously); the
        result is not waited for.  The pad window is this thread's
        (``_pad_pairs`` ran on it just before)."""
        # mode = the key's kind (always at position 3); tier = its
        # precision-mode component (always last): a compile under traffic
        # must be attributable to the tier whose warmup missed it.
        labels = dict(bucket=f"{key[0]}x{key[1]}", iters=str(key[2]),
                      mode=key[3], tier=key[-1])
        if self.fault_plan is not None:
            # slow_replica chaos: sleep BEFORE taking the engine lock so
            # the injected latency models a slow device, not a convoy —
            # concurrent stream dispatches on other engines proceed.
            delay = self.fault_plan.dispatch_delay()
            if delay > 0.0:
                time.sleep(delay)
        with self._lock:
            with self._stats_lock:
                miss = key not in self._compiled
            if self.metrics is not None:
                (self.metrics.compile_misses if miss
                 else self.metrics.compile_hits).labels(**labels).inc()
            # launch: the asynchronous call of the jitted function until
            # it returns — a slow launch is host time, not device time.
            with timed_phase("launch", bucket=labels["bucket"],
                             iters=key[2]) as ph_launch:
                with self._device_ctx():
                    out_dev = call()
            self.last_included_compile = miss
            with self._stats_lock:  # the call has compiled what it missed
                self._compiled.add(key)
            # the staging phases belong to this launch alone
            staged, self._seg.staged = getattr(self._seg, "staged", ()), ()
            launched = _Launched(
                key, labels, miss, out_dev, ph_launch.window,
                getattr(self._seg, "pad", None),
                getattr(self._seg, "pad_px", None),
                stage={ph.name: ph.window for ph in staged[1:]},
                thread_ms={ph.name: ph.thread_ms
                           for ph in (*staged, ph_launch)},
                behind=self._last_launched, padders=padders)
            self._last_launched = launched
        return launched

    def _finish(self, launched: _Launched):
        """Second half: wait until the result exists on the device
        (``device_wait``), copy every output to the host (``host_fetch``;
        fetch = completion), record timing and metrics.  Returns
        ``(host_outputs, included_compile)`` — the flag is per-call, not
        read back from shared engine state, so concurrent callers cannot
        race each other's compile accounting — and leaves every window
        of the dispatch in ``launched.segments``.

        ``device_queued`` is the time the dispatch lay behind the one
        launched before it: from ``launch``'s end to that one's
        ``device_wait`` end, zero-length when the device was free.  A
        dispatch that was queued counts its ``device_wait`` and its
        ``dispatch`` window (the ``device_compute`` span) from where
        ``device_queued`` ends, so both go on reading the device's time
        for ONE dispatch; one that was not counts ``dispatch`` from
        ``launch``'s start, as ever."""
        ph_wait = timed_phase("device_wait")
        try:
            with ph_wait:
                jax.block_until_ready(launched.out_dev)
            with timed_phase("host_fetch") as ph_fetch:
                out = [np.asarray(o, np.float32) for o in launched.out_dev]
        finally:
            # also where the wait raised: the device is free again, and
            # neither the chain nor the result outlives the dispatch
            launched.ready_at = t_compute = ph_wait.t1
            behind, launched.behind = launched.behind, None
            launched.out_dev = None
        t_launch0, t_launch1 = launched.launch
        # behind.ready_at is None where a later launch was finished first
        # (concurrent direct callers): the wait was then the other's.
        t_free = max(t_launch1, (behind.ready_at or 0.0) if behind else 0.0)
        queued = t_free > t_launch1
        start = t_free if queued else t_launch0
        t_fetch = ph_fetch.t1
        if launched.miss and self.tracer is not None:
            # the bucket's own compile span, beside the per-program
            # ones of the jax.monitoring listener (trace ``xla``)
            self.tracer.record(
                "compile", t_launch0, t_compute, "xla",
                attrs={"kind": "bucket", **launched.labels,
                       **self._program_facts(launched.key)})
        # a queued dispatch's device_wait window starts later than the
        # phase did, and so carries no thread times
        thread_ms = {**launched.thread_ms, "host_fetch": ph_fetch.thread_ms}
        if not queued:
            thread_ms["device_wait"] = ph_wait.thread_ms
        launched.segments = {
            "pad": launched.pad,
            **launched.stage,
            "pad_px": launched.pad_px,
            "launch": launched.launch,
            "device_queued": (t_launch1, t_free),
            "device_wait": (t_free if queued else ph_wait.t0, t_compute),
            "dispatch": (start, t_compute),
            "host_fetch": (t_compute, t_fetch),
            "compile": launched.miss,
            "thread_ms": thread_ms,
        }
        if self.metrics is not None and not launched.miss:
            self.metrics.batch_latency.observe(t_fetch - start)
        return out, launched.miss

    def _dispatch(self, key, call):
        """Lock-serialized synchronous dispatch: both halves under the
        engine lock (re-entrant), so nothing is launched between this
        call and its result — fetch-before-release is the completion
        contract of the warm-start, ``--sched``, cascade and spatial
        paths.  Returns ``(host_outputs, included_compile)`` and leaves
        the windows in this thread's ``last_segments``."""
        with self._lock:
            launched = self._launch(key, call)
            out = self._finish(launched)
        self._seg.last = launched.segments
        return out

    def infer_batch(self, pairs: Sequence[Tuple[np.ndarray, np.ndarray]],
                    iters: int, mode: Optional[str] = None
                    ) -> List[np.ndarray]:
        """Run ``1 <= n <= max_batch_size`` pairs of one bucket; returns
        one (H, W) disparity per pair.  A compiled row count
        (``row_counts``) is one dispatch of exactly those rows — all the
        batcher ever hands over, so one batch stays one dispatch; any
        other ``n`` (direct callers) runs as the fewest dispatches of
        compiled counts, in order (3 -> 1 + 1 + 1), and no reply rides
        beside a zero row.
        ``mode`` is the resolved precision mode (None = the default
        path); the micro-batcher groups by it, so a batch is always
        single-mode."""
        assert 1 <= len(pairs) <= self.cfg.max_batch_size, (
            f"batch {len(pairs)} outside 1..max_batch_size "
            f"{self.cfg.max_batch_size}")
        split = split_rows(len(pairs), self.row_counts)
        if len(split) > 1:  # one dispatch checks its own (_stage_pairs)
            buckets = {self.bucket_of(p[0].shape) for p in pairs}
            assert len(buckets) == 1, (
                f"mixed buckets in one batch: {sorted(buckets)}")
        out, start = [], 0
        for rows in split:
            launched = self.launch_batch(pairs[start:start + rows], iters,
                                         mode)
            out += self.finish_batch(launched)
            # direct callers read the windows where they always did
            self._seg.last = launched.segments
            start += rows
        return out

    def launch_batch(self, pairs, iters: int,
                     mode: Optional[str] = None) -> _Launched:
        """First half of one plain dispatch (``len(pairs)`` is a compiled
        row count): stage the rows and launch the program, without
        waiting for it.  A caller that launches the next batch before it
        finishes this one has that batch queued on the device behind this
        one, staged and ready to start the moment this one ends
        (serve/batcher.py keeps at most two in flight)."""
        rows = len(pairs)
        assert rows in self.row_counts, (
            f"{rows} rows is not a compiled row count {self.row_counts}")
        padders, hw, i1, i2, _ = self._pad_pairs(pairs, rows)
        m = self._mode(mode)
        return self._launch(
            self._batch_key(hw, iters, rows, m),
            lambda: [self._fn(iters, m)(self.variables, i1, i2)[1]],
            padders)

    def finish_batch(self, launched: _Launched) -> List[np.ndarray]:
        """Second half: wait, fetch, count, un-pad — one (H, W) disparity
        per pair of the launch, in its order.  Finish in launch order:
        the device runs in that order, and ``device_queued`` is read
        from the dispatch before."""
        (flow_up,), _ = self._finish(launched)
        if self.metrics is not None:
            self.metrics.batch_rows.labels(
                rows=str(len(launched.padders))).inc()
        return [padder.unpad(flow_up[i:i + 1])[0, ..., 0]
                for i, padder in enumerate(launched.padders)]

    def infer_stream_batch(self, pairs: Sequence[Tuple[np.ndarray,
                                                       np.ndarray]],
                           iters: int,
                           flow_inits: Sequence[Optional[np.ndarray]],
                           mode: Optional[str] = None
                           ) -> List[Tuple[np.ndarray, np.ndarray, bool]]:
        """Warm-start batch: per pair an optional low-res ``flow_init``
        ((H/f, W/f) at the padded bucket shape; None = cold, zeros are
        substituted so the batch always runs the same executable).

        Returns one ``(disparity, disp_low, included_compile)`` per pair:
        the unpadded full-resolution (H, W) disparity, the PADDED 1/factor
        field — the session state a stream forward-warps into the next
        frame's ``flow_init`` (kept padded so it is already at the shape
        the next dispatch needs) — and whether this call paid the XLA
        compile.  Same bucket policy as ``infer_batch``; the batch axis
        is always zero-padded to ``max_batch_size`` (one program a ladder
        level, not one a row count: no record says what stream batches
        look like, so this path is kept apart from the plain one's
        rule).
        """
        assert len(pairs) == len(flow_inits), (len(pairs), len(flow_inits))
        padders, hw, i1, i2, pad_rows = self._pad_pairs(
            pairs, self.cfg.max_batch_size)
        lh, lw = self.low_hw(hw)
        inits = []
        with self._device_ctx():  # stage on this replica's device
            for init in flow_inits:
                if init is None:
                    init = np.zeros((lh, lw), np.float32)
                init = np.asarray(init, np.float32)
                assert init.shape == (lh, lw), (
                    f"flow_init {init.shape} != low-res bucket shape "
                    f"{(lh, lw)} (bucket {hw}, factor "
                    f"{self.model.config.factor})")
                inits.append(jnp.asarray(init)[None, :, :, None])
            fi = jnp.concatenate(inits, axis=0)
            if pad_rows:
                fi = jnp.pad(fi, ((0, pad_rows), (0, 0), (0, 0), (0, 0)))
        m = self._mode(mode)
        key = (hw[0], hw[1], iters, "stream", self.input_mode, m)
        (low, up), miss = self._dispatch(
            key, lambda: self._stream_fn(iters, m)(self.variables, i1, i2,
                                                   fi))
        # .copy(): the low-res slice becomes long-lived session state; a
        # view would pin the whole (max_batch_size, ...) batch array in the
        # session store for its TTL.
        return [(padder.unpad(up[i:i + 1])[0, ..., 0],
                 low[i, :, :, 0].copy(), miss)
                for i, padder in enumerate(padders)]

    def infer_spatial(self, left: np.ndarray, right: np.ndarray,
                      iters: int, flow_init: Optional[np.ndarray] = None,
                      mode: Optional[str] = None,
                      shards: Optional[int] = None
                      ) -> Tuple[np.ndarray, np.ndarray, bool]:
        """ONE pair with image height sharded across the spatial mesh
        (parallel/spatial.py) — no batch axis: the request owns every
        chip of the (1, N) mesh for the duration of the dispatch.

        ``flow_init`` follows ``infer_stream_batch``: an optional
        (H/f, W/f) warm-start at the padded spatial bucket shape, None =
        cold (zeros — same executable).  Returns ``(disparity, disp_low,
        included_compile)``: the unpadded (H, W) disparity, the PADDED
        1/factor field (next-frame warm-start state), and whether this
        call paid the XLA compile.  The cache key carries the shard
        count: a 2-shard and a 4-shard program at the same bucket are
        different executables."""
        n = self._spatial_shard_count(shards)
        t_pad0 = time.perf_counter()
        padder = self._spatial_padder(left.shape)
        hw = padder.bucket_hw
        lh, lw = self.low_hw(hw)
        i1, i2 = padder.pad(jnp.asarray(left, jnp.float32)[None],
                            jnp.asarray(right, jnp.float32)[None])
        if flow_init is None:
            fi = jnp.zeros((1, lh, lw, 1), jnp.float32)
        else:
            flow_init = np.asarray(flow_init, np.float32)
            assert flow_init.shape == (lh, lw), (
                f"flow_init {flow_init.shape} != low-res spatial bucket "
                f"shape {(lh, lw)} (bucket {hw})")
            fi = jnp.asarray(flow_init)[None, :, :, None]
        self._seg.pad = (t_pad0, time.perf_counter())
        m = self._mode(mode)
        key = (hw[0], hw[1], iters, "spatial", f"s{n}", self.input_mode, m)
        (low, up), miss = self._dispatch(
            key, lambda: self._spatial_fn(iters, m)(self.variables, i1, i2,
                                                    fi))
        # .copy() for the same session-state-lifetime reason as
        # infer_stream_batch (here it only drops the channel axis' view).
        return (padder.unpad(up)[0, ..., 0], low[0, :, :, 0].copy(), miss)

    def warmup_spatial(self, buckets=None, iters_list=None,
                       modes: Optional[Sequence[str]] = None) -> List[Tuple]:
        """Compile the spatial executables for every configured spatial
        bucket before serving, so a 4K request never pays the (largest
        possible) XLA compile under traffic.  Returns the (h, w, iters,
        "spatial", "sN", input_mode, mode) keys warmed."""
        n = self._spatial_shard_count(None)
        buckets = list(buckets if buckets is not None
                       else getattr(self.cfg, "spatial_buckets", ()) or ())
        iters_list = sorted(iters_list or {self.cfg.iters})
        modes = list(modes or [self.default_mode])
        warmed = []
        for h, w in buckets:
            bh, bw = self.spatial_bucket_of((h, w, self.input_channels))
            for iters in iters_list:
                for mode in modes:
                    key = (bh, bw, iters, "spatial", f"s{n}",
                           self.input_mode, mode)
                    if self.is_spatial_warm((bh, bw), iters, mode):
                        continue
                    zero = np.zeros((h, w, self.input_channels), np.float32)
                    t0 = time.perf_counter()
                    self.infer_spatial(zero, zero, iters, mode=mode)
                    logger.info("spatial warmup: bucket %dx%d iters=%d "
                                "mode=%s shards=%d compiled in %.1fs", bh,
                                bw, iters, mode, n,
                                time.perf_counter() - t0)
                    warmed.append(key)
        return warmed

    # ------------------------------------------- iteration-level scheduling
    #
    # The phase executables behind serve/sched/ (docs/serving.md): the
    # split forward runs as prologue -> step x N -> epilogue, with the
    # carried state device-resident between boundaries.  All four phases
    # live in the same compile cache under keys
    # (h, w, iters_per_step, phase, input_mode, mode) —
    # iters_per_step is 0 for the phases it cannot affect — so /healthz,
    # the RSA401 checker and the warmup accounting see them like every
    # other executable.

    def _sched_keys(self, hw: Tuple[int, int], iters_per_step: int,
                    mode: Optional[str] = None) -> List[Tuple]:
        im = self.input_mode
        m = self._mode(mode)
        return [(hw[0], hw[1], 0, "sched_prologue", im, m),
                (hw[0], hw[1], iters_per_step, "sched_step", im, m),
                (hw[0], hw[1], 0, "sched_epilogue", im, m),
                (hw[0], hw[1], 0, "sched_join", im, m)]

    def is_sched_warm(self, hw: Tuple[int, int], iters_per_step: int,
                      mode: Optional[str] = None) -> bool:
        """Whether all four phase executables are compiled for (bucket,
        iters_per_step, mode)."""
        with self._stats_lock:
            return all(k in self._compiled
                       for k in self._sched_keys(hw, iters_per_step, mode))

    def _dispatch_state(self, key, call):
        """``_dispatch`` minus the host fetch: the scheduler's carried
        state stays on device between iteration boundaries, so completion
        here means block_until_ready, not a host copy.  Same lock
        serialization and compile-cache bookkeeping."""
        labels = dict(bucket=f"{key[0]}x{key[1]}", iters=str(key[2]),
                      mode=key[3], tier=key[-1])
        with self._lock:
            with self._stats_lock:
                miss = key not in self._compiled
            if self.metrics is not None:
                (self.metrics.compile_misses if miss
                 else self.metrics.compile_hits).labels(**labels).inc()
            start = time.perf_counter()
            with self._device_ctx():
                out = call()
            jax.block_until_ready(out)
            t_done = time.perf_counter()
            self.last_included_compile = miss
            with self._stats_lock:
                self._compiled.add(key)
        # Consume the pad window: only the prologue has one, and leaving
        # it set would stamp the stale window onto this thread's later
        # step/join/epilogue segments.
        pad = getattr(self._seg, "pad", None)
        self._seg.pad = None
        self._seg.last = {
            "pad": pad,
            "dispatch": (start, t_done),
            "host_fetch": (t_done, t_done),
            "compile": miss,
        }
        return out, miss

    def _sched_assemble(self, pairs, flow_inits, slots):
        """Shared join-group input assembly for the sched AND cascade
        prologues: each joining pair placed at its assigned batch slot
        (remaining slots are zero images — dead weight, exactly like
        batch padding rows).  Host-side assembly, ONE transfer at
        dispatch: out-of-jit ``.at[slot].set`` would copy the whole
        (B, H, W, 3) batch buffer once per joiner (same rationale as
        _pad_pairs).  Returns ``(hw, i1, i2, fi)`` and stamps the pad
        timing window."""
        assert len(pairs) == len(flow_inits) == len(slots), (
            len(pairs), len(flow_inits), len(slots))
        assert pairs, "empty join group"
        bsz = self.cfg.max_batch_size
        assert len(set(slots)) == len(slots) and all(
            0 <= s < bsz for s in slots), f"bad slots {slots}"
        t_pad0 = time.perf_counter()
        padders = [self._padder(p[0].shape) for p in pairs]
        hw = padders[0].bucket_hw
        assert all(p.bucket_hw == hw for p in padders), (
            "mixed buckets in one join group: "
            f"{sorted({p.bucket_hw for p in padders})}")
        lh, lw = self.low_hw(hw)
        i1 = np.zeros((bsz, hw[0], hw[1], self.input_channels), np.float32)
        i2 = np.zeros((bsz, hw[0], hw[1], self.input_channels), np.float32)
        fi = np.zeros((bsz, lh, lw, 1), np.float32)
        for (im1, im2), padder, init, slot in zip(pairs, padders,
                                                  flow_inits, slots):
            with self._device_ctx():  # tiny pad ops on our own device
                p1, p2 = padder.pad(jnp.asarray(im1, jnp.float32)[None],
                                    jnp.asarray(im2, jnp.float32)[None])
            i1[slot] = np.asarray(p1[0], np.float32)
            i2[slot] = np.asarray(p2[0], np.float32)
            if init is not None:
                init = np.asarray(init, np.float32)
                assert init.shape == (lh, lw), (
                    f"flow_init {init.shape} != low-res bucket shape "
                    f"{(lh, lw)} (bucket {hw})")
                fi[slot, :, :, 0] = init
        self._seg.pad = (t_pad0, time.perf_counter())
        return hw, i1, i2, fi

    def infer_sched_prologue(self, pairs: Sequence[Tuple[np.ndarray,
                                                         np.ndarray]],
                             flow_inits: Sequence[Optional[np.ndarray]],
                             slots: Sequence[int],
                             mode: Optional[str] = None):
        """Run the prologue for joining requests, each placed at its
        assigned batch slot.

        ``flow_inits`` follows ``infer_stream_batch``: an optional padded
        low-res warm-start per pair, None = cold (zeros).  Returns
        ``(hw, state, included_compile)`` with ``state`` device-resident.
        """
        hw, i1, i2, fi = self._sched_assemble(pairs, flow_inits, slots)
        m = self._mode(mode)
        key = (hw[0], hw[1], 0, "sched_prologue", self.input_mode, m)
        state, miss = self._dispatch_state(
            key, lambda: self._sched_prologue_fn(m)(self.variables, i1, i2,
                                                    fi))
        return hw, state, miss

    def infer_sched_step(self, hw: Tuple[int, int], state,
                         iters_per_step: int, mode: Optional[str] = None):
        """Advance the running batch by one boundary (``iters_per_step``
        GRU iterations); returns ``(state, included_compile)``."""
        m = self._mode(mode)
        key = (hw[0], hw[1], iters_per_step, "sched_step",
               self.input_mode, m)
        return self._dispatch_state(
            key, lambda: self._sched_step_fn(iters_per_step, m)(
                self.variables, state))

    def infer_sched_join(self, hw: Tuple[int, int], running, incoming,
                         mask: np.ndarray, mode: Optional[str] = None):
        """Merge ``incoming`` into ``running`` where ``mask`` (B,) is
        True; returns ``(state, included_compile)``.  The join body is
        mode-agnostic (a dtype-polymorphic tree select) but the key
        carries the mode: each tier's state pytree compiles its own
        program, and the warmup accounting must see that."""
        with self._device_ctx():  # the mask joins device-resident state
            mk = jnp.asarray(mask, bool)
        assert mk.shape == (self.cfg.max_batch_size,), mk.shape
        m = self._mode(mode)
        key = (hw[0], hw[1], 0, "sched_join", self.input_mode, m)
        return self._dispatch_state(
            key, lambda: self._sched_join_fn()(running, incoming, mk))

    def infer_sched_epilogue(self, hw: Tuple[int, int], state,
                             mode: Optional[str] = None):
        """Final mask + upsample for the whole batch, fetched to host:
        ``(disp_low (B, H/f, W/f, 1), disp_up (B, H, W, 1),
        included_compile)`` — the scheduler unpads per leaving slot
        (``padder_of``)."""
        m = self._mode(mode)
        key = (hw[0], hw[1], 0, "sched_epilogue", self.input_mode, m)
        (low, up), miss = self._dispatch_state(
            key, lambda: self._sched_epilogue_fn(m)(self.variables, state))
        return (np.asarray(low, np.float32), np.asarray(up, np.float32),
                miss)

    def warmup_sched(self, buckets=None, iters_per_step: int = 1,
                     modes: Optional[Sequence[str]] = None) -> List[Tuple]:
        """Compile all four phase executables for every configured bucket
        (and every requested precision mode) before scheduled traffic, so
        joins/steps/leaves never stall a running batch behind an XLA
        compile.  Sorted like ``warmup`` for reproducible compile order.
        Returns the keys warmed."""
        buckets = list(buckets or self.cfg.buckets)
        modes = list(modes or [self.default_mode])
        bsz = self.cfg.max_batch_size
        warmed = []
        for h, w in buckets:
            bh, bw = self.bucket_of((h, w, self.input_channels))
            for mode in modes:
                if self.is_sched_warm((bh, bw), iters_per_step, mode):
                    continue
                zero = np.zeros((h, w, self.input_channels), np.float32)
                t0 = time.perf_counter()
                hw, state, _ = self.infer_sched_prologue(
                    [(zero, zero)], [None], [0], mode=mode)
                state, _ = self.infer_sched_step(hw, state, iters_per_step,
                                                 mode=mode)
                state, _ = self.infer_sched_join(hw, state, state,
                                                 np.zeros(bsz, bool),
                                                 mode=mode)
                self.infer_sched_epilogue(hw, state, mode=mode)
                logger.info("sched warmup: bucket %dx%d iters_per_step=%d "
                            "mode=%s compiled in %.1fs", bh, bw,
                            iters_per_step, mode,
                            time.perf_counter() - t0)
                warmed.extend(self._sched_keys((bh, bw), iters_per_step,
                                               mode))
        return warmed

    # ------------------------------------------------- speculative cascades
    #
    # The cross-tier handoff executables behind serve/cascade/
    # (docs/serving.md "Tier cascade"): a cascade slot drafts on a cheap
    # tier's step executable and hands its carried state to the certified
    # tier's for the last K iterations.  Four cascade-specific phases —
    # dual prologue (cheap state + staged certified state), stage join,
    # handoff (cast + corr swap + lane gather) and the divergence delta —
    # under keys (h, w, 0, phase, input_mode, cheap_mode, cert_mode):
    # every cascade executable is keyed by BOTH
    # precision modes (ints at 0-2, strings from 3 on, so the mixed-arity
    # key set stays sortable for /healthz).  The cheap/certified step and
    # epilogue executables are the UNMODIFIED per-mode sched phases — a
    # cascade adds no new math to either tier's iteration loop, which is
    # what keeps the single-tier paths bitwise-unchanged.

    def _cascade_pair(self, cheap_mode: Optional[str],
                      cert_mode: Optional[str]) -> Tuple[str, str]:
        cm, xm = self._mode(cheap_mode), self._mode(cert_mode)
        assert cm != xm, (
            f"cascade needs two distinct precision modes, got {cm!r} "
            "for both legs")
        return cm, xm

    def _cascade_keys(self, hw: Tuple[int, int],
                      cheap_mode: Optional[str] = None,
                      cert_mode: Optional[str] = None) -> List[Tuple]:
        im = self.input_mode
        cm, xm = self._cascade_pair(cheap_mode, cert_mode)
        return [(hw[0], hw[1], 0, "cascade_prologue", im, cm, xm),
                (hw[0], hw[1], 0, "cascade_stage_join", im, cm, xm),
                (hw[0], hw[1], 0, "cascade_handoff", im, cm, xm),
                (hw[0], hw[1], 0, "cascade_delta", im, cm, xm)]

    def is_cascade_warm(self, hw: Tuple[int, int], iters_per_step: int,
                        cheap_mode: Optional[str] = None,
                        cert_mode: Optional[str] = None) -> bool:
        """Whether a (bucket, cheap_mode -> cert_mode) cascade is fully
        compiled: the four cascade phases AND both tiers' sched phase
        executables (the cascade rides them for its steps/epilogue)."""
        keys = self._cascade_keys(hw, cheap_mode, cert_mode)
        with self._stats_lock:
            warm = all(k in self._compiled for k in keys)
        return (warm
                and self.is_sched_warm(hw, iters_per_step, cheap_mode)
                and self.is_sched_warm(hw, iters_per_step, cert_mode))

    def infer_cascade_prologue(self, pairs: Sequence[Tuple[np.ndarray,
                                                           np.ndarray]],
                               flow_inits: Sequence[Optional[np.ndarray]],
                               slots: Sequence[int],
                               cheap_mode: Optional[str] = None,
                               cert_mode: Optional[str] = None):
        """Run BOTH tiers' prologues for joining cascade requests in one
        dispatch; returns ``(hw, state, stage, included_compile)`` —
        ``state`` is the cheap tier's carried state (EXACTLY what
        ``infer_sched_prologue(mode=cheap_mode)`` returns, so the slot
        joins the cheap tier's running batch indistinguishably) and
        ``stage`` is the certified tier's staged state, device-resident
        until the handoff swaps its corr in."""
        hw, i1, i2, fi = self._sched_assemble(pairs, flow_inits, slots)
        cm, xm = self._cascade_pair(cheap_mode, cert_mode)
        key = (hw[0], hw[1], 0, "cascade_prologue", self.input_mode, cm, xm)
        (state, stage), miss = self._dispatch_state(
            key, lambda: self._cascade_prologue_fn(cm, xm)(
                self.variables, i1, i2, fi))
        return hw, state, stage, miss

    def infer_cascade_stage_join(self, hw: Tuple[int, int], running,
                                 incoming, mask: np.ndarray,
                                 cheap_mode: Optional[str] = None,
                                 cert_mode: Optional[str] = None):
        """Merge newly staged certified state into the running batch's
        stage where ``mask`` (B,) is True — the side-car twin of
        ``infer_sched_join`` (same tree-select body, cascade-keyed);
        returns ``(stage, included_compile)``."""
        with self._device_ctx():
            mk = jnp.asarray(mask, bool)
        assert mk.shape == (self.cfg.max_batch_size,), mk.shape
        cm, xm = self._cascade_pair(cheap_mode, cert_mode)
        key = (hw[0], hw[1], 0, "cascade_stage_join", self.input_mode, cm, xm)
        return self._dispatch_state(
            key, lambda: self._sched_join_fn()(running, incoming, mk))

    def infer_cascade_handoff(self, hw: Tuple[int, int], state, stage,
                              slot_map: np.ndarray,
                              cheap_mode: Optional[str] = None,
                              cert_mode: Optional[str] = None):
        """The tier handoff: assemble the certified-format carried state
        (tier-independent leaves cast from the cheap ``state``, corr
        swapped in from ``stage`` — serve/cascade/handoff.py) and gather
        lanes so promoted slots land at their certified-batch slots.

        ``slot_map`` is a (max_batch_size,) int array mapping TARGET
        slot index -> SOURCE slot index (unpromoted target lanes may map
        anywhere — their rows are dead weight the follow-up
        ``infer_sched_join`` mask ignores).  Returns
        ``(state, included_compile)`` with ``state`` device-resident in
        the certified tier's trace signature."""
        slot_map = np.asarray(slot_map, np.int32)
        assert slot_map.shape == (self.cfg.max_batch_size,), slot_map.shape
        with self._device_ctx():
            idx = jnp.asarray(slot_map)
        cm, xm = self._cascade_pair(cheap_mode, cert_mode)
        key = (hw[0], hw[1], 0, "cascade_handoff", self.input_mode, cm, xm)
        return self._dispatch_state(
            key, lambda: self._cascade_handoff_fn(cm, xm)(state, stage,
                                                          idx))

    def infer_cascade_delta(self, hw: Tuple[int, int], prev_disp, disp,
                            cheap_mode: Optional[str] = None,
                            cert_mode: Optional[str] = None):
        """Per-slot mean |Δdisparity| between consecutive boundaries on
        the low-res grid, fetched to host — the divergence trigger's EMA
        input (serve/cascade/policy.py).  Returns ``((B,) float32,
        included_compile)``."""
        cm, xm = self._cascade_pair(cheap_mode, cert_mode)
        key = (hw[0], hw[1], 0, "cascade_delta", self.input_mode, cm, xm)
        (deltas,), miss = self._dispatch(
            key, lambda: [self._cascade_delta_fn()(prev_disp, disp)])
        return deltas, miss

    def warmup_cascade(self, buckets=None, iters_per_step: int = 1,
                       schedules: Sequence[object] = ()) -> List[Tuple]:
        """Compile every cascade executable — including the transition
        pair — for the configured buckets before serving, so a cascade
        request never stalls behind an XLA compile: both tiers' sched
        phases (via ``warmup_sched``), the four cascade phases, AND one
        certified step + epilogue driven from a handed-off state, so any
        signature drift between the handoff output and the certified
        trace retraces HERE, not under traffic (the retrace-budget-0
        e2e in tests/test_cascade.py holds the engine to that).

        ``schedules`` are CascadeSchedule objects or schedule strings;
        distinct (cheap, certified) mode pairs are compiled once.
        Returns the newly warmed keys."""
        from .cascade.schedule import parse_schedule
        buckets = list(buckets or self.cfg.buckets)
        parsed = [s if hasattr(s, "legs") else parse_schedule(s)
                  for s in schedules]
        mode_pairs = sorted({(s.cheap_mode, s.cert_mode) for s in parsed})
        bsz = self.cfg.max_batch_size
        warmed: List[Tuple] = []
        for cheap_mode, cert_mode in mode_pairs:
            # The cascade rides both tiers' step/epilogue executables;
            # warm them first (no-op for already-warm modes).
            warmed.extend(self.warmup_sched(buckets=buckets,
                                            iters_per_step=iters_per_step,
                                            modes=[cheap_mode, cert_mode]))
            for h, w in buckets:
                bh, bw = self.bucket_of((h, w, self.input_channels))
                if self.is_cascade_warm((bh, bw), iters_per_step,
                                        cheap_mode, cert_mode):
                    continue
                zero = np.zeros((h, w, self.input_channels), np.float32)
                t0 = time.perf_counter()
                hw, state, stage, _ = self.infer_cascade_prologue(
                    [(zero, zero)], [None], [0], cheap_mode=cheap_mode,
                    cert_mode=cert_mode)
                stage, _ = self.infer_cascade_stage_join(
                    hw, stage, stage, np.zeros(bsz, bool),
                    cheap_mode=cheap_mode, cert_mode=cert_mode)
                self.infer_cascade_delta(
                    hw, state["disp"], state["disp"],
                    cheap_mode=cheap_mode, cert_mode=cert_mode)
                state, _ = self.infer_cascade_handoff(
                    hw, state, stage, np.zeros(bsz, np.int32),
                    cheap_mode=cheap_mode, cert_mode=cert_mode)
                # The transition pair: certified step + epilogue FROM the
                # handoff output (cache hits when the handoff reproduces
                # the certified trace signature — the design contract).
                state, _ = self.infer_sched_step(hw, state, iters_per_step,
                                                 mode=cert_mode)
                self.infer_sched_epilogue(hw, state, mode=cert_mode)
                logger.info(
                    "cascade warmup: bucket %dx%d %s->%s "
                    "iters_per_step=%d compiled in %.1fs", bh, bw,
                    cheap_mode, cert_mode, iters_per_step,
                    time.perf_counter() - t0)
                warmed.extend(self._cascade_keys((bh, bw), cheap_mode,
                                                 cert_mode))
        return warmed
