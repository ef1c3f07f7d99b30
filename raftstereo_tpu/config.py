"""Single typed configuration shared by all entry points.

The reference duplicates argparse model flags across four scripts
(reference: train_stereo.py:233-241, evaluate_stereo.py:199-207, demo.py:64-72,
test.py:26-34).  Here every entry point consumes one frozen dataclass, which is
also hashable so it can be passed as a static argument through ``jax.jit``.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class RAFTStereoConfig:
    """Architecture hyper-parameters of the RAFT-Stereo model.

    Mirrors the capability surface of the reference flags
    (reference: train_stereo.py:233-241) while staying a single typed object.
    Level index 0 is the finest GRU resolution (1/2^n_downsample); higher
    indices are coarser, matching the reference's ``net_list`` ordering
    (reference: core/raft_stereo.py:84-85).
    """

    # Correlation engine.  Backends: "reg" (precomputed pyramid + XLA gather
    # lookup), "alt" (on-demand, O(H*W) memory), "pallas" (precomputed pyramid +
    # Pallas TPU lookup kernel — the reg_cuda analogue; reference: core/corr.py),
    # "pallas_alt" (on-demand Pallas kernel, O(H*W) memory — working form of
    # the reference's dead alt_cuda backend, core/corr.py:159-188), "auto"
    # (the fastest backend for the active platform: pallas_alt on TPU — also
    # O(H*W) memory — reg elsewhere; resolved at trace time, ops/corr.py).
    corr_implementation: str = "reg"
    corr_levels: int = 4
    corr_radius: int = 4

    # Resolution of the disparity field: 1/2^n_downsample.
    n_downsample: int = 2

    # GRU stack.
    n_gru_layers: int = 3
    hidden_dims: Tuple[int, ...] = (128, 128, 128)  # finest -> coarsest
    slow_fast_gru: bool = False

    # Encoders.
    shared_backbone: bool = False
    context_norm: str = "batch"

    # Precision policy.  "float32" or "bfloat16" compute for encoders + GRUs.
    # The correlation volume dtype is controlled separately because lookup
    # accuracy is precision-sensitive (reference: evaluate_stereo.py:227-230).
    compute_dtype: str = "float32"
    corr_dtype: str = "float32"
    # MXU multiply precision for the fp32 correlation matmuls: "highest"
    # (6-pass bf16 emulation, exact fp32), "high" (3-pass, ~fp32-accurate at
    # half the MXU cost), "default" (single bf16 pass).  Only consulted when
    # the inputs are fp32 — bf16 corr_dtype always takes the native path.
    corr_precision: str = "highest"
    # Int8-quantized correlation volume (ops/quant.py): symmetric per-row
    # int8 quantization of both feature maps, int8 x int8 -> int32
    # all-pairs product, scales folded into the dequant epilogue.  Forces
    # a precomputed-volume lookup backend (the on-demand backends would
    # re-quantize per lookup); the serving "turbo" accuracy tier sets it
    # via ops/quant.config_for_mode.  Inference-only numerics knob —
    # training always runs unquantized.
    corr_quant: bool = False

    # Fused Pallas encoder stem (ops/pallas_encoder.py).  None = auto
    # (enabled on TPU backends, incl. under a partitionable corr mesh via
    # shard_map); True/False force one numeric path — the fused stage's
    # instance-norm stats are fp32 kernel sums, which differ from the XLA
    # stage at stat-precision level (~1e-3 relative on bf16 activations),
    # so evaluations comparing runs across device counts can pin the path.
    fused_encoder: Optional[bool] = None

    # Rematerialize each GRU iteration in the backward pass (jax.checkpoint
    # on the scan body): activation memory drops from O(iters) to O(1) at the
    # cost of one extra forward per iteration.  Required to fit the reference
    # training recipe (batch 8, 320x720, 16+ iters) in one chip's HBM; free
    # for inference (no backward pass to rematerialize for).
    remat: bool = False

    # Input modality (sl/, docs/structured_light.md).  "passive" is the
    # classic 3-channel RGB pair; "sl" stacks the 9 projected-pattern
    # channels from data/sl.py onto each side (ambient 3 + patterns 9 = 12
    # channels per image) and routes both stacks through a learned
    # projection before the shared feature encoders.  The passive path is
    # bitwise-unchanged: no projection module exists, no extra params are
    # created, and the traced program is identical to pre-SL builds.
    # Serving executables are cache-keyed by this field (serve/engine.py),
    # and it joins the certification architecture fingerprint
    # (eval/certify.ARCH_FIELDS).
    input_mode: str = "passive"

    # Spatial sharding (parallel/spatial.py, docs/serving.md "Spatial
    # sharding"): shard one inference's image height across this many
    # chips on the ``space`` axis of a (1, N) mesh under shard_map —
    # single-request multi-chip inference for pairs whose corr pyramid +
    # activations exceed one chip's HBM.  1 = the classic single-chip
    # forward.  A model-level default: ``ServeConfig.spatial_shards``
    # overrides it serverside, and the engine cache-keys every spatial
    # executable by the resolved count.  v1 refuses shared_backbone,
    # group context norm and corr_quant
    # (parallel/spatial.validate_spatial_config).
    spatial_shards: int = 1

    def __post_init__(self):
        if isinstance(self.hidden_dims, list):
            object.__setattr__(self, "hidden_dims", tuple(self.hidden_dims))
        assert self.corr_implementation in (
            "auto", "reg", "alt", "pallas", "pallas_alt"), self.corr_implementation
        assert self.corr_precision in (
            "highest", "high", "default"), self.corr_precision
        assert self.input_mode in ("passive", "sl"), self.input_mode
        assert 1 <= self.n_gru_layers <= 3, self.n_gru_layers
        assert len(self.hidden_dims) >= self.n_gru_layers
        assert self.spatial_shards >= 1, self.spatial_shards

    @property
    def factor(self) -> int:
        """Full-resolution upsampling factor for the disparity field."""
        return 2 ** self.n_downsample

    @property
    def cor_planes(self) -> int:
        """Correlation feature channels fed to the motion encoder."""
        return self.corr_levels * (2 * self.corr_radius + 1)

    @property
    def input_channels(self) -> int:
        """Channels per input image: 3 (passive RGB) or 12 (ambient RGB +
        9 pattern channels, sl/adapter.py)."""
        return 3 if self.input_mode == "passive" else 12


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training-loop hyper-parameters (reference: train_stereo.py:216-248)."""

    name: str = "raft-stereo"
    batch_size: int = 6
    train_datasets: Tuple[str, ...] = ("sceneflow",)
    lr: float = 2e-4
    num_steps: int = 100000
    image_size: Tuple[int, int] = (320, 720)
    train_iters: int = 16
    valid_iters: int = 32
    wdecay: float = 1e-5
    loss_gamma: float = 0.9
    max_flow: float = 700.0
    grad_clip: float = 1.0
    seed: int = 1234
    validation_frequency: int = 10000
    checkpoint_dir: str = "checkpoints"
    restore_ckpt: Optional[str] = None
    keep_checkpoints: int = 5

    # Data augmentation (reference: train_stereo.py:244-248).
    # img_gamma: (GMIN, GMAX) or (GMIN, GMAX, GAIN_MIN, GAIN_MAX).
    img_gamma: Optional[Tuple[float, ...]] = None
    saturation_range: Optional[Tuple[float, float]] = None
    do_flip: Optional[str] = None  # None | "h" | "v"
    spatial_scale: Tuple[float, float] = (0.0, 0.0)
    noyjitter: bool = False
    # Run the photometric chain (jitter + eraser) on-device inside the
    # jitted train step instead of in host workers (data/device_aug.py) —
    # for hosts whose CPUs can't feed the chip.
    device_photometric: bool = False

    # Parallelism: number of data-parallel shards (devices along the "data"
    # mesh axis); None = all visible devices.
    data_parallel: Optional[int] = None

    # Failure handling.  "abort": raise on a non-finite loss/gradient (the
    # reference's assert behaviour, train_stereo.py:49-52); "skip": drop the
    # bad update on-device, keep params/optimizer unchanged, advance the
    # schedule (the GradScaler-skip behaviour of torch AMP).
    nan_policy: str = "abort"
    # Auto-restart-from-checkpoint budget for the train loop (elastic
    # recovery; the reference's only recovery is a manual --restore_ckpt).
    # The budget counts restarts WITHOUT progress: a restart that resumes
    # from a later step than the previous one resets the count, so a long
    # run with occasional transient failures is never killed by an absolute
    # cap, while a crash loop stuck at one step exhausts it quickly.
    max_restarts: int = 0
    # Base of the exponential backoff between restarts (seconds; doubles per
    # consecutive no-progress restart, capped at 60s).
    restart_backoff: float = 1.0

    # Self-healing data pipeline (data/loader.py): per-sample retries with
    # backoff, bounded quarantine of persistently-bad indices (replaced by
    # deterministic resamples, counted in metrics), and a timeout on worker
    # batches after which the pool is recycled (0 disables).
    sample_retries: int = 2
    quarantine_limit: int = 64
    loader_timeout_s: float = 300.0

    # Step watchdog: flag (log + metric) any device step slower than this
    # multiple of the running median step time (0 disables).  Flags only —
    # a hung XLA collective is for the operator/restart policy to kill.
    watchdog_factor: float = 10.0

    def __post_init__(self):
        assert self.nan_policy in ("abort", "skip"), self.nan_policy
        for f in ("train_datasets", "image_size", "spatial_scale"):
            v = getattr(self, f)
            if isinstance(v, list):
                object.__setattr__(self, f, tuple(v))
        if isinstance(self.img_gamma, list):
            object.__setattr__(self, "img_gamma", tuple(self.img_gamma))
        if isinstance(self.saturation_range, list):
            object.__setattr__(self, "saturation_range", tuple(self.saturation_range))


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Temporal warm-start streaming parameters (stream/, docs/streaming.md).

    ``ladder`` is the small fixed set of GRU iteration counts the subsystem
    ever runs — each (bucket, level) pair is one compiled executable, so the
    adaptive controller can move between levels without ever paying an XLA
    compile mid-stream.  ``ladder[0]`` is the cold-start (full) count; warm
    frames use ``ladder[1:]``, picked per frame from an EMA of the update
    magnitude (mean |refined - warm-start init| at 1/factor resolution, in
    pixels).  Frozen + hashable like the other configs."""

    ladder: Tuple[int, ...] = (32, 16, 8)
    # EMA decay of the per-frame update magnitude (higher = smoother).
    ema_decay: float = 0.6
    # Controller thresholds on that EMA, in low-res pixels:
    # above promote -> more iterations next frame; below demote -> fewer;
    # above cold_reset -> the warm start is not tracking the scene (cut,
    # fast motion), next frame re-runs cold at ladder[0].
    promote_threshold: float = 1.0
    demote_threshold: float = 0.25
    cold_reset_threshold: float = 4.0
    # Session store bounds: LRU-evict beyond session_limit, treat sessions
    # idle past session_ttl_s as expired (next frame is cold, never an
    # error).
    session_limit: int = 256
    session_ttl_s: float = 300.0
    # Byte budget for the in-replica session store (docs/streaming.md
    # "Durable sessions"): LRU-evict while the byte-accurate state total
    # (disparity nbytes + controller overhead) exceeds it.  0 keeps the
    # historical count-only bound; the count cap stays as a secondary
    # limit either way.
    session_budget_mb: float = 0.0
    # Snapshot wire compression for exports + write-behind tier pushes:
    # "off" ships raw f32 planes (bitwise); "int8" rides ops/quant.py's
    # per-row symmetric int8 with a per-snapshot exactness manifest and
    # a bitwise f32 fallback when the manifest bound would be violated.
    snapshot_compress: str = "off"
    # Quantization-error bound (low-res px) the int8 manifest must
    # certify; a snapshot whose max |dequant - f32| exceeds it ships raw.
    snapshot_compress_bound: float = 0.05
    # External durable session tier (stream/tier.py, cli.sessiontier):
    # when set, every completed frame's snapshot is pushed write-behind
    # (bounded coalescing queue, never on the request path) so any
    # replica resumes any stream warm.  None = local-pin-only (PR 13).
    tier: Optional[Tuple[str, int]] = None
    # Write-behind robustness: per-call socket timeout, bounded
    # retry/backoff (utils/backoff.py), coalescing-queue bound, and the
    # re-probe cadence while degraded (tier unreachable -> local-pin
    # behavior, never an error).
    tier_timeout_s: float = 2.0
    tier_retries: int = 2
    tier_backoff_ms: float = 50.0
    tier_queue_limit: int = 1024
    tier_reprobe_s: float = 1.0

    def __post_init__(self):
        if isinstance(self.ladder, list):
            object.__setattr__(self, "ladder", tuple(self.ladder))
        if isinstance(self.tier, list):
            object.__setattr__(self, "tier", tuple(self.tier))
        assert self.snapshot_compress in ("off", "int8"), \
            self.snapshot_compress
        assert self.snapshot_compress_bound >= 0, \
            self.snapshot_compress_bound
        assert self.session_budget_mb >= 0, self.session_budget_mb
        assert self.tier_timeout_s > 0, self.tier_timeout_s
        assert self.tier_retries >= 0, self.tier_retries
        assert self.tier_backoff_ms >= 0, self.tier_backoff_ms
        assert self.tier_queue_limit >= 1, self.tier_queue_limit
        assert self.tier_reprobe_s > 0, self.tier_reprobe_s
        assert len(self.ladder) >= 2, (
            f"ladder {self.ladder} needs a cold level and at least one "
            f"warm level")
        assert all(i >= 1 for i in self.ladder), self.ladder
        assert all(a > b for a, b in zip(self.ladder, self.ladder[1:])), (
            f"ladder {self.ladder} must be strictly descending "
            f"(cold/full first)")
        # The design contract the stream subsystem is built around (and the
        # acceptance tests assert): warm frames run at most HALF the cold
        # iteration count.
        assert 2 * self.ladder[1] <= self.ladder[0], (
            f"first warm level {self.ladder[1]} must be <= half the cold "
            f"level {self.ladder[0]}")
        assert 0.0 <= self.ema_decay < 1.0, self.ema_decay
        assert (self.demote_threshold < self.promote_threshold
                < self.cold_reset_threshold), (
            self.demote_threshold, self.promote_threshold,
            self.cold_reset_threshold)
        assert self.session_limit >= 1, self.session_limit
        assert self.session_ttl_s > 0, self.session_ttl_s


@dataclasses.dataclass(frozen=True)
class SchedConfig:
    """Iteration-level continuous batching (serve/sched/, docs/serving.md).

    Replaces whole-request dispatch with iteration-granular scheduling:
    the engine advances one running batch per shape bucket through
    single-iteration step executables, and requests join/leave at
    iteration boundaries — so a 32-iteration request never head-of-line
    blocks a 7-iteration stream frame.  Frozen + hashable like the other
    configs."""

    # GRU iterations per scheduler boundary.  1 gives the finest
    # join/leave granularity (lowest short-job latency); larger values
    # amortize per-boundary dispatch overhead.  Per-request iteration
    # targets must be divisible by it.
    iters_per_step: int = 1
    # Aging interval for the priority queue: a queued request is promoted
    # one priority class for every starvation_ms it has waited, so low
    # priority means "later", never "never".
    starvation_ms: float = 2000.0
    # Upper bound on a request's explicit per-request iteration target.
    # Unlike the monolithic path, ANY value up to this cap is served from
    # the same step executable — no per-iters compile to protect against.
    max_iters: int = 64

    def __post_init__(self):
        assert self.iters_per_step >= 1, self.iters_per_step
        assert self.starvation_ms > 0, self.starvation_ms
        assert self.max_iters >= self.iters_per_step, (
            self.max_iters, self.iters_per_step)
        assert self.max_iters % self.iters_per_step == 0, (
            f"max_iters {self.max_iters} not divisible by iters_per_step "
            f"{self.iters_per_step}")


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    """Replicated multi-chip serving (serve/cluster/, docs/serving.md
    "Cluster").

    When set on a :class:`ServeConfig`, the server runs N independent
    engine replicas — one per device from ``parallel.mesh`` (or N
    thread-backed replicas on the CPU host platform under
    ``--xla_force_host_platform_device_count``) — behind a dispatcher
    that places cold work on the least-loaded ready replica and pins
    session/scheduled work to one replica (warm-start state and running
    batches must stay put).  Frozen + hashable like the other configs."""

    # Engine replicas.  None = one per visible device.
    replicas: Optional[int] = None
    # Bound on the session -> replica pin table (LRU beyond it; a
    # re-routed session degrades to a cold frame, never an error).
    session_pin_limit: int = 4096
    # Consecutive engine failures after which a replica is marked
    # ``failed`` and stops receiving new work (existing futures already
    # carry their error; the dispatcher never retries state-carrying
    # work on another replica).
    fail_threshold: int = 3
    # Warm replicas concurrently (one thread each; every engine owns its
    # compile cache and lock, so warmups never contend).
    warmup_parallel: bool = True
    # Optional fitted capacity model (JSON from ``cli.loadgen fit``,
    # docs/slo_harness.md) + the planned aggregate request rate: with
    # both, the dispatcher's autoscaler advice carries a model-based
    # recommended replica count and the ``cluster_capacity_headroom``
    # gauge reports headroom against target_rps.
    capacity_model: Optional[str] = None
    target_rps: float = 0.0

    def __post_init__(self):
        assert self.replicas is None or self.replicas >= 1, self.replicas
        assert self.session_pin_limit >= 1, self.session_pin_limit
        assert self.fail_threshold >= 1, self.fail_threshold
        assert self.target_rps >= 0, self.target_rps


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """Front-end HTTP router over N backend stereo servers
    (serve/cluster/router.py, ``python -m raftstereo_tpu.cli.router``).

    The router owns no model: it probes each backend's ``/healthz``
    (``live``/``ready``/``draining``), places cold ``/predict`` traffic
    on the least-outstanding ready backend with bounded
    retry-with-backoff failover on backend failure (cold inference is
    idempotent), pins session frames to one backend (warm-start state is
    backend-local), and exports the ``cluster_*`` autoscaling metric
    families."""

    host: str = "127.0.0.1"
    port: int = 8081  # 0 = ephemeral (tests bind a free port)
    # (host, port) of each backend stereo server.
    backends: Tuple[Tuple[str, int], ...] = ()
    # Health probing: poll each backend's /healthz on this cadence; a
    # backend is unroutable after fail_after consecutive probe failures
    # (an in-flight connection error marks it unroutable immediately).
    probe_interval_s: float = 1.0
    probe_timeout_s: float = 2.0
    fail_after: int = 2
    # Failover for idempotent cold requests: total attempts are
    # retries + 1, spaced by retry_backoff_ms * 2^attempt with +-50%
    # jitter.  Session frames never retry a possibly-processed send
    # (a duplicate would advance the session state) — they re-pin on
    # connect-time failure only.
    retries: int = 2
    retry_backoff_ms: float = 50.0
    # Per-attempt socket timeout for forwarded requests; sized for one
    # in-flight batch plus a cold XLA compile behind it.
    request_timeout_s: float = 660.0
    # Same body cap as the backends: refuse before buffering.
    max_body_mb: float = 160.0
    # Span ring capacity behind the router's /debug/trace.
    trace_buffer: int = 4096
    # Bound on the session -> backend pin table (LRU beyond it, same
    # contract as ClusterConfig.session_pin_limit: an evicted session's
    # next frame re-pins and runs cold).
    session_pin_limit: int = 4096
    # Optional fitted capacity model + planned aggregate rate (same
    # contract as ClusterConfig.capacity_model/target_rps; the router
    # loads the JSON via the stdlib ops/autoscale.load_capacity_model,
    # staying model-free).
    capacity_model: Optional[str] = None
    target_rps: float = 0.0
    # Circuit breaker (serve/cluster/router.py, docs/fault_tolerance.md):
    # a backend's breaker opens after fail_after consecutive
    # connect/timeout failures (request path or probes); after
    # breaker_reset_s it admits ONE half-open trial, whose outcome
    # closes or re-opens it.
    breaker_reset_s: float = 5.0
    # (host, port) of a durable session tier (stream/tier.py,
    # ``python -m raftstereo_tpu.cli.sessiontier``): when set, a session
    # whose home backend is lost is resumed WARM from the tier's latest
    # snapshot instead of the PR 13 ``cold_lost`` fallback.
    session_tier: Optional[Tuple[str, int]] = None
    # Hedged requests for idempotent cold JSON /predict forwards:
    # 0 disables hedging (default).  When > 0, a hedge to the next
    # ready backend fires after max(hedge_floor_ms, live forward p99)
    # — the p99 term engages once hedge_min_samples forwards have been
    # observed.  Never for sessions or streamed binary bodies.
    hedge_floor_ms: float = 0.0
    hedge_min_samples: int = 20
    # Tail-based trace retention ring (obs/stitch.py): how many
    # kept-trace records GET /debug/vars surfaces.  Error traces and
    # traces slower than the live hop p99 are retained; the boring
    # middle is dropped deterministically.
    tail_ring: int = 256
    # Burn-rate alerting (obs/alerts.py): fast evaluation window; the
    # slow window is 5x it (the standard multi-window pairing).
    alert_window_s: float = 30.0
    # Error-rate budget per alert class: observed error rate divided by
    # this IS the burn rate (1.0 = consuming budget exactly at limit).
    alert_error_budget: float = 0.05
    # Shed-rate budget, same semantics.
    alert_shed_budget: float = 0.25
    # Both windows burning at >= this rate -> PAGE (state 2) and an
    # autoscaler scale-up signal.
    alert_page_burn: float = 2.0
    # Per-target timeout for GET /metrics/fleet federation scrapes.
    fleet_timeout_s: float = 2.0

    def __post_init__(self):
        if isinstance(self.backends, list):
            object.__setattr__(
                self, "backends", tuple(tuple(b) for b in self.backends))
        if isinstance(self.session_tier, list):
            object.__setattr__(
                self, "session_tier", tuple(self.session_tier))
        assert self.probe_interval_s > 0, self.probe_interval_s
        assert self.probe_timeout_s > 0, self.probe_timeout_s
        assert self.fail_after >= 1, self.fail_after
        assert self.retries >= 0, self.retries
        assert self.retry_backoff_ms >= 0, self.retry_backoff_ms
        assert self.request_timeout_s > 0, self.request_timeout_s
        assert self.max_body_mb > 0, self.max_body_mb
        assert self.trace_buffer >= 1, self.trace_buffer
        assert self.session_pin_limit >= 1, self.session_pin_limit
        assert self.target_rps >= 0, self.target_rps
        assert self.breaker_reset_s > 0, self.breaker_reset_s
        assert self.hedge_floor_ms >= 0, self.hedge_floor_ms
        assert self.hedge_min_samples >= 1, self.hedge_min_samples
        assert self.tail_ring >= 1, self.tail_ring
        assert self.alert_window_s > 0, self.alert_window_s
        assert 0 < self.alert_error_budget <= 1, self.alert_error_budget
        assert 0 < self.alert_shed_budget <= 1, self.alert_shed_budget
        assert self.alert_page_burn >= 1, self.alert_page_burn
        assert self.fleet_timeout_s > 0, self.fleet_timeout_s


@dataclasses.dataclass(frozen=True)
class TierConfig:
    """Durable session tier (stream/tier.py,
    ``python -m raftstereo_tpu.cli.sessiontier``).

    The tier is model-free: it stores each session's latest snapshot as
    the verbatim wire JSON the backends already exchange over
    ``/debug/sessions`` (docs/serving.md "Session migration"), never
    decoding the arrays — so it starts in milliseconds, like the
    router, and any schema the backends agree on rides through it
    untouched."""

    host: str = "127.0.0.1"
    port: int = 8082  # 0 = ephemeral (tests bind a free port)
    # Count cap on stored sessions (LRU beyond it — an evicted
    # session's next resume falls back cold, never an error).
    session_limit: int = 65536
    # Byte budget over the stored wire bodies; LRU eviction while over
    # it (0 disables the byte bound; the count cap stays either way).
    budget_mb: float = 256.0
    # Snapshot bodies are small (a low-res disparity plane), so the
    # body cap is far below the serving default.
    max_body_mb: float = 16.0

    def __post_init__(self):
        assert self.session_limit >= 1, self.session_limit
        assert self.budget_mb >= 0, self.budget_mb
        assert self.max_body_mb > 0, self.max_body_mb


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving-layer parameters (serve/): dynamic micro-batching, the
    shape-bucketed compile cache, admission control and graceful
    degradation.  Consumed by ``python -m raftstereo_tpu.cli.serve``;
    frozen + hashable like the other configs."""

    host: str = "127.0.0.1"
    port: int = 8080  # 0 = ephemeral (tests bind a free port)

    # Shape policy, shared bitwise with the Evaluator via
    # ops/image.BucketPadder: align to divis_by, round up to bucket_multiple.
    divis_by: int = 32
    bucket_multiple: int = 64
    # Image shapes (H, W) whose buckets are compiled at startup so the first
    # real request in each never pays an XLA compile.
    buckets: Tuple[Tuple[int, int], ...] = ((540, 960),)
    warmup: bool = True

    # Dynamic micro-batching: a batch closes at max_batch_size or when the
    # oldest member has waited max_wait_ms, whichever comes first.  A
    # dispatch holds the rows that came, at row count 1 or max_batch_size
    # and with no zero row (a full batch when one is queued, one row when
    # not), so each shape bucket compiles once per row count: twice.
    max_batch_size: int = 8
    max_wait_ms: float = 5.0

    # Robustness: bounded queue (admission control sheds above the limit),
    # per-request timeout, and load-adaptive GRU-iteration reduction once
    # the queue backlog crosses degrade_queue_depth.
    queue_limit: int = 64
    request_timeout_ms: float = 30000.0
    iters: int = 32
    degraded_iters: int = 16
    degrade_queue_depth: int = 16

    # Request-size admission caps (each compile and each oversized tensor
    # costs everyone queued behind it): reject bodies above max_body_mb
    # (413) and images with a side above max_image_dim (400) before any
    # decode/allocation.  The body default is sized to what max_image_dim
    # actually needs (a 2048^2 fp32 pair is ~134 MB base64), not beyond
    # it.  max_image_dim bounds what a server will COMPILE on demand and
    # stays what the operator set.  A shape the operator lists in
    # ``buckets`` is warmed at start-up and is admissible whatever the
    # constant says: above the constant a pair passes only if it fits
    # inside a listed bucket, both sides (``admits``), and the body cap
    # is raised to what the largest such bucket needs (__post_init__,
    # ``bucket_body_mb``).  So a 1988x2964 bucket is served with no other
    # flag, and a 3000-wide or a 2900x2900 pair is still a 400.
    # cold_buckets=False additionally rejects shapes whose bucket was
    # not warmed at startup (400) — the production setting; True
    # compiles on demand (development, tests).
    max_body_mb: float = 160.0
    max_image_dim: int = 2048
    cold_buckets: bool = True

    # Temporal warm-start streaming (stream/, docs/streaming.md): when set,
    # ``/predict`` accepts ``session_id``/``seq_no`` and frames of a session
    # are warm-started from the previous frame's forward-warped disparity at
    # an adaptively reduced iteration count.  None disables the session
    # endpoints.  ``stream_warmup`` eagerly compiles the ladder levels for
    # every configured bucket at startup (the stream analogue of
    # ``warmup``), so mid-stream level switches never pay an XLA compile.
    stream: Optional[StreamConfig] = None
    stream_warmup: bool = False

    # Iteration-level continuous batching (serve/sched/): when set, the
    # server replaces the whole-request micro-batcher with the per-request
    # scheduler — requests join/leave one running batch per bucket at
    # iteration boundaries, ``/predict`` accepts ``deadline_ms`` +
    # ``priority``, and session frames ride the same scheduler as
    # high-priority short jobs instead of the batch-size-1 bypass.  None
    # keeps the monolithic dispatch path.
    sched: Optional[SchedConfig] = None

    # Replicated serving (serve/cluster/, docs/serving.md "Cluster"):
    # when set, the server runs N engine replicas (one per device)
    # behind a least-outstanding-work dispatcher with session-sticky
    # routing.  None keeps the single-engine path.
    cluster: Optional[ClusterConfig] = None

    # Spatial sharding (parallel/spatial.py, serve/spatial/,
    # docs/serving.md "Spatial sharding"): when > 1 the server can run
    # ONE request with its image height sharded across that many chips
    # on the ``space`` axis of a (1, N) mesh — the path for resolutions
    # above the single-chip bucket ceiling (``max_image_dim``).  0
    # inherits the model config's ``spatial_shards``.
    # ``spatial_buckets`` are the (H, W) image shapes the spatial path
    # serves (warmed at startup like ``buckets``); spatial requests to
    # other shapes — or with an ``accuracy`` tier / ``session_id``, both
    # unsupported under sharding in v1 — are 400s at admission, never a
    # compile.  When spatial buckets are configured, ``max_body_mb`` is
    # auto-raised to fit the largest one (see ``bucket_body_mb``), so a
    # 4K pair is not 413'd before admission ever sees it.
    spatial_shards: int = 0
    spatial_buckets: Tuple[Tuple[int, int], ...] = ()

    # Per-request accuracy tiers (ops/quant.py, docs/serving.md "Accuracy
    # tiers"): tier names ("certified"/"fast"/"turbo") the server should
    # OFFER on /predict's ``accuracy`` field.  "fast"/"turbo" are only
    # ADVERTISED (accepted + warmed) when ``cert_manifest`` certifies
    # their EPE delta within bound for this model (eval/certify.py;
    # python -m raftstereo_tpu.cli.certify writes it) — an uncertified
    # tier is refused with a clean 400, never served silently.  Empty =
    # the historical single-precision server: any ``accuracy`` field is
    # a 400 and no extra executables are compiled.
    tiers: Tuple[str, ...] = ()
    cert_manifest: Optional[str] = None

    # Speculative tier cascades (serve/cascade/, docs/serving.md "Tier
    # cascade"): schedule strings like "int8:24+fp32:8" — most GRU
    # iterations drafted on a cheap precision tier, the last K run on
    # the certified fp32 executables.  Requires ``sched`` (the handoff
    # is an iteration-boundary leave+join) and a ``cert_manifest``
    # certifying each schedule's EPE delta (cli.certify cascade);
    # uncertified schedules are refused at startup, never served.
    # ``cascade_divergence`` arms the early-promotion trigger: when the
    # EMA of a drafting slot's per-step low-res disparity delta (px)
    # exceeds it, the slot promotes to the certified tier before its
    # scheduled boundary.  0 = scheduled handoffs only.
    cascades: Tuple[str, ...] = ()
    cascade_divergence: float = 0.0

    # Observability (obs/, docs/observability.md): capacity of the span
    # ring buffer behind /debug/trace.  Spans are a few hundred bytes; the
    # ring bounds memory no matter the traffic.
    trace_buffer: int = 4096

    def __post_init__(self):
        if isinstance(self.buckets, list):
            object.__setattr__(
                self, "buckets", tuple(tuple(b) for b in self.buckets))
        if isinstance(self.spatial_buckets, list):
            object.__setattr__(
                self, "spatial_buckets",
                tuple(tuple(b) for b in self.spatial_buckets))
        assert self.spatial_shards >= 0, self.spatial_shards
        # A configured bucket above the side ceiling is admissible
        # (``admits``): the body cap follows from the largest such one
        # (the operator's own cap stands for everything under it).
        over = tuple(b for b in self.buckets if max(b) > self.max_image_dim)
        need = bucket_body_mb(over)
        if self.spatial_shards > 1 and self.spatial_buckets:
            # The whole point of the spatial path is payloads above the
            # single-chip cap — refusing them at the body cap would make
            # the capability unreachable (serve/httpbase.py 413s before
            # admission ever sees the request).
            need = max(need, bucket_body_mb(self.spatial_buckets))
        if need > self.max_body_mb:
            object.__setattr__(self, "max_body_mb", need)
        _known_tiers = ("certified", "fast", "turbo")  # ops/quant.TIERS
        bad_tiers = [t for t in self.tiers if t not in _known_tiers]
        assert not bad_tiers, (
            f"unknown accuracy tiers {bad_tiers}; choose from "
            f"{list(_known_tiers)}")
        if isinstance(self.cascades, list):
            object.__setattr__(self, "cascades", tuple(self.cascades))
        assert self.cascade_divergence >= 0, self.cascade_divergence
        if self.cascades or self.cascade_divergence > 0:
            assert self.sched is not None, (
                "cascades require --sched: the tier handoff is an "
                "iteration-boundary leave+join on the scheduler's "
                "running batches (docs/serving.md \"Tier cascade\")")
            assert self.cascades or self.cascade_divergence == 0, (
                "--cascade_divergence without --cascades arms a trigger "
                "nothing can fire")
            # Parse + canonicalize each schedule against the grammar and
            # the scheduler's granularity, fail-fast at config time (the
            # grammar module is jax-free, so this costs no import
            # weight in client-side processes).
            from .serve.cascade.schedule import (parse_schedule,
                                                 validate_schedule)
            canon = []
            for text in self.cascades:
                s = validate_schedule(
                    parse_schedule(text),
                    iters_per_step=self.sched.iters_per_step,
                    max_iters=self.sched.max_iters)
                canon.append(s.schedule)
            assert len(set(canon)) == len(canon), (
                f"duplicate cascade schedules in {list(self.cascades)} "
                f"(canonical: {canon})")
            object.__setattr__(self, "cascades", tuple(canon))
        # Degradation can only reduce work: a degraded_iters above iters
        # (e.g. the default 16 with --serve_iters 8) clamps down rather
        # than rejecting the config.
        if self.degraded_iters > self.iters:
            object.__setattr__(self, "degraded_iters", self.iters)
        assert self.max_batch_size >= 1, self.max_batch_size
        assert self.queue_limit >= self.max_batch_size, (
            f"queue_limit {self.queue_limit} < max_batch_size "
            f"{self.max_batch_size}: no full batch could ever form")
        assert self.iters >= 1 and self.degraded_iters >= 1, (
            self.iters, self.degraded_iters)
        assert self.max_wait_ms >= 0, self.max_wait_ms
        assert self.divis_by >= 1 and self.bucket_multiple >= 1
        assert self.max_body_mb > 0 and self.max_image_dim >= 1
        assert self.trace_buffer >= 1, self.trace_buffer
        if self.sched is not None:
            assert self.iters % self.sched.iters_per_step == 0, (
                f"iters {self.iters} not divisible by sched.iters_per_step "
                f"{self.sched.iters_per_step}")
            assert self.iters <= self.sched.max_iters, (
                f"iters {self.iters} exceeds sched.max_iters "
                f"{self.sched.max_iters}")
            if self.stream is not None:
                # Session frames ride the scheduler: every ladder level
                # must be a reachable iteration target.
                bad = [lv for lv in self.stream.ladder
                       if lv % self.sched.iters_per_step
                       or lv > self.sched.max_iters]
                assert not bad, (
                    f"stream ladder levels {bad} unreachable under sched "
                    f"(iters_per_step {self.sched.iters_per_step}, "
                    f"max_iters {self.sched.max_iters})")


    def admits(self, h: int, w: int) -> bool:
        """Whether an (h, w) image passes the side ceiling: no side above
        ``max_image_dim``, or both sides inside a bucket the operator
        listed.  ``max_image_dim`` stays the bound on what is compiled
        cold: a pair above it runs the listed bucket's program or a
        smaller one, never a larger."""
        if max(h, w) <= self.max_image_dim:
            return True
        return any(h <= bh and w <= bw for bh, bw in self.buckets)


def bucket_body_mb(buckets: Tuple[Tuple[int, int], ...],
                   channels: int = 3) -> float:
    """Request-body cap (MB) the largest of ``buckets`` needs: two fp32
    images base64-encoded (4/3 expansion) plus 25% JSON/meta headroom.
    ``ServeConfig`` raises ``max_body_mb`` to this for spatial buckets
    and for plain buckets above ``max_image_dim`` — a 4K pair is ~265 MB
    on the wire, a 1988x2964 one 235 MB, both above the default cap."""
    if not buckets:
        return 0.0
    h, w = max(buckets, key=lambda b: b[0] * b[1])
    raw = 2 * h * w * channels * 4  # two fp32 images
    return round(raw * (4 / 3) * 1.25 / 2 ** 20, 1)


def _parse_bucket(text: str) -> Tuple[int, int]:
    try:
        h, w = (int(v) for v in text.lower().split("x"))
        return h, w
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"bucket {text!r} is not HxW (e.g. 540x960)")


def add_serve_args(parser: argparse.ArgumentParser) -> None:
    d = ServeConfig()
    g = parser.add_argument_group("serve")
    g.add_argument("--host", default=d.host)
    g.add_argument("--port", type=int, default=d.port,
                   help="0 binds an ephemeral port")
    g.add_argument("--divis_by", type=int, default=d.divis_by)
    g.add_argument("--bucket_multiple", type=int, default=d.bucket_multiple,
                   help="round padded shapes up to this grid so "
                        "near-identical sizes share one compile")
    g.add_argument("--buckets", nargs="+", type=_parse_bucket,
                   default=list(d.buckets), metavar="HxW",
                   help="image shapes warmed at startup (e.g. 540x960)")
    g.add_argument("--no_warmup", action="store_true",
                   help="skip startup compilation of --buckets")
    g.add_argument("--max_batch_size", type=int, default=d.max_batch_size)
    g.add_argument("--max_wait_ms", type=float, default=d.max_wait_ms,
                   help="batching deadline: max time the oldest queued "
                        "request waits for a batch to fill")
    g.add_argument("--queue_limit", type=int, default=d.queue_limit,
                   help="admission control: requests beyond this backlog "
                        "are shed with an 'overloaded' response")
    g.add_argument("--request_timeout_ms", type=float,
                   default=d.request_timeout_ms)
    g.add_argument("--serve_iters", type=int, default=d.iters,
                   help="GRU iterations per request under normal load")
    g.add_argument("--degraded_iters", type=int, default=d.degraded_iters,
                   help="reduced GRU iterations once the queue backlog "
                        "crosses --degrade_queue_depth (graceful "
                        "degradation; RAFT-Stereo quality falls smoothly "
                        "with iteration count)")
    g.add_argument("--degrade_queue_depth", type=int,
                   default=d.degrade_queue_depth)
    g.add_argument("--max_body_mb", type=float, default=d.max_body_mb,
                   help="reject request bodies above this size (HTTP 413); "
                        "raised to fit a --buckets shape above "
                        "--max_image_dim")
    g.add_argument("--max_image_dim", type=int, default=d.max_image_dim,
                   help="reject images with a side above this (HTTP 400) "
                        "unless they fit inside a shape listed in "
                        "--buckets")
    g.add_argument("--no_cold_buckets", action="store_true",
                   help="reject shapes whose bucket was not warmed at "
                        "startup instead of compiling on demand (recommended "
                        "in production: a compile stalls everyone queued)")
    g.add_argument("--trace_buffer", type=int, default=d.trace_buffer,
                   help="span ring-buffer capacity behind /debug/trace "
                        "(docs/observability.md)")
    g.add_argument("--spatial_shards", type=int, default=d.spatial_shards,
                   help="shard one request's image height across this many "
                        "chips (space axis, parallel/spatial.py) for "
                        "resolutions above --max_image_dim; 0 inherits the "
                        "model config, 1 disables "
                        "(docs/serving.md \"Spatial sharding\")")
    g.add_argument("--spatial_buckets", nargs="+", type=_parse_bucket,
                   default=list(d.spatial_buckets), metavar="HxW",
                   help="image shapes the spatial path serves (warmed at "
                        "startup; other spatial shapes are a 400). "
                        "Raises --max_body_mb to fit the largest one.")
    g.add_argument("--tiers", nargs="+", default=list(d.tiers),
                   choices=["certified", "fast", "turbo"], metavar="TIER",
                   help="accuracy tiers offered on /predict's 'accuracy' "
                        "field (certified=fp32, fast=bf16, turbo=int8 "
                        "corr + bf16); fast/turbo also need a "
                        "--cert_manifest certifying their EPE delta "
                        "(docs/serving.md \"Accuracy tiers\")")
    g.add_argument("--cert_manifest", default=d.cert_manifest,
                   help="certification manifest written by "
                        "'python -m raftstereo_tpu.cli.certify'; "
                        "validated at startup before a tier is advertised")
    g.add_argument("--cascades", nargs="+", default=list(d.cascades),
                   metavar="SCHEDULE",
                   help="speculative tier-cascade schedules to offer, "
                        "e.g. int8:24+fp32:8 (draft on the cheap tier, "
                        "certify on fp32); requires --sched and a "
                        "--cert_manifest certifying each schedule "
                        "('cli.certify cascade'; docs/serving.md "
                        "\"Tier cascade\")")
    g.add_argument("--cascade_divergence", type=float,
                   default=d.cascade_divergence,
                   help="early-promotion trigger: EMA of a drafting "
                        "slot's per-step low-res disparity delta (px) "
                        "above which it hands off to the certified tier "
                        "before its scheduled boundary; 0 = scheduled "
                        "handoffs only")


def add_sched_args(parser: argparse.ArgumentParser) -> None:
    d = SchedConfig()
    g = parser.add_argument_group("sched")
    g.add_argument("--sched_iters_per_step", type=int,
                   default=d.iters_per_step,
                   help="GRU iterations per scheduler boundary (1 = finest "
                        "join/leave granularity; per-request iteration "
                        "targets must be divisible by it)")
    g.add_argument("--sched_starvation_ms", type=float,
                   default=d.starvation_ms,
                   help="queued requests gain one priority class per this "
                        "many ms waited, so low priority is never starved")
    g.add_argument("--sched_max_iters", type=int, default=d.max_iters,
                   help="cap on per-request iteration targets (any value "
                        "up to it is served from the same step executable)")


def sched_config_from_args(args: argparse.Namespace) -> SchedConfig:
    return SchedConfig(
        iters_per_step=args.sched_iters_per_step,
        starvation_ms=args.sched_starvation_ms,
        max_iters=args.sched_max_iters,
    )


def add_cluster_args(parser: argparse.ArgumentParser) -> None:
    d = ClusterConfig()
    g = parser.add_argument_group("cluster")
    g.add_argument("--replicas", type=int, default=None,
                   help="engine replicas, one per device (0/unset = "
                        "single-engine serving; -1 = one per visible "
                        "device); each replica owns its compile cache "
                        "and is warmed in-process before it is routable")
    g.add_argument("--session_pin_limit", type=int,
                   default=d.session_pin_limit,
                   help="bound on the session->replica pin table (LRU "
                        "beyond it; a re-routed session re-runs cold)")
    g.add_argument("--replica_fail_threshold", type=int,
                   default=d.fail_threshold,
                   help="consecutive engine failures after which a "
                        "replica stops receiving new work")
    g.add_argument("--capacity_model", default=None,
                   help="fitted capacity-model JSON (cli.loadgen fit, "
                        "docs/slo_harness.md) for model-based autoscale "
                        "advice + the cluster_capacity_headroom gauge")
    g.add_argument("--target_rps", type=float, default=d.target_rps,
                   help="planned aggregate request rate the capacity "
                        "model sizes the fleet for")


def cluster_config_from_args(args: argparse.Namespace
                             ) -> Optional[ClusterConfig]:
    if not args.replicas:
        return None
    return ClusterConfig(
        replicas=None if args.replicas < 0 else args.replicas,
        session_pin_limit=args.session_pin_limit,
        fail_threshold=args.replica_fail_threshold,
        capacity_model=args.capacity_model,
        target_rps=args.target_rps,
    )


def _parse_backend(text: str) -> Tuple[str, int]:
    host, _, port = text.rpartition(":")
    try:
        return (host or "127.0.0.1"), int(port)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"backend {text!r} is not HOST:PORT (e.g. 127.0.0.1:8080)")


def add_router_args(parser: argparse.ArgumentParser) -> None:
    d = RouterConfig()
    g = parser.add_argument_group("router")
    g.add_argument("--host", default=d.host)
    g.add_argument("--port", type=int, default=d.port,
                   help="0 binds an ephemeral port")
    g.add_argument("--backends", nargs="+", type=_parse_backend,
                   required=True, metavar="HOST:PORT",
                   help="backend stereo servers to route over")
    g.add_argument("--probe_interval_s", type=float,
                   default=d.probe_interval_s,
                   help="seconds between /healthz probes per backend")
    g.add_argument("--probe_timeout_s", type=float,
                   default=d.probe_timeout_s)
    g.add_argument("--fail_after", type=int, default=d.fail_after,
                   help="consecutive probe failures before a backend is "
                        "unroutable")
    g.add_argument("--router_retries", type=int, default=d.retries,
                   help="failover attempts beyond the first for "
                        "idempotent cold requests on backend failure")
    g.add_argument("--retry_backoff_ms", type=float,
                   default=d.retry_backoff_ms,
                   help="base backoff between failover attempts "
                        "(doubles per attempt, +-50%% jitter)")
    g.add_argument("--router_timeout_s", type=float,
                   default=d.request_timeout_s,
                   help="per-attempt socket timeout for forwarded "
                        "requests")
    g.add_argument("--max_body_mb", type=float, default=d.max_body_mb)
    g.add_argument("--trace_buffer", type=int, default=d.trace_buffer)
    g.add_argument("--session_pin_limit", type=int,
                   default=d.session_pin_limit,
                   help="bound on the session -> backend pin table (LRU "
                        "beyond it; an evicted session's next frame "
                        "re-pins and runs cold)")
    g.add_argument("--capacity_model", default=None,
                   help="fitted capacity-model JSON (cli.loadgen fit, "
                        "docs/slo_harness.md) for model-based autoscale "
                        "advice + the cluster_capacity_headroom gauge")
    g.add_argument("--target_rps", type=float, default=d.target_rps,
                   help="planned aggregate request rate the capacity "
                        "model sizes the backend fleet for")
    g.add_argument("--session_tier", type=_parse_backend, default=None,
                   metavar="HOST:PORT",
                   help="durable session tier (cli.sessiontier): resume "
                        "a session warm from it when its home backend "
                        "is lost (docs/streaming.md \"Durable sessions\")")
    g.add_argument("--breaker_reset_s", type=float,
                   default=d.breaker_reset_s,
                   help="seconds an open circuit breaker waits before "
                        "admitting a half-open trial request")
    g.add_argument("--hedge_floor_ms", type=float,
                   default=d.hedge_floor_ms,
                   help="floor on the hedged-request delay for idempotent "
                        "cold JSON requests; 0 disables hedging")
    g.add_argument("--hedge_min_samples", type=int,
                   default=d.hedge_min_samples,
                   help="forward-latency samples required before the hedge "
                        "delay tracks live p99 instead of the floor")
    g.add_argument("--tail_ring", type=int, default=d.tail_ring,
                   help="tail-based trace retention ring capacity: error "
                        "and slower-than-live-p99 traces kept, the "
                        "boring middle dropped (docs/observability.md)")
    g.add_argument("--alert_window_s", type=float,
                   default=d.alert_window_s,
                   help="fast burn-rate evaluation window; the slow "
                        "window is 5x it")
    g.add_argument("--alert_error_budget", type=float,
                   default=d.alert_error_budget,
                   help="error-rate budget: observed error rate over "
                        "this is the burn rate")
    g.add_argument("--alert_shed_budget", type=float,
                   default=d.alert_shed_budget,
                   help="shed-rate budget, same burn semantics")
    g.add_argument("--alert_page_burn", type=float,
                   default=d.alert_page_burn,
                   help="both windows burning at >= this pages (alert "
                        "state 2) and signals the autoscaler")
    g.add_argument("--fleet_timeout_s", type=float,
                   default=d.fleet_timeout_s,
                   help="per-target scrape timeout for GET /metrics/fleet "
                        "federation")


def router_config_from_args(args: argparse.Namespace) -> RouterConfig:
    return RouterConfig(
        host=args.host,
        port=args.port,
        backends=tuple(tuple(b) for b in args.backends),
        probe_interval_s=args.probe_interval_s,
        probe_timeout_s=args.probe_timeout_s,
        fail_after=args.fail_after,
        retries=args.router_retries,
        retry_backoff_ms=args.retry_backoff_ms,
        request_timeout_s=args.router_timeout_s,
        max_body_mb=args.max_body_mb,
        trace_buffer=args.trace_buffer,
        session_pin_limit=args.session_pin_limit,
        capacity_model=args.capacity_model,
        target_rps=args.target_rps,
        session_tier=(tuple(args.session_tier)
                      if args.session_tier is not None else None),
        breaker_reset_s=args.breaker_reset_s,
        hedge_floor_ms=args.hedge_floor_ms,
        hedge_min_samples=args.hedge_min_samples,
        tail_ring=args.tail_ring,
        alert_window_s=args.alert_window_s,
        alert_error_budget=args.alert_error_budget,
        alert_shed_budget=args.alert_shed_budget,
        alert_page_burn=args.alert_page_burn,
        fleet_timeout_s=args.fleet_timeout_s,
    )


def add_tier_args(parser: argparse.ArgumentParser) -> None:
    d = TierConfig()
    g = parser.add_argument_group("session tier")
    g.add_argument("--host", default=d.host)
    g.add_argument("--port", type=int, default=d.port,
                   help="0 binds an ephemeral port")
    g.add_argument("--session_limit", type=int, default=d.session_limit,
                   help="max stored sessions (LRU beyond it; an evicted "
                        "session's next resume falls back cold)")
    g.add_argument("--budget_mb", type=float, default=d.budget_mb,
                   help="byte budget over stored snapshot bodies (LRU "
                        "eviction while over it; 0 = count-bounded only)")
    g.add_argument("--max_body_mb", type=float, default=d.max_body_mb)


def tier_config_from_args(args: argparse.Namespace) -> TierConfig:
    return TierConfig(
        host=args.host,
        port=args.port,
        session_limit=args.session_limit,
        budget_mb=args.budget_mb,
        max_body_mb=args.max_body_mb,
    )


def add_stream_args(parser: argparse.ArgumentParser) -> None:
    d = StreamConfig()
    g = parser.add_argument_group("stream")
    g.add_argument("--stream_ladder", nargs="+", type=int,
                   default=list(d.ladder), metavar="ITERS",
                   help="descending GRU-iteration levels; ladder[0] is the "
                        "cold-start count, warm frames pick from the rest "
                        "(each level is one pre-compilable executable)")
    g.add_argument("--ema_decay", type=float, default=d.ema_decay,
                   help="EMA decay of the per-frame update magnitude that "
                        "drives the adaptive iteration controller")
    g.add_argument("--promote_threshold", type=float,
                   default=d.promote_threshold,
                   help="EMA (low-res px) above which the next frame runs "
                        "more iterations")
    g.add_argument("--demote_threshold", type=float,
                   default=d.demote_threshold,
                   help="EMA below which the next frame runs fewer "
                        "iterations")
    g.add_argument("--cold_reset_threshold", type=float,
                   default=d.cold_reset_threshold,
                   help="EMA above which the warm start is judged lost and "
                        "the next frame re-runs cold at ladder[0]")
    g.add_argument("--session_limit", type=int, default=d.session_limit,
                   help="max live sessions; beyond this the LRU session is "
                        "evicted (its next frame re-runs cold)")
    g.add_argument("--session_ttl_s", type=float, default=d.session_ttl_s,
                   help="idle seconds after which a session expires (its "
                        "next frame re-runs cold, never an error)")
    g.add_argument("--session_budget_mb", type=float,
                   default=d.session_budget_mb,
                   help="byte budget for in-replica session state (LRU "
                        "eviction while over it; 0 = count-bounded only)")
    g.add_argument("--snapshot_compress", choices=["off", "int8"],
                   default=d.snapshot_compress,
                   help="snapshot wire compression for exports + tier "
                        "pushes: int8 = per-row symmetric quantization "
                        "with an exactness manifest and a bitwise f32 "
                        "fallback (docs/streaming.md)")
    g.add_argument("--session_tier", type=_parse_backend, default=None,
                   metavar="HOST:PORT",
                   help="durable session tier (cli.sessiontier) to push "
                        "completed-frame snapshots to, write-behind; "
                        "unset = local-pin-only sessions")


def stream_config_from_args(args: argparse.Namespace) -> StreamConfig:
    return StreamConfig(
        ladder=tuple(args.stream_ladder),
        ema_decay=args.ema_decay,
        promote_threshold=args.promote_threshold,
        demote_threshold=args.demote_threshold,
        cold_reset_threshold=args.cold_reset_threshold,
        session_limit=args.session_limit,
        session_ttl_s=args.session_ttl_s,
        session_budget_mb=args.session_budget_mb,
        snapshot_compress=args.snapshot_compress,
        tier=(tuple(args.session_tier)
              if args.session_tier is not None else None),
    )


def serve_config_from_args(args: argparse.Namespace,
                           stream: Optional[StreamConfig] = None,
                           stream_warmup: bool = False,
                           sched: Optional[SchedConfig] = None,
                           cluster: Optional[ClusterConfig] = None
                           ) -> ServeConfig:
    return ServeConfig(
        stream=stream,
        stream_warmup=stream_warmup,
        sched=sched,
        cluster=cluster,
        host=args.host,
        port=args.port,
        divis_by=args.divis_by,
        bucket_multiple=args.bucket_multiple,
        buckets=tuple(tuple(b) for b in args.buckets),
        warmup=not args.no_warmup,
        max_batch_size=args.max_batch_size,
        max_wait_ms=args.max_wait_ms,
        queue_limit=args.queue_limit,
        request_timeout_ms=args.request_timeout_ms,
        iters=args.serve_iters,
        degraded_iters=args.degraded_iters,
        degrade_queue_depth=args.degrade_queue_depth,
        max_body_mb=args.max_body_mb,
        max_image_dim=args.max_image_dim,
        cold_buckets=not args.no_cold_buckets,
        spatial_shards=args.spatial_shards,
        spatial_buckets=tuple(tuple(b) for b in args.spatial_buckets),
        trace_buffer=args.trace_buffer,
        tiers=tuple(args.tiers),
        cert_manifest=args.cert_manifest,
        cascades=tuple(args.cascades),
        cascade_divergence=args.cascade_divergence,
    )


# ---------------------------------------------------------------------------
# CLI plumbing: one flag set, shared by every entry point.
# ---------------------------------------------------------------------------

def add_model_args(parser: argparse.ArgumentParser) -> None:
    g = parser.add_argument_group("model")
    g.add_argument("--corr_implementation",
                   choices=["auto", "reg", "alt", "pallas", "pallas_alt"],
                   default="reg",
                   help="correlation backend; 'auto' = fastest for the "
                        "active platform (pallas_alt on TPU, reg elsewhere)")
    g.add_argument("--corr_levels", type=int, default=4)
    g.add_argument("--corr_radius", type=int, default=4)
    g.add_argument("--n_downsample", type=int, default=2)
    g.add_argument("--n_gru_layers", type=int, default=3)
    g.add_argument("--hidden_dims", nargs="+", type=int, default=[128, 128, 128])
    g.add_argument("--slow_fast_gru", action="store_true")
    g.add_argument("--shared_backbone", action="store_true")
    g.add_argument("--context_norm", choices=["group", "batch", "instance", "none"],
                   default="batch")
    g.add_argument("--mixed_precision", action="store_true",
                   help="bfloat16 compute for encoders and GRUs")
    g.add_argument("--corr_dtype", choices=["float32", "bfloat16"], default="float32")
    g.add_argument("--corr_precision", choices=["highest", "high", "default"],
                   default="highest",
                   help="MXU multiply precision for fp32 correlation matmuls "
                        "(highest=exact 6-pass, high=3-pass, default=1-pass)")
    g.add_argument("--corr_quant", action="store_true",
                   help="int8-quantized correlation volume (symmetric "
                        "per-row scales, int8 matmul + dequant epilogue; "
                        "ops/quant.py) — the 'turbo' serving tier's "
                        "numeric policy, inference only")
    g.add_argument("--remat", action="store_true",
                   help="rematerialize each GRU iteration in backward: "
                        "O(1) activation memory instead of O(iters); "
                        "needed to fit the full training recipe on one chip")
    g.add_argument("--input_mode", choices=["passive", "sl"],
                   default="passive",
                   help="input modality: 'passive' = 3-channel RGB pairs; "
                        "'sl' = 12-channel structured-light stacks (ambient "
                        "+ 9 pattern channels per side) through a learned "
                        "projection (docs/structured_light.md)")


def model_config_from_args(args: argparse.Namespace) -> RAFTStereoConfig:
    return RAFTStereoConfig(
        corr_implementation=args.corr_implementation,
        corr_levels=args.corr_levels,
        corr_radius=args.corr_radius,
        n_downsample=args.n_downsample,
        n_gru_layers=args.n_gru_layers,
        hidden_dims=tuple(args.hidden_dims),
        slow_fast_gru=args.slow_fast_gru,
        shared_backbone=args.shared_backbone,
        context_norm=args.context_norm,
        compute_dtype="bfloat16" if args.mixed_precision else "float32",
        corr_dtype=args.corr_dtype,
        corr_precision=args.corr_precision,
        corr_quant=args.corr_quant,
        remat=args.remat,
        input_mode=args.input_mode,
    )
