"""Serving observability: a small metrics registry with Prometheus text
exposition (no client library dependency — the format is plain text).

Three instrument kinds: monotonically increasing ``Counter``, last-value
``Gauge`` and the fixed-bucket ``LatencyHistogram`` from utils/profiling.py
(shared with the Evaluator's per-call timing).  Counters and gauges can be
registered with ``labels=(...)`` — a label FAMILY whose per-label-set
children are created on first use — so hot counters split by dimension
(``serve_requests_total{endpoint=,outcome=}``,
``serve_compile_cache_misses_total{bucket=,iters=,mode=}``) while the
render stays valid Prometheus 0.0.4 (label values escaped, one TYPE block
per family; validated by raftstereo_tpu/obs/prom.py in the tier-1 tests).
``MetricsRegistry.render`` emits the text format Prometheus scrapes from
``GET /metrics``:

    # HELP serve_requests_total ...
    # TYPE serve_requests_total counter
    serve_requests_total{endpoint="predict",outcome="ok"} 42
    serve_request_latency_seconds_bucket{le="0.1"} 17
    ...

``ServeMetrics`` bundles every instrument the serving subsystem records, so
the engine, batcher and HTTP layer share one object and ``/metrics`` is one
render call.
"""

from __future__ import annotations

import math
import sys
import threading
from typing import Dict, List, Optional, Sequence, Tuple

from ..utils.profiling import LatencyHistogram

__all__ = ["CallbackGauge", "ClusterMetrics", "Counter", "Gauge",
           "LabelFamily", "MetricsRegistry", "ServeMetrics",
           "device_memory"]


class Counter:
    """Monotonic counter (Prometheus ``counter``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0  # guarded_by: _lock

    def inc(self, n: float = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        with self._lock:  # a torn read mid-inc would render a bogus sample
            return self._value


class Gauge:
    """Last-value instrument (Prometheus ``gauge``).

    Locked ``set`` AND ``add``: read-modify-write callers (live session
    counts, in-flight gauges) must not lose updates under the threaded
    HTTP front-end, and ``g.set(g.value + 1)`` races exactly there.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0.0  # guarded_by: _lock

    def set(self, v: float) -> None:
        with self._lock:
            self._value = v

    def add(self, delta: float) -> None:
        with self._lock:
            self._value += delta

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class CallbackGauge:
    """A gauge read at scrape time: ``value`` is ``fn()`` when the
    registry renders (device memory, which only the runtime knows)."""

    def __init__(self, fn):
        self._fn = fn

    @property
    def value(self) -> float:
        return float(self._fn())


# device.memory_stats() keys -> what the gauges and /debug/vars call them.
# ``peak_bytes_reserved`` is the running program's temporaries on this
# runtime, which ``peak_bytes_in_use`` leaves out (PERF.md section 3).
DEVICE_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use",
                      "peak_bytes_reserved")


def device_memory() -> Dict[str, int]:
    """``DEVICE_MEMORY_KEYS`` of the fullest local device, each taken as
    the largest over the devices; zeros where the backend reports none
    (CPU) or where this process never imported JAX (no device to ask:
    the router, the lint)."""
    jax = sys.modules.get("jax")
    stats = ([d.memory_stats() or {} for d in jax.local_devices()]
             if jax is not None else [])
    return {k: max((int(s.get(k, 0)) for s in stats), default=0)
            for k in DEVICE_MEMORY_KEYS}


class LabelFamily:
    """A labeled metric family: ``family.labels(k=v, ...)`` returns the
    child instrument for that label set, creating it on first use.

    ``value`` sums the children — the label-blind total, which is also
    what pre-label callers and tests read.  Children render as one series
    per label set under a single HELP/TYPE block.
    """

    def __init__(self, make_child, label_names: Sequence[str]):
        assert label_names, "a family needs at least one label"
        self._make = make_child
        self.label_names = tuple(label_names)
        self._lock = threading.Lock()
        # child instruments by label values  # guarded_by: _lock
        self._children: Dict[Tuple[str, ...], object] = {}

    def labels(self, **kv):
        if set(kv) != set(self.label_names):
            raise ValueError(
                f"labels {sorted(kv)} != declared {sorted(self.label_names)}")
        key = tuple(str(kv[k]) for k in self.label_names)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._children[key] = self._make()
            return child

    def series(self) -> List[Tuple[Tuple[str, ...], object]]:
        """(label_values, child) pairs in first-use order (snapshot)."""
        with self._lock:
            return list(self._children.items())

    @property
    def value(self) -> float:
        return sum(c.value for _, c in self.series())


def _fmt(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    if float(v).is_integer():
        return str(int(v))
    return format(v, ".9g")


def _escape_help(s: str) -> str:
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class MetricsRegistry:
    """Ordered name -> instrument registry with Prometheus text rendering."""

    def __init__(self):
        self._lock = threading.Lock()
        # (kind, name, help, instrument)  # guarded_by: _lock
        self._entries: List[Tuple[str, str, str, object]] = []

    def _register(self, kind: str, name: str, help_: str, obj):
        with self._lock:
            if any(e[1] == name for e in self._entries):
                raise ValueError(f"metric {name!r} already registered")
            self._entries.append((kind, name, help_, obj))
        return obj

    def counter(self, name: str, help_: str,
                labels: Sequence[str] = ()):
        obj = LabelFamily(Counter, labels) if labels else Counter()
        return self._register("counter", name, help_, obj)

    def gauge(self, name: str, help_: str, labels: Sequence[str] = (),
              fn=None):
        """``fn`` makes it a ``CallbackGauge`` read at render time."""
        obj = (CallbackGauge(fn) if fn is not None
               else LabelFamily(Gauge, labels) if labels else Gauge())
        return self._register("gauge", name, help_, obj)

    def device_memory_gauges(self, prefix: str) -> None:
        """``<prefix>_device_<key>`` for each of ``DEVICE_MEMORY_KEYS``,
        read from the runtime at scrape time."""
        for key in DEVICE_MEMORY_KEYS:
            self.gauge(
                f"{prefix}_device_{key}",
                f"device.memory_stats()[{key!r}] of the fullest local "
                "device at scrape time (reserved = the running program's "
                "temporaries, outside in_use)",
                fn=lambda key=key: device_memory()[key])

    def histogram(self, name: str, help_: str,
                  bounds=None, lo: float = 1e-4,
                  hi: float = 60.0) -> LatencyHistogram:
        return self._register("histogram", name, help_,
                              LatencyHistogram(bounds=bounds, lo=lo, hi=hi))

    def entries(self) -> List[Tuple[str, str, str, object]]:
        """(kind, name, help, instrument) snapshot — for the name lint
        (scripts/check_metrics.py) and exporters."""
        with self._lock:
            return list(self._entries)

    def render(self) -> str:
        """Prometheus text exposition format, version 0.0.4."""
        lines: List[str] = []
        for kind, name, help_, obj in self.entries():
            lines.append(f"# HELP {name} {_escape_help(help_)}")
            lines.append(f"# TYPE {name} {kind}")
            if kind == "histogram":
                # One atomic snapshot: _count must equal the +Inf bucket.
                pairs, count, total = obj.prometheus()
                for bound, cum in pairs:
                    lines.append(
                        f'{name}_bucket{{le="{_fmt(bound)}"}} {cum}')
                lines.append(f"{name}_sum {format(total, '.9g')}")
                lines.append(f"{name}_count {count}")
            elif isinstance(obj, LabelFamily):
                # A family with no children renders HELP/TYPE only —
                # legal, and keeps scrape schemas stable from startup.
                for values, child in obj.series():
                    labelset = ",".join(
                        f'{k}="{_escape_label(v)}"'
                        for k, v in zip(obj.label_names, values))
                    lines.append(f"{name}{{{labelset}}} {_fmt(child.value)}")
            else:
                lines.append(f"{name} {_fmt(obj.value)}")
        return "\n".join(lines) + "\n"


class ServeMetrics:
    """Every instrument the serving subsystem records, in one bundle."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        r = registry or MetricsRegistry()
        self.registry = r
        self.requests = r.counter(
            "serve_requests_total",
            "requests answered by the HTTP front-end, by endpoint "
            "(predict/stream; other = POST to an unknown path) and outcome "
            "(ok/bad_request/shed/timeout/unavailable/too_large/not_found/"
            "error)",
            labels=("endpoint", "outcome"))
        self.responses = r.counter(
            "serve_responses_total", "requests answered successfully")
        self.tier_requests = r.counter(
            "serve_tier_requests_total",
            "/predict requests by resolved accuracy tier "
            "(certified/fast/turbo; 'default' = no accuracy field — the "
            "base precision path; docs/serving.md \"Accuracy tiers\")",
            labels=("tier",))
        self.shed = r.counter(
            "serve_shed_total",
            "requests rejected at admission because the queue was full")
        self.timeouts = r.counter(
            "serve_timeout_total",
            "requests that exceeded request_timeout_ms while queued")
        self.errors = r.counter(
            "serve_errors_total", "requests failed by an engine error")
        self.degraded_batches = r.counter(
            "serve_degraded_batches_total",
            "batches run at degraded_iters due to queue backlog")
        self.compile_hits = r.counter(
            "serve_compile_cache_hits_total",
            "batches dispatched to an already-compiled executable",
            labels=("bucket", "iters", "mode", "tier"))
        self.compile_misses = r.counter(
            "serve_compile_cache_misses_total",
            "batches whose (bucket, iters, precision mode) triggered an "
            "XLA compile — tier= is the resolved precision mode, so a "
            "per-tier compile under traffic is attributable",
            labels=("bucket", "iters", "mode", "tier"))
        self.xla_compiles = r.counter(
            "serve_xla_compiles_total",
            "programs this process built (kind=compile) or read back from "
            "the persistent compile cache (kind=cache_load), counted by "
            "jax.monitoring: EVERY program, eager ones included, which "
            "the engine's own hit/miss counters cannot see",
            labels=("kind",))
        r.device_memory_gauges("serve")
        self.model_info = r.gauge(
            "serve_model_info",
            "1 for the network this server runs: the fields that tell the "
            "published default from the real-time variant "
            "(utils/platform.describe_network) and its served iterations",
            labels=("n_downsample", "n_gru_layers", "shared_backbone",
                    "slow_fast_gru", "iters"))
        self.queue_depth = r.gauge(
            "serve_queue_depth", "requests currently waiting in the queue")
        self.batch_size = r.histogram(
            "serve_batch_size", "requests per batch the batcher closed",
            bounds=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64))
        self.batch_rows = r.counter(
            "serve_batch_rows_total",
            "plain dispatches by compiled row count (serve/engine.py "
            "row_counts: 1 and max_batch_size), each holding exactly that "
            "many real rows; serve_batch_size counts the rows of a batch "
            "the batcher closed",
            labels=("rows",))
        self.launched_ahead = r.counter(
            "serve_batch_launched_ahead_total",
            "plain dispatches the batcher closed and launched while "
            "another was in flight (closed_by=full_ahead): staged and "
            "queued on the device behind the running one; over "
            "serve_batch_rows_total, the share of dispatches the "
            "launch-ahead rule took")
        self.latency = r.histogram(
            "serve_request_latency_seconds",
            "submit-to-result latency per request (queue wait + compute)")
        self.batch_latency = r.histogram(
            "serve_batch_latency_seconds",
            "engine wall-clock per dispatched batch (forward + host fetch)")
        # Temporal warm-start streaming (stream/, docs/streaming.md).
        self.stream_active = r.gauge(
            "stream_sessions_active", "live sessions in the session store")
        self.stream_warm_frames = r.counter(
            "stream_warm_frames_total",
            "frames warm-started from the previous frame's disparity")
        self.stream_cold_frames = r.counter(
            "stream_cold_frames_total",
            "frames run cold, by reason: new (no session state — includes "
            "expired/evicted sessions re-established), reset (controller "
            "cold reset), out_of_order (seq_no mismatch), resized (bucket "
            "change mid-stream)",
            labels=("reason",))
        self.stream_evicted = r.counter(
            "stream_sessions_evicted_total",
            "sessions LRU-evicted because the store hit session_limit")
        self.stream_expired = r.counter(
            "stream_sessions_expired_total",
            "sessions dropped after idling past session_ttl_s")
        self.stream_frame_iters = r.histogram(
            "stream_frame_iters", "GRU iterations run per streamed frame",
            bounds=(1, 2, 4, 8, 12, 16, 24, 32, 48, 64))
        self.stream_frame_latency = r.histogram(
            "stream_frame_latency_seconds",
            "per-frame wall-clock (warp + forward + host fetch), "
            "compile-free frames only")
        # Durable session tier (stream/tier.py, docs/streaming.md
        # "Durable sessions").
        self.stream_session_bytes = r.gauge(
            "stream_session_bytes",
            "byte-accurate total of all live session state in the "
            "in-replica store (disparity plane nbytes + fixed controller "
            "overhead per session) — the value the session_budget_mb "
            "byte-budget eviction bounds")
        self.stream_tier_pushes = r.counter(
            "stream_tier_pushes_total",
            "write-behind snapshot pushes to the session tier by outcome: "
            "ok (stored), stale (tier already held fresher state — "
            "harmless), degraded (suppressed while detached from an "
            "unreachable tier), dropped (coalescing queue overflowed; "
            "oldest pending SID discarded, its next frame re-enqueues), "
            "skipped (no exportable state at send time), error (push "
            "failed after retries; the publisher detached)",
            labels=("outcome",))
        self.stream_tier_degraded = r.counter(
            "stream_tier_degraded_total",
            "pushes suppressed or failed because the session tier was "
            "unreachable/slow — graceful degradation to local-pin "
            "behaviour, never an error; the publisher re-probes every "
            "tier_reprobe_s and re-attaches")
        self.stream_tier_attached = r.gauge(
            "stream_tier_attached",
            "1 while the write-behind publisher considers the session "
            "tier reachable, 0 while degraded to local-pin behaviour")
        # Iteration-level continuous batching (serve/sched/,
        # docs/serving.md).
        self.sched_slots_active = r.gauge(
            "sched_slots_active",
            "occupied slots across the scheduler's running batches")
        self.sched_occupancy = r.gauge(
            "sched_occupancy",
            "occupied fraction (0-1) of the running batches' slots")
        self.sched_queue_depth = r.gauge(
            "sched_queue_depth",
            "requests waiting for a slot, by priority class "
            "(high/normal/low)",
            labels=("priority",))
        self.sched_joins = r.counter(
            "sched_joins_total",
            "requests that joined a running batch at an iteration boundary")
        self.sched_leaves = r.counter(
            "sched_leaves_total",
            "requests that left a running batch (target iterations reached "
            "or deadline early exit)")
        self.sched_early_exits = r.counter(
            "sched_early_exits_total",
            "deadline-aware early exits: requests answered with the "
            "anytime result before their target iterations "
            "(meta.degraded=true)")
        self.sched_steps = r.counter(
            "sched_steps_total",
            "single-boundary step executions across running batches")
        self.sched_step_latency = r.histogram(
            "sched_step_latency_seconds",
            "engine wall-clock per scheduler step (every occupied slot "
            "advances iters_per_step iterations), compile-free steps only")
        # Speculative tier cascades (serve/cascade/, docs/serving.md
        # "Tier cascade").
        self.cascade_schedules = r.counter(
            "cascade_schedules_total",
            "completed cascade requests by canonical schedule string "
            "(deadline-degraded cheap-phase exits are NOT counted: "
            "their answer never reached the certified tier)",
            labels=("schedule",))
        self.cascade_promotions = r.counter(
            "cascade_promotions_total",
            "cheap-to-certified tier handoffs by kind: 'scheduled' at "
            "the schedule's cheap-leg boundary, 'early' when the "
            "divergence EMA crossed --cascade_divergence first",
            labels=("kind",))
        self.cascade_iterations = r.counter(
            "cascade_iterations_total",
            "GRU iterations executed for cascade slots by phase "
            "(cheap/certified) — certified over the sum is the EXECUTED "
            "fp32-iteration fraction the cascade is buying down",
            labels=("phase",))
        self.cascade_fp32_fraction = r.gauge(
            "cascade_fp32_fraction",
            "executed fp32-iteration fraction of the most recently "
            "completed cascade request (scheduled fraction when no "
            "early promotion fired)")
        # Spatial sharding (parallel/spatial.py, serve/spatial/,
        # docs/serving.md "Spatial sharding").
        self.spatial_shards = r.gauge(
            "spatial_shards",
            "spatial mesh width the engine was built with (0 = spatial "
            "sharding disabled)")
        self.spatial_requests = r.counter(
            "spatial_requests_total",
            "requests dispatched on the spatial path by outcome "
            "(ok/error/shed) — admission 400s never reach the mesh and "
            "are counted only in serve_requests_total",
            labels=("outcome",))
        self.spatial_latency = r.histogram(
            "spatial_request_latency_seconds",
            "engine wall-clock per spatial dispatch (pad + sharded "
            "forward + host fetch); the mesh is exclusive, so this is "
            "also the mesh-busy time per request")
        # Binary wire format (raftstereo_tpu/wire, docs/wire_format.md).
        self.wire_bytes = r.counter(
            "wire_bytes_total",
            "/predict data-plane bytes by direction (in = request "
            "bodies, out = 200 response bodies) and format "
            "(json = base64 dialect, binary = wire frames) — the "
            "wire-bytes/pair SLO signal is out+in over "
            "serve_requests_total",
            labels=("direction", "format"))
        self.wire_tiles = r.counter(
            "serve_wire_tiles_total",
            "compression tiles of binary /predict frames by direction "
            "(in = request bodies, out = 200 replies) and coding "
            "(stored = the encoder's sample did not shrink, so the "
            "tile travels as a stored zlib stream; deflate = it did) "
            "— how often the per-tile decision of wire/format.py "
            "engages; raw (compress=false) planes count no tile",
            labels=("direction", "coding"))
        # The server's serial points on a binary request's path
        # (serve/server.py): the `decode_slot_wait` and `reply_wait` spans.
        self.host_wait = r.counter(
            "serve_host_wait_seconds_total",
            "seconds /predict handler threads spent waiting to acquire "
            "one of the server's serial points (decode_slot = a decode "
            "slot for a binary body, reply_lock = the turn to encode a "
            "binary reply); its rate over wall time is the mean number "
            "of waiters there",
            labels=("point",))
        self.wire_negotiations = r.counter(
            "wire_negotiations_total",
            "/predict format negotiations by resolved request dialect "
            "(Content-Type) and response dialect (Accept; error "
            "replies are always JSON regardless)",
            labels=("request", "response"))

    def render(self) -> str:
        return self.registry.render()


class ClusterMetrics:
    """The replicated-serving / autoscaling signal bundle
    (serve/cluster/, docs/serving.md "Cluster").

    Shared by the in-process dispatcher (mounted on the server's
    ``ServeMetrics`` registry, so one ``/metrics`` scrape covers both)
    and the front-end router (its own registry — the router process has
    no serve bundle).  The ``cluster_replicas{state=}`` gauge family and
    the per-replica queue-depth/utilization gauges are the autoscaling
    inputs: scale out when ready replicas run hot, scale in when
    utilization stays low; ``cluster_dispatch_total{outcome=}`` exposes
    failover and shed rates.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        r = registry or MetricsRegistry()
        self.registry = r
        self.replicas = r.gauge(
            "cluster_replicas",
            "engine replicas / backends by state (starting/ready/"
            "draining/drained/failed/unreachable)",
            labels=("state",))
        self.queue_depth = r.gauge(
            "cluster_queue_depth",
            "requests queued or in flight, per replica",
            labels=("replica",))
        self.dispatch = r.counter(
            "cluster_dispatch_total",
            "dispatch decisions per replica and outcome (ok/error/shed/"
            "timeout/unavailable/failover/connect_error)",
            labels=("replica", "outcome"))
        self.utilization = r.gauge(
            "cluster_utilization",
            "mean occupied fraction (0-1) of the ready replicas' batch "
            "capacity — the primary scale-out signal")
        self.session_repins = r.counter(
            "cluster_session_repins_total",
            "session frames re-pinned to a new replica, by why the old "
            "pin was unusable (failed/draining/evicted); each re-pin "
            "attempts a warm state handoff, counted separately in "
            "cluster_session_handoffs_total",
            labels=("reason",))
        self.session_handoffs = r.counter(
            "cluster_session_handoffs_total",
            "warm-start state migrations between replicas/backends by "
            "outcome: warm (state moved, next frame runs warm), "
            "cold_schema (fingerprint/version mismatch — documented cold "
            "fallback), cold_lost (no exportable state — the old home is "
            "gone or never finished a frame)",
            labels=("outcome",))
        self.autoscale_recommendation = r.gauge(
            "cluster_autoscale_recommendation",
            "recommended change in replica count from ops/autoscale.py "
            "(positive = scale out, negative = scale in, 0 = hold)")
        self.capacity_headroom = r.gauge(
            "cluster_capacity_headroom",
            "fraction of the ready fleet's fitted capacity left above "
            "the planned target_rps (1 = idle, 0 = at the fitted limit, "
            "negative = overcommitted), from the loadgen capacity model "
            "(docs/slo_harness.md); 0 when no model is configured")
        self.probe_failures = r.counter(
            "cluster_probe_failures_total",
            "health-probe failures per backend (router only)",
            labels=("replica",))
        self.router_latency = r.histogram(
            "cluster_router_hop_latency_seconds",
            "router-added latency per forwarded request (route pick + "
            "proxying, excluding the backend's own compute)")
        self.wire_stream_bytes = r.counter(
            "cluster_wire_stream_bytes_total",
            "binary /predict bytes relayed chunk-wise by the streaming "
            "forward path, by direction (in = client->backend request "
            "bodies including the peeked header+meta prefix, out = "
            "backend->client response bodies); router only "
            "(docs/wire_format.md)",
            labels=("direction",))
        self.wire_stream_peak_chunk = r.gauge(
            "cluster_wire_stream_peak_chunk_bytes",
            "largest single buffer the streaming forward path has held "
            "for any request — bounded by the 64 KiB pump window no "
            "matter the pair size, which is the router's "
            "never-buffers-a-full-body guarantee")
        self.breaker_state = r.gauge(
            "cluster_breaker_state",
            "per-backend circuit-breaker state (0 = closed, 1 = open, "
            "2 = half_open); router only (docs/fault_tolerance.md "
            "\"Circuit breaker\")",
            labels=("backend",))
        self.breaker_transitions = r.counter(
            "cluster_breaker_transitions_total",
            "circuit-breaker state transitions per backend, by the state "
            "entered (open/half_open/closed) — the counter the chaos "
            "verdict asserts on (gauges race a recovery)",
            labels=("backend", "to"))
        self.hedges = r.counter(
            "cluster_hedges_total",
            "hedged cold-request forwards by outcome: fired (a hedge was "
            "launched after the hedge delay), won (the hedge's reply was "
            "used), lost (the primary answered first; the hedge socket "
            "was abandoned)",
            labels=("outcome",))

    def set_states(self, states: Dict[str, int]) -> None:
        """Overwrite the per-state replica gauge (absent states -> 0, so
        a replica leaving a state does not leave a stale sample)."""
        for state in ("starting", "ready", "draining", "drained",
                      "failed", "unreachable"):
            self.replicas.labels(state=state).set(states.get(state, 0))

    def render(self) -> str:
        return self.registry.render()
