"""Dynamic-batching inference serving subsystem (docs/serving.md).

Layers, bottom-up:

* ``engine.BatchEngine``    — shape-bucketed, padded-batch compile cache
                              around the test-mode forward, with startup
                              warmup (shares ``ops/image.BucketPadder``
                              with the Evaluator, bitwise).
* ``batcher.DynamicBatcher``— deadline-aware micro-batching, bounded-queue
                              admission control, per-request timeouts, and
                              load-adaptive GRU-iteration degradation.
* ``sched``                 — iteration-level continuous batching
                              (``--sched``): a per-request scheduler over
                              the engine's prologue/step/epilogue phase
                              executables — requests join/leave one
                              running batch per bucket at iteration
                              boundaries (priorities with anti-starvation
                              aging, deadline-aware anytime early exit,
                              no head-of-line blocking; docs/serving.md
                              "Scheduling").
* ``cascade``               — speculative tier cascades (``--cascades``):
                              schedule grammar, divergence-trigger
                              policy and the cheap-to-certified state
                              handoff — most GRU iterations on a cheap
                              precision tier, the last K on the
                              certified fp32 executables (docs/serving.md
                              "Tier cascade").
* ``metrics``               — counters / gauges / latency histograms with
                              Prometheus text exposition.
* ``server.StereoServer``   — stdlib HTTP front-end: ``/predict``,
                              ``/metrics``, ``/healthz``, ``/debug/*``
                              (per-request traces keyed by X-Request-Id,
                              on-demand XLA profile, thread dump, vars —
                              raftstereo_tpu.obs, docs/observability.md).
* ``client``                — blocking client + closed/open-loop load
                              generator.

Entry point: ``python -m raftstereo_tpu.cli.serve``; the benchmark that
measures it on the chip is ``benchmark/run.py`` (PERF.md).

Video streams ride the same engine: ``/predict`` with ``session_id``/
``seq_no`` warm-starts each frame from the session's previous disparity
through the engine's warm-start executables (``infer_stream_batch``),
with per-stream state and the adaptive iteration ladder living in the
``raftstereo_tpu.stream`` package (docs/streaming.md).
"""

import importlib

# Lazy (PEP 562) exports: importing this package must stay cheap so the
# model-free surfaces (cli.router, serve/cluster/router.py, client-side
# tooling) never drag in the engine/model stack — ``BatchEngine`` pulls
# jax + flax + the model, which a proxy or load-gen process has no use
# for.  ``from raftstereo_tpu.serve import X`` works unchanged; the
# submodule is imported on first attribute access.
_EXPORTS = {
    "DynamicBatcher": ".batcher",
    "Future": ".batcher",
    "Overloaded": ".batcher",
    "RequestTimedOut": ".batcher",
    "ServeResult": ".batcher",
    "ShuttingDown": ".batcher",
    "ServeClient": ".client",
    "ServeError": ".client",
    "run_load": ".client",
    "synthetic_pair_pool": ".client",
    "ClusterDispatcher": ".cluster",
    "ReplicaSet": ".cluster",
    "StereoRouter": ".cluster",
    "build_router": ".cluster",
    "BatchEngine": ".engine",
    "CascadeSchedule": ".cascade",
    "cheapest": ".cascade",
    "handoff_state": ".cascade",
    "parse_schedule": ".cascade",
    "validate_schedule": ".cascade",
    "ClusterMetrics": ".metrics",
    "Counter": ".metrics",
    "Gauge": ".metrics",
    "MetricsRegistry": ".metrics",
    "ServeMetrics": ".metrics",
    "IterationScheduler": ".sched",
    "SchedResult": ".sched",
    "StereoServer": ".server",
    "build_server": ".server",
    "decode_array": ".server",
    "encode_array": ".server",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    try:
        rel = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    value = getattr(importlib.import_module(rel, __name__), name)
    globals()[name] = value  # cache: next access skips __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
