"""RSA5xx — the metric-name/exposition lint, behind the analysis runner.

This is the runtime half of the suite (imports the metrics bundles, so
it needs the package importable — unlike the AST checkers): it
instantiates ``ServeMetrics`` + ``TrainMetrics`` on ONE registry (a name
collision between the bundles fails here instead of when both are
mounted on one process), runs the naming lint, populates one child per
labeled family and validates the full Prometheus 0.0.4 render.

Formerly ``scripts/check_metrics.py`` (PR 5); that script is now a thin
shim over this module so tier-1 has a single lint entry point
(``python -m raftstereo_tpu.analysis``).

Codes:

* RSA501 — metric-name lint violation (obs/prom.py ``lint_registry``).
* RSA502 — rendered exposition fails the format validator.
* RSA503 — serve/train bundles collide on one registry.
"""

from __future__ import annotations

from typing import List

from .core import Finding

__all__ = ["run_metrics_lint"]

# Findings anchor at the bundle definitions — the registry names are
# declared there, so that is where a violation is fixed.
_SERVE_PATH = "raftstereo_tpu/serve/metrics.py"
_TRAIN_PATH = "raftstereo_tpu/train/telemetry.py"
_LOADGEN_PATH = "raftstereo_tpu/loadgen/metrics.py"
_TIER_PATH = "raftstereo_tpu/stream/tier.py"
_OBS_PATH = "raftstereo_tpu/obs/fleet.py"


def run_metrics_lint() -> List[Finding]:
    """Instantiate + lint + render-validate the repo's metric bundles."""
    from ..loadgen.metrics import LoadgenMetrics
    from ..obs import (BurnRateAlerts, FleetFederator, lint_registry,
                       validate_prometheus)
    from ..serve.metrics import (ClusterMetrics, MetricsRegistry,
                                 ServeMetrics)
    from ..stream.tier import TierMetrics
    from ..train.telemetry import TrainMetrics

    findings: List[Finding] = []
    registry = MetricsRegistry()
    try:
        serve = ServeMetrics(registry)
        # The cluster dispatcher mounts its families on the SAME
        # registry as the serve bundle (server /metrics is one render),
        # so collisions between the two must fail here.
        cluster = ClusterMetrics(registry)
        TrainMetrics(registry)
        # Harness-side families (loadgen_*/slo_*): a soak rig may mount
        # them next to a scrape of any other bundle.
        loadgen = LoadgenMetrics(registry)
        # The durable session tier's families (tier_*): its own process
        # normally, but they must stay collision-free with the rest.
        tier = TierMetrics(registry)
        # The fleet observability plane (fleet_*): the router mounts
        # the federator's scrape counters and the burn-rate alert
        # gauges next to the cluster bundle — one registry, one render.
        federator = FleetFederator(registry)
        alerts = BurnRateAlerts(registry)
    except ValueError as e:  # duplicate registration across bundles
        return [Finding("RSA503", _TRAIN_PATH, 1,
                        f"bundle collision: {e}", "metrics")]
    for msg in lint_registry(registry.entries()):
        name = msg.split(":")[0]
        path = _TRAIN_PATH if name.startswith("train") \
            else _LOADGEN_PATH \
            if name.startswith(("loadgen", "slo", "chaos")) \
            else _TIER_PATH if name.startswith("tier") \
            else _OBS_PATH if name.startswith("fleet") \
            else _SERVE_PATH
        findings.append(Finding("RSA501", path, 1, msg, "metrics"))

    # Populate one child per labeled family (families render no samples
    # until first use) and validate the full exposition.
    serve.requests.labels(endpoint="predict", outcome="ok").inc()
    serve.tier_requests.labels(tier="default").inc()
    serve.compile_misses.labels(bucket="64x96", iters="8",
                                mode="batch", tier="fp32").inc()
    serve.compile_hits.labels(bucket="64x96", iters="8",
                              mode="stream", tier="bf16").inc()
    serve.batch_rows.labels(rows="8").inc()
    serve.stream_cold_frames.labels(reason="new").inc()
    serve.stream_tier_pushes.labels(outcome="ok").inc()
    serve.wire_bytes.labels(direction="in", format="binary").inc(1024)
    serve.wire_tiles.labels(direction="out", coding="stored").inc()
    serve.host_wait.labels(point="reply_lock").inc(0.25)
    serve.wire_negotiations.labels(request="binary",
                                   response="json").inc()
    serve.cascade_schedules.labels(schedule="int8:24+fp32:8").inc()
    serve.cascade_promotions.labels(kind="scheduled").inc()
    serve.cascade_iterations.labels(phase="certified").inc(8)
    serve.latency.observe(0.01)
    cluster.set_states({"ready": 1})
    cluster.queue_depth.labels(replica="r0").set(0)
    cluster.dispatch.labels(replica="r0", outcome="ok").inc()
    cluster.session_repins.labels(reason="draining").inc()
    cluster.session_handoffs.labels(outcome="warm").inc()
    cluster.autoscale_recommendation.set(0)
    cluster.probe_failures.labels(replica="r0").inc()
    cluster.router_latency.observe(0.001)
    cluster.capacity_headroom.set(0.5)
    cluster.wire_stream_bytes.labels(direction="in").inc(65536)
    cluster.wire_stream_peak_chunk.set(65536)
    cluster.breaker_state.labels(backend="b0").set(0)
    cluster.breaker_transitions.labels(backend="b0", to="open").inc()
    cluster.hedges.labels(outcome="won").inc()
    loadgen.requests.labels(outcome="ok", tier="default").inc()
    loadgen.chaos_actions.labels(kind="slow_replica",
                                 outcome="armed").inc()
    loadgen.send_lag.observe(0.001)
    loadgen.latency.observe(0.01)
    loadgen.slo_checks.labels(status="pass").inc()
    loadgen.slo_pass.set(1)
    tier.requests.labels(op="put", outcome="ok").inc()
    federator.scrapes.labels(backend="b0").inc()
    federator.scrape_failures.labels(backend="b0").inc()
    alerts.alert_state.labels(**{"class": "tier=*,priority=*"}).set(0)
    alerts.alert_burn.labels(**{"class": "tier=*,priority=*"}).set(0.0)
    for msg in validate_prometheus(registry.render()):
        findings.append(Finding("RSA502", _SERVE_PATH, 1, msg, "metrics"))
    return findings
