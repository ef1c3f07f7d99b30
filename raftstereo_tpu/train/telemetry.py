"""Train-side metrics bundle for the ``--metrics_port`` exporter.

Long TPU runs previously exposed their health only through the JSONL log
on disk (train/logger.py); this bundle mirrors the hot signals into a
``MetricsRegistry`` (serve/metrics.py) that ``obs.TelemetryServer`` serves
over HTTP, so a scraper sees steps/s, the data-wait fraction (is the TPU
idle waiting on the input pipeline?), the loader's self-healing gauges
(quarantines, resamples, pool recycles) and checkpoint-save latency live —
the same render format, validator and name lint as the serving metrics
(scripts/check_metrics.py keeps both namespaces collision-free).
"""

from __future__ import annotations

from typing import Dict, Optional

from ..serve.metrics import MetricsRegistry

__all__ = ["TrainMetrics"]

# Mirrors data/loader.DataLoader.health_metrics() keys; fixed here so the
# gauges exist (and lint) from step 0, not after the first incident.
_HEALTH_GAUGES = (
    ("data_samples_retried", "sample loads that needed a retry"),
    ("data_samples_quarantined", "dataset indices quarantined as bad"),
    ("data_samples_replaced", "quarantined samples deterministically "
                              "resampled"),
    ("data_load_timeouts", "worker batches that exceeded batch_timeout"),
    ("data_pool_recycles", "worker pools recycled after a timeout"),
)

# Steps/s smoothing: high enough to damp per-step jitter, low enough that
# a throughput regression shows within ~20 steps.
_RATE_DECAY = 0.9


class TrainMetrics:
    """Every instrument the train loop exports, in one bundle."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        r = registry or MetricsRegistry()
        self.registry = r
        self.steps = r.counter(
            "train_steps_total", "optimizer steps completed this process")
        self.steps_per_sec = r.gauge(
            "train_steps_per_sec",
            "recent throughput, EMA over (data wait + step) wall-clock")
        self.data_wait_frac = r.gauge(
            "train_data_wait_fraction",
            "cumulative fraction of loop wall-clock spent waiting on the "
            "input pipeline (the TPU-idle signal)")
        self.skipped = r.counter(
            "train_steps_skipped_total",
            "steps whose update was dropped (nan_policy=skip)")
        self.watchdog_slow = r.counter(
            "train_watchdog_slow_total",
            "steps flagged by the step watchdog (> watchdog_factor x "
            "running median)")
        self.step_seconds = r.histogram(
            "train_step_seconds",
            "device step wall-clock (dispatch through metrics fetch)",
            lo=1e-3, hi=600.0)
        self.data_wait_seconds = r.histogram(
            "train_data_wait_seconds",
            "host wall-clock blocked on the next prefetched batch",
            lo=1e-5, hi=600.0)
        self.checkpoint_seconds = r.histogram(
            "train_checkpoint_save_seconds",
            "CheckpointManager.save call wall-clock (async saves measure "
            "the dispatch, wait=True saves the full write)",
            lo=1e-3, hi=600.0)
        self.health = {name: r.gauge(name, help_)
                       for name, help_ in _HEALTH_GAUGES}
        # train_device_{bytes_in_use,peak_bytes_in_use,peak_bytes_reserved}
        r.device_memory_gauges("train")
        self._data_total = 0.0
        self._step_total = 0.0

    def observe_step(self, step_s: float, data_s: float) -> None:
        """Record one loop iteration's phase split."""
        self.steps.inc()
        self.step_seconds.observe(step_s)
        self.data_wait_seconds.observe(data_s)
        self._data_total += data_s
        self._step_total += step_s
        busy = self._data_total + self._step_total
        if busy > 0:
            self.data_wait_frac.set(self._data_total / busy)
        rate = 1.0 / max(step_s + data_s, 1e-9)
        prev = self.steps_per_sec.value
        self.steps_per_sec.set(
            rate if prev == 0.0
            else _RATE_DECAY * prev + (1 - _RATE_DECAY) * rate)

    def observe_health(self, health: Dict[str, float]) -> None:
        """Mirror ``DataLoader.health_metrics()`` (cumulative counts set
        as gauges) plus the loop's per-step flags."""
        for k, v in health.items():
            g = self.health.get(k)
            if g is not None:
                g.set(float(v))
        if health.get("watchdog_slow", 0.0) >= 0.5:
            self.watchdog_slow.inc()
