"""Tracing / profiling subsystem.

The reference's only observability is wall-clock FPS in the KITTI evaluator
(reference: evaluate_stereo.py:77-81,105-107).  The TPU-native equivalent is
the XLA profiler: device traces viewable in TensorBoard / Perfetto, over
which the program's own phases (``obs.Tracer.phase``) appear as host events.
This module wraps ``jax.profiler`` so the train CLI (``--profile_steps``) and
the serving front-end (``POST /debug/profile``) never import it directly, and
holds the fixed-bucket ``LatencyHistogram`` the metrics share.

Every capture is a LIGHT one (``_start_capture``): the Python tracer off and
the host tracer at the level that keeps ``TraceAnnotation``s and drops the
runtime's per-tile events, so the process that is profiled runs as it does
unprofiled (PERF.md section 3 has the measurement).
"""

from __future__ import annotations

import bisect
import contextlib
import logging
import math
import threading
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

__all__ = ["StepProfiler", "LatencyHistogram", "OnDemandProfiler",
           "ProfilerBusy"]

# TraceMe levels: 1 = annotations the program (and the runtime's coarse
# phases) emit, 2 adds the runtime's per-tile / per-transfer events — on a
# serving TPU host millions of them, which stretched the host phase
# between two dispatches from 0.017 to 0.3 s (PERF.md, PR 25).
HOST_TRACER_LEVEL = 1
PYTHON_TRACER_LEVEL = 0


def _start_capture(log_dir: str) -> None:
    """``jax.profiler.start_trace`` with the light options, then the
    ``obs.clock`` annotation that ties the program's clocks to the
    profiler's (obs/trace.py ``clock_annotation``)."""
    import jax

    from ..obs.trace import clock_annotation

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = HOST_TRACER_LEVEL
    options.python_tracer_level = PYTHON_TRACER_LEVEL
    jax.profiler.start_trace(log_dir, profiler_options=options)
    with clock_annotation():
        pass


def _stop_capture() -> None:
    import jax

    from ..obs.trace import clock_annotation

    with clock_annotation():
        pass
    jax.profiler.stop_trace()


class StepProfiler:
    """Trace a window of training steps [start, stop).

    Driven from a plain per-step ``step()`` call so the train loop stays
    branch-free; inside the window every step is bracketed by a
    ``StepTraceAnnotation`` the trace viewer correlates with the device
    operations launched inside it:

        prof = StepProfiler(log_dir, start=100, stop=105)
        for i in range(num_steps):
            with prof.step(i):
                train_step(...)
    """

    def __init__(self, log_dir: str, start: int = -1, stop: int = -1):
        self.log_dir = log_dir
        self.start, self.stop = start, stop
        self._active = False

    @property
    def enabled(self) -> bool:
        return 0 <= self.start < self.stop

    @contextlib.contextmanager
    def step(self, i: int) -> Iterator[None]:
        if not self.enabled:
            yield
            return
        import jax

        # >= not ==: a resumed run whose restored step is already inside (or
        # past the start of) the window must still trace the remainder.
        if self.start <= i < self.stop and not self._active:
            _start_capture(self.log_dir)
            self._active = True
            logger.info("Profiling steps [%d, %d) -> %s",
                        self.start, self.stop, self.log_dir)
        try:
            if self._active:
                with jax.profiler.StepTraceAnnotation("train", step_num=i):
                    yield
            else:
                yield
        except BaseException:
            # Flush the trace even when the profiled step dies — the data is
            # most wanted exactly then.
            self.close()
            raise
        if self._active and i >= self.stop - 1:
            self.close()
            logger.info("Profiler trace written to %s", self.log_dir)

    def close(self) -> None:
        if self._active:
            _stop_capture()
            self._active = False


def _log_spaced_bounds(lo: float, hi: float,
                       per_decade: int) -> Tuple[float, ...]:
    """Ascending bucket upper bounds, ``per_decade`` per factor of 10."""
    n = int(math.ceil(math.log10(hi / lo) * per_decade)) + 1
    return tuple(lo * 10 ** (i / per_decade) for i in range(n))


class LatencyHistogram:
    """Fixed-bucket histogram with percentile summaries, O(1) per observation.

    Default buckets are log-spaced (5 per decade) from 100 us to 60 s — wide
    enough for a compiled TPU forward on one end and a compile-included
    first request on the other.  Pass explicit ``bounds`` for non-latency
    quantities (e.g. batch sizes).  Thread-safe: the serve layer observes
    from the batcher worker while the HTTP threads render ``/metrics``.

    Percentiles are estimated by linear interpolation inside the containing
    bucket (clamped to the observed min/max), the standard fixed-bucket
    estimate Prometheus applies server-side — exact at bucket edges, off by
    at most one bucket width inside.
    """

    def __init__(self, bounds: Optional[Sequence[float]] = None,
                 lo: float = 1e-4, hi: float = 60.0, per_decade: int = 5):
        self.bounds: Tuple[float, ...] = (
            tuple(sorted(bounds)) if bounds is not None
            else _log_spaced_bounds(lo, hi, per_decade))
        self._lock = threading.Lock()
        # One count per bound plus the +Inf overflow bucket.
        self._counts = [0] * (len(self.bounds) + 1)  # guarded_by: _lock
        self._count = 0  # guarded_by: _lock
        self._sum = 0.0  # guarded_by: _lock
        self._min = math.inf  # guarded_by: _lock
        self._max = -math.inf  # guarded_by: _lock

    def observe(self, value: float) -> None:
        i = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += value
            self._min = min(self._min, value)
            self._max = max(self._max, value)

    @property
    def count(self) -> int:
        with self._lock:  # vs a concurrent observe() read-modify-write
            return self._count

    @property
    def total(self) -> float:
        with self._lock:
            return self._sum

    def _snapshot(self):
        """Counts/count/sum/min/max from ONE lock acquisition — derived
        views (percentiles, Prometheus series) must all come from the same
        snapshot or a concurrent observe() makes them mutually
        inconsistent (e.g. a +Inf bucket that disagrees with _count)."""
        with self._lock:
            return (list(self._counts), self._count, self._sum,
                    self._min, self._max)

    def _percentile_from(self, counts, n, vmin, vmax, q: float) -> float:
        if not n:
            return float("nan")
        rank = q / 100.0 * n
        cum = 0
        for i, c in enumerate(counts):
            if not c:
                continue
            if cum + c >= rank:
                lower = self.bounds[i - 1] if i > 0 else vmin
                upper = self.bounds[i] if i < len(self.bounds) else vmax
                frac = (rank - cum) / c
                v = lower + frac * (upper - lower)
                return min(max(v, vmin), vmax)
            cum += c
        return vmax

    def percentile(self, q: float) -> float:
        """Estimated q-th percentile (q in [0, 100]); NaN when empty."""
        counts, n, _, vmin, vmax = self._snapshot()
        return self._percentile_from(counts, n, vmin, vmax, q)

    def quantile(self, q: float) -> float:
        """Estimated q-quantile, q in [0, 1] — ``quantile(0.99)`` is
        ``percentile(99)``.  The SLO-spec convention (loadgen/slo.py,
        ``/debug/vars`` live percentiles) alongside the Prometheus-style
        ``percentile``; NaN when empty, asserts on out-of-range q."""
        assert 0.0 <= q <= 1.0, q
        return self.percentile(q * 100.0)

    def summary(self) -> Dict[str, float]:
        counts, n, total, vmin, vmax = self._snapshot()
        if not n:
            return {"count": 0}
        pct = lambda q: self._percentile_from(counts, n, vmin, vmax, q)  # noqa: E731
        return {
            "count": n,
            "total": total,
            "mean": total / n,
            "min": vmin,
            "max": vmax,
            "p50": pct(50),
            "p90": pct(90),
            "p99": pct(99),
        }

    def cumulative(self) -> List[Tuple[float, int]]:
        """(upper_bound, cumulative_count) pairs ending with (+inf, count) —
        the Prometheus ``_bucket{le=...}`` series.  See ``prometheus``
        for the series together with its consistent sum/count."""
        return self.prometheus()[0]

    def prometheus(self):
        """(bucket_pairs, count, sum) from one atomic snapshot, so the
        rendered ``_count`` always equals the ``le="+Inf"`` bucket."""
        counts, n, total, _, _ = self._snapshot()
        out, cum = [], 0
        for b, c in zip(self.bounds, counts):
            cum += c
            out.append((b, cum))
        out.append((math.inf, n))
        return out, n, total

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self.bounds) + 1)
            self._count = 0
            self._sum = 0.0
            self._min = math.inf
            self._max = -math.inf


class ProfilerBusy(RuntimeError):
    """An on-demand capture was requested while one is already running."""


class OnDemandProfiler:
    """Bounded on-demand ``jax.profiler`` windows (``POST /debug/profile``).

    One capture at a time, started from any thread, stopped by a timer
    thread after ``seconds`` — two overlapping windows would corrupt each
    other, and an uncapped one would grow a file without bound.  The
    capture is the light one of ``_start_capture`` (no Python tracer, no
    per-tile host events): the heavy trace has no documented user.
    """

    def __init__(self, log_dir: str = "profile",
                 max_seconds: float = 120.0):
        self.log_dir = log_dir
        self.max_seconds = max_seconds
        self._lock = threading.Lock()
        self._until: Optional[float] = None  # guarded_by: _lock
        self._captures = 0  # guarded_by: _lock

    @property
    def running(self) -> bool:
        with self._lock:
            return self._until is not None

    def start(self, seconds: float,
              log_dir: Optional[str] = None) -> Dict[str, object]:
        """Begin a capture of ``seconds``; raises ``ProfilerBusy`` when one
        is already running (the mutual exclusion the endpoint maps to HTTP
        409).  Returns ``{"log_dir", "seconds", "capture"}``."""
        seconds = float(seconds)
        if not 0 < seconds <= self.max_seconds:
            raise ValueError(
                f"seconds must be in (0, {self.max_seconds}], got {seconds}")
        target = log_dir or self.log_dir
        with self._lock:
            if self._until is not None:
                raise ProfilerBusy(
                    f"capture already running until ~{self._until:.1f} "
                    f"(perf_counter)")
            self._until = time.perf_counter() + seconds
            self._captures += 1
            capture = self._captures
        try:
            _start_capture(target)
        except BaseException:
            with self._lock:
                self._until = None
            raise
        logger.info("on-demand profile #%d: %.2fs -> %s",
                    capture, seconds, target)

        def _stop():
            time.sleep(seconds)
            try:
                _stop_capture()
                logger.info("on-demand profile #%d written to %s",
                            capture, target)
            finally:
                with self._lock:
                    self._until = None

        threading.Thread(target=_stop, daemon=True,
                         name=f"profile-stop-{capture}").start()
        return {"log_dir": target, "seconds": seconds, "capture": capture}
