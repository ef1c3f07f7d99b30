"""Profiling subsystem (utils/profiling.py) — SURVEY.md §5 tracing equivalent."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raftstereo_tpu.utils.profiling import LatencyHistogram, StepProfiler


def _work():
    x = jnp.ones((64, 64))
    return float(jax.jit(lambda a: (a @ a).sum())(x))


class TestStepProfiler:
    def test_disabled_by_default(self, tmp_path):
        prof = StepProfiler(str(tmp_path / "p"))
        assert not prof.enabled
        for i in range(3):
            with prof.step(i):
                _work()
        assert not os.path.exists(str(tmp_path / "p"))

    def test_window_traced_and_stopped(self, tmp_path):
        d = str(tmp_path / "p")
        prof = StepProfiler(d, start=1, stop=3)
        assert prof.enabled
        for i in range(5):
            with prof.step(i):
                _work()
        assert not prof._active
        files = glob.glob(os.path.join(d, "**", "*"), recursive=True)
        assert any(os.path.isfile(f) for f in files)

    def test_resume_inside_window_still_traces(self, tmp_path):
        """A resumed run whose first step index is already inside [start, stop)
        must trace the remainder, not silently no-op."""
        d = str(tmp_path / "p")
        prof = StepProfiler(d, start=0, stop=10)
        for i in (7, 8, 9):   # restored step > start
            with prof.step(i):
                _work()
        assert not prof._active
        files = glob.glob(os.path.join(d, "**", "*"), recursive=True)
        assert any(os.path.isfile(f) for f in files)

    def test_exception_inside_step_flushes_trace(self, tmp_path):
        prof = StepProfiler(str(tmp_path / "p"), start=0, stop=10)
        try:
            with prof.step(0):
                raise RuntimeError("step died")
        except RuntimeError:
            pass
        assert not prof._active   # trace stopped, not leaked

    def test_close_ends_open_trace(self, tmp_path):
        prof = StepProfiler(str(tmp_path / "p"), start=0, stop=100)
        with prof.step(0):
            _work()
        assert prof._active
        prof.close()
        assert not prof._active


class TestLatencyHistogram:
    def test_empty(self):
        h = LatencyHistogram()
        assert h.count == 0
        assert h.summary() == {"count": 0}
        assert np.isnan(h.percentile(50))

    def test_percentiles_on_uniform_data(self):
        h = LatencyHistogram(lo=1e-3, hi=10.0)
        for v in np.linspace(0.001, 1.0, 1000):
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == 1000
        assert s["mean"] == pytest.approx(0.5005, rel=1e-3)
        # Log-spaced buckets: estimates are bucket-resolution accurate.
        assert s["p50"] == pytest.approx(0.5, rel=0.3)
        assert s["p99"] == pytest.approx(0.99, rel=0.3)
        assert s["p50"] < s["p90"] <= s["p99"] <= s["max"] == 1.0

    def test_explicit_bounds_and_le_semantics(self):
        h = LatencyHistogram(bounds=(1, 2, 4, 8))
        for v in (1, 1, 2, 3, 5, 100):
            h.observe(v)
        cum = dict(h.cumulative())
        assert cum[1] == 2      # le="1" counts values <= 1
        assert cum[2] == 3
        assert cum[4] == 4
        assert cum[8] == 5
        assert cum[float("inf")] == 6  # overflow lands in +Inf only
        assert h.total == 112

    def test_quantile_is_percentile_rescaled(self):
        h = LatencyHistogram(lo=1e-3, hi=10.0)
        for v in np.linspace(0.001, 1.0, 1000):
            h.observe(float(v))
        for q in (0.0, 0.5, 0.9, 0.99, 1.0):
            assert h.quantile(q) == h.percentile(q * 100.0)
        with pytest.raises(AssertionError):
            h.quantile(50)         # percentile scale on the quantile API

    def test_quantile_empty_is_nan(self):
        assert np.isnan(LatencyHistogram().quantile(0.5))

    def test_reset(self):
        h = LatencyHistogram()
        h.observe(0.5)
        h.reset()
        assert h.count == 0 and h.summary() == {"count": 0}

    def test_thread_safety_totals(self):
        import threading

        h = LatencyHistogram(bounds=(0.5,))
        def hammer():
            for _ in range(1000):
                h.observe(0.1)
        ts = [threading.Thread(target=hammer) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert h.count == 4000
        assert dict(h.cumulative())[0.5] == 4000
