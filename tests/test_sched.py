"""Iteration-level continuous batching (raftstereo_tpu/serve/sched,
docs/serving.md "Scheduling").

Policy tests drive ``IterationScheduler.run_once`` directly against a
stub engine with an injected clock (no device, no threads) — join/leave
at boundaries, priority ordering with anti-starvation aging, deadline
early exit, timeouts/overload/shutdown.  Engine and end-to-end tests use
a tiny real model; the acceptance gate is ``test_e2e_...``: a 32-iter
request and concurrent 7-iter high-priority short jobs interleave with
ZERO XLA compiles beyond warmup (retrace-guard budget 0), results are
bitwise-identical to the monolithic executables of the same row count,
and the first short job overtakes the long one where the monolithic
micro-batcher, driven in the same test, makes it wait (no head-of-line
blocking).
"""

import dataclasses
import threading
import time

import numpy as np
import pytest

import jax

from raftstereo_tpu.config import (RAFTStereoConfig, SchedConfig,
                                   ServeConfig, StreamConfig)
from raftstereo_tpu.ops.image import BucketPadder
from raftstereo_tpu.serve import (BatchEngine, DynamicBatcher,
                                  IterationScheduler, Overloaded,
                                  RequestTimedOut, ServeClient, ServeError,
                                  ServeMetrics, ShuttingDown, StereoServer)
from raftstereo_tpu.serve.sched.policy import (effective_class,
                                               priority_class, should_exit)


# ----------------------------------------------------------------- fixtures

TINY = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
            corr_radius=2)


@pytest.fixture(scope="module")
def sched_model():
    from raftstereo_tpu.models import RAFTStereo

    model = RAFTStereo(RAFTStereoConfig(**TINY))
    variables = model.init(jax.random.key(0), (64, 96))
    return model, variables


@pytest.fixture(scope="module")
def sched_engine(sched_model):
    """One engine (and metrics bundle) shared by every device test in
    this module — XLA compiles are the expensive part, pay each once."""
    model, variables = sched_model
    cfg = _cfg(max_batch_size=4, queue_limit=32,
               request_timeout_ms=60000.0, iters=32, degraded_iters=7,
               degrade_queue_depth=10 ** 6)
    metrics = ServeMetrics()
    return BatchEngine(model, variables, cfg, metrics), cfg, metrics


def _full_rows(engine, pair, iters):
    """The monolithic answer at the scheduler's batch shape: the plain path
    pads a batch to the smallest row count that holds it, the scheduler
    always runs ``max_batch_size`` slots, and XLA promises equal bits only
    for equal program shapes — so the reference fills every row."""
    return engine.infer_batch([pair] * engine.cfg.max_batch_size, iters)[0]


def _img(h=60, w=90, seed=0):
    return np.random.default_rng(seed).integers(
        0, 255, (h, w, 3)).astype(np.float32)


def _cfg(**kw):
    sched_kw = {k[len("sched_"):]: kw.pop(k) for k in list(kw)
                if k.startswith("sched_")}
    base = dict(port=0, bucket_multiple=32, buckets=((60, 90),),
                warmup=False, max_batch_size=2, max_wait_ms=1.0,
                queue_limit=16, request_timeout_ms=5000.0, iters=4,
                degraded_iters=2, cold_buckets=False,
                sched=SchedConfig(**sched_kw))
    base.update(kw)
    return ServeConfig(**base)


class FakeClock:
    """Injected deterministic clock (the SessionStore test idiom)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class StubSchedEngine:
    """Phase-executable contract stand-in (no device): the carried state
    is each slot's identifying pixel value, a step advances the clock by
    ``step_cost``, the epilogue broadcasts the slot values — so tests
    can assert slot assignment, result routing and timing exactly."""

    def __init__(self, max_batch_size=2, clock=None, step_cost=0.0,
                 divis_by=32, bucket_multiple=32):
        self.max_batch_size = max_batch_size
        self.clock = clock
        self.step_cost = step_cost
        self.divis_by = divis_by
        self.bucket_multiple = bucket_multiple
        self.join_slots = []   # slots tuple per prologue call
        self.steps = 0

    def _padder(self, shape):
        return BucketPadder(shape, divis_by=self.divis_by,
                            bucket_multiple=self.bucket_multiple)

    def bucket_of(self, shape):
        return self._padder(shape).bucket_hw

    def padder_of(self, shape):
        return self._padder(shape)

    def infer_sched_prologue(self, pairs, flow_inits, slots, mode=None):
        hw = self.bucket_of(pairs[0][0].shape)
        vals = np.zeros(self.max_batch_size, np.float32)
        for (im1, _), s in zip(pairs, slots):
            vals[s] = float(im1.flat[0])
        self.join_slots.append(tuple(slots))
        return hw, {"vals": vals, "mode": mode}, False

    def infer_sched_join(self, hw, running, incoming, mask, mode=None):
        return {"vals": np.where(mask, incoming["vals"],
                                 running["vals"]), "mode": mode}, False

    def infer_sched_step(self, hw, state, iters_per_step, mode=None):
        self.steps += 1
        if self.clock is not None and self.step_cost:
            self.clock.advance(self.step_cost)
        return state, False

    def infer_sched_epilogue(self, hw, state, mode=None):
        b = self.max_batch_size
        low = np.zeros((b, hw[0] // 4, hw[1] // 4, 1), np.float32)
        up = np.tile(state["vals"][:, None, None, None],
                     (1, hw[0], hw[1], 1))
        return low, up, False


def _stub_sched(clock, step_cost=0.0, **cfg_kw):
    cfg = _cfg(**cfg_kw)
    eng = StubSchedEngine(max_batch_size=cfg.max_batch_size, clock=clock,
                          step_cost=step_cost)
    return eng, IterationScheduler(eng, cfg, now_fn=clock)


def _const_pair(value, h=60, w=90):
    img = np.full((h, w, 3), float(value), np.float32)
    return img, img


# ------------------------------------------------------------------- policy

class TestPolicy:
    def test_pure_policy_functions(self):
        assert priority_class("high") == 0
        assert priority_class("low") == 2
        with pytest.raises(ValueError, match="priority"):
            priority_class("urgent")
        # Aging: one class per starvation interval, floored at 0.
        assert effective_class(2, 0.0, 1.0) == 2
        assert effective_class(2, 1.5, 1.0) == 1
        assert effective_class(2, 9.0, 1.0) == 0
        # Leave decisions.
        assert should_exit(4, 4, 0.0, None, 10.0, 1.0) == (True, False)
        assert should_exit(3, 4, 0.0, None, 10.0, 1.0) == (False, False)
        assert should_exit(2, 8, 0.0, 2.5, 2.0, 1.0) == (True, True)
        assert should_exit(1, 8, 0.0, 2.5, 1.0, 1.0) == (False, False)

    def test_join_and_leave_at_iteration_boundaries(self):
        clock = FakeClock()
        eng, sched = _stub_sched(clock, max_batch_size=2)
        f1 = sched.submit(*_const_pair(1), iters=2)
        f2 = sched.submit(*_const_pair(2), iters=4)
        f3 = sched.submit(*_const_pair(3), iters=2)
        assert sched.queue_depth == 3
        sched.run_once()   # r1+r2 fill the batch; r3 waits
        assert eng.join_slots == [(0, 1)]
        assert sched.queue_depth == 1
        assert not f1.done()
        sched.run_once()   # r1 reaches 2 iters and leaves
        r1 = f1.result(timeout=1)
        assert (r1.iters, r1.degraded) == (2, False)
        assert r1.disparity.shape == (60, 90) and r1.disparity[0, 0] == 1.0
        sched.run_once()   # r3 joins the freed slot 0
        assert eng.join_slots == [(0, 1), (0,)]
        sched.run_once()   # r2 reaches 4, r3 reaches 2: both leave
        r2, r3 = f2.result(timeout=1), f3.result(timeout=1)
        assert r2.iters == 4 and r2.disparity[0, 0] == 2.0
        assert r3.iters == 2 and r3.disparity[0, 0] == 3.0
        assert r2.batch_slots == 2  # left from a shared running batch
        assert sched.run_once() is False  # drained: nothing left to do
        assert sched.stats()["active_slots"] == 0

    def test_priority_ordering_at_join(self):
        clock = FakeClock()
        eng, sched = _stub_sched(clock, max_batch_size=1)
        blocker = sched.submit(*_const_pair(9), iters=3)
        sched.run_once()
        f_low = sched.submit(*_const_pair(1), iters=1, priority="low")
        f_high = sched.submit(*_const_pair(2), iters=1, priority="high")
        while not blocker.done():
            sched.run_once()
        sched.run_once()   # the freed slot goes to HIGH despite later seq
        assert f_high.done() and not f_low.done()
        sched.run_once()
        assert f_low.result(timeout=1).priority == "low"

    def test_low_priority_is_not_starved(self):
        clock = FakeClock()
        eng, sched = _stub_sched(clock, step_cost=1.0, max_batch_size=1,
                                 sched_starvation_ms=2000.0)
        f_low = sched.submit(*_const_pair(1), iters=1, priority="low")
        highs = []
        for i in range(8):
            if f_low.done():
                break
            highs.append(sched.submit(*_const_pair(10 + i), iters=1,
                                      priority="high"))
            sched.run_once()
        # Aging promoted the low request past the steady high stream
        # (2 s/class at 1 s/boundary -> it wins by round 5), while the
        # early highs still went first.
        assert f_low.done(), "low-priority request starved"
        assert len(highs) >= 3 and highs[0].done()

    def test_deadline_early_exit_returns_anytime_result(self):
        clock = FakeClock()
        eng, sched = _stub_sched(clock, step_cost=1.0, max_batch_size=1)
        f = sched.submit(*_const_pair(5), iters=10, deadline_ms=2500.0)
        sched.run_once()   # est=1s; 1+1 < 2.5 -> keep iterating
        assert not f.done()
        sched.run_once()   # 2+1 > 2.5 -> early exit with 2 iters done
        res = f.result(timeout=1)
        assert res.degraded and res.iters == 2 and res.target_iters == 10
        assert res.disparity[0, 0] == 5.0  # the anytime result, not junk

    def test_timeout_overload_shutdown_and_validation(self):
        clock = FakeClock()
        eng, sched = _stub_sched(clock, step_cost=2.0, max_batch_size=1,
                                 queue_limit=2,
                                 request_timeout_ms=5000.0)
        blocker = sched.submit(*_const_pair(1), iters=8)
        sched.run_once()
        waiting = sched.submit(*_const_pair(2), iters=1)
        with pytest.raises(Overloaded):
            for i in range(3):
                sched.submit(*_const_pair(3 + i), iters=1)
        for _ in range(4):   # clock passes 5 s while the slot is held
            sched.run_once()
        with pytest.raises(RequestTimedOut):
            waiting.result(timeout=1)
        # Validation: target/priority/deadline checked at submit (400s).
        for kw in (dict(iters=0), dict(iters=10 ** 9),
                   dict(priority="bogus"), dict(deadline_ms=-3.0)):
            with pytest.raises(ValueError):
                sched.submit(*_const_pair(0), **kw)
        queued = sched.submit(*_const_pair(4), iters=1)
        sched.stop(drain=False)
        with pytest.raises(ShuttingDown):
            queued.result(timeout=1)
        with pytest.raises(ShuttingDown):
            sched.submit(*_const_pair(5), iters=1)
        assert not blocker.done()  # abandoned with the non-drain stop

    def test_iters_per_step_granularity(self):
        clock = FakeClock()
        eng, sched = _stub_sched(clock, max_batch_size=1,
                                 sched_iters_per_step=2, iters=4)
        with pytest.raises(ValueError, match="divisible"):
            sched.submit(*_const_pair(1), iters=3)
        f = sched.submit(*_const_pair(1), iters=4)
        sched.run_once()
        sched.run_once()
        assert f.result(timeout=1).iters == 4
        assert eng.steps == 2  # two boundaries of two iterations


# ----------------------------------------------------- engine + end-to-end

class TestSchedEngine:
    def test_warmup_budget_and_bitwise_parity(self, sched_engine,
                                              retrace_guard):
        """Cold path: the four phase executables compile exactly at
        warmup (retrace-guard budget 4 at the model-scale floor), and a
        scheduled request is bitwise-identical to the monolithic
        executable at equal (bucket, iters) — cold AND warm-start."""
        engine, cfg, metrics = sched_engine
        with retrace_guard(4, what="sched warmup: 4 phase executables",
                           min_duration_s=0.5) as cold:
            warmed = engine.warmup_sched()
        assert sorted(warmed) == [
            (64, 96, 0, "sched_epilogue", "passive", "fp32"),
            (64, 96, 0, "sched_join", "passive", "fp32"),
            (64, 96, 0, "sched_prologue", "passive", "fp32"),
            (64, 96, 1, "sched_step", "passive", "fp32")]
        # The step executable (the GRU body) is a model-scale compile:
        # if the 0.5 s floor ever rises above the real compile times, the
        # warm budget-0 guard below would pass vacuously — keep that loud.
        # (The tiny model's prologue/epilogue/join compile in
        # milliseconds, below the floor by design.)
        assert cold.compiles >= 1, cold.durations
        # Monolithic executables for the parity comparisons (and the
        # micro-batcher baseline in the e2e test).
        engine.warmup(iters_list=[7, 32])

        a, b = _img(60, 90, 1), _img(60, 90, 2)
        with IterationScheduler(engine, cfg, metrics) as sched:
            f_long = sched.submit(a, b, iters=32)
            f_short = sched.submit(b, a, iters=7, priority="high")
            r_long = f_long.result(timeout=300)
            r_short = f_short.result(timeout=300)
        assert (r_long.iters, r_long.degraded) == (32, False)
        np.testing.assert_array_equal(
            r_long.disparity, _full_rows(engine, (a, b), 32))
        np.testing.assert_array_equal(
            r_short.disparity, _full_rows(engine, (b, a), 7))

        # Warm start: a scheduled request with flow_init equals the
        # monolithic warm-start (stream) executable bitwise, low-res
        # session state included.
        init = r_short.disp_low
        mono_disp, mono_low, _ = engine.infer_stream_batch(
            [(b, a)], 7, [init])[0]
        with IterationScheduler(engine, cfg, metrics) as sched:
            r_warm = sched.submit(b, a, iters=7, flow_init=init,
                                  priority="high").result(timeout=300)
        np.testing.assert_array_equal(r_warm.disparity, mono_disp)
        np.testing.assert_array_equal(r_warm.disp_low, mono_low)

    def test_e2e_no_hol_blocking_zero_compiles(self, sched_engine,
                                               retrace_guard):
        """THE acceptance gate: a 32-iter request and concurrent 7-iter
        high-priority short jobs (the stream-frame profile) interleave
        with zero XLA compiles beyond warmup, the long answer stays
        bitwise-identical to the monolithic path, and the first short job
        through the scheduler is answered while the long one is still
        running, where the same workload through the monolithic
        micro-batcher makes it wait the long dispatch out — measured in
        the same test, by order and not by the clock (the batcher's lone
        long request now rides a one-row program, which on a CPU is
        shorter than the scheduler's four slots)."""
        engine, cfg, metrics = sched_engine
        if not engine.is_sched_warm((64, 96), 1):  # -k e2e runs alone
            engine.warmup_sched()
            engine.warmup(iters_list=[7, 32])
        a, b = _img(60, 90, 1), _img(60, 90, 2)
        n_short = 4

        def run_mixed(submit_long, submit_short):
            f_long = submit_long()
            time.sleep(0.05)  # the long request is in flight first
            overtook = []  # answered while the long one was running?
            for _ in range(n_short):
                submit_short().result(timeout=300)
                overtook.append(not f_long.done())
            return f_long.result(timeout=300), overtook

        with retrace_guard(0, what="steady-state join/leave traffic "
                                   "reuses warm executables",
                           min_duration_s=0.5):
            with IterationScheduler(engine, cfg, metrics) as sched:
                r_sched, over_sched = run_mixed(
                    lambda: sched.submit(a, b, iters=32),
                    lambda: sched.submit(b, a, iters=7, priority="high"))
            with DynamicBatcher(engine, cfg, metrics) as batcher:
                r_mono, over_mono = run_mixed(
                    lambda: batcher.submit(a, b, iters=32),
                    lambda: batcher.submit(b, a, iters=7))
        # Bitwise parity under interleaving: slot occupancy changed
        # round to round, the math did not.  (Against the monolithic
        # program of the scheduler's row count: the batcher's lone long
        # request rode the one-row program.)
        assert r_mono.iters == 32 and r_mono.batch_size == 1
        np.testing.assert_array_equal(r_sched.disparity,
                                      _full_rows(engine, (a, b), 32))
        assert r_sched.iters == 32 and not r_sched.degraded
        # No head-of-line blocking: through the batcher the first short
        # job waits out the whole 32-iter dispatch; through the scheduler
        # it joins the running batch at the next boundary and leaves 25
        # iterations before the long one.
        assert over_sched[0] and not any(over_mono), (over_sched, over_mono)
        assert metrics.sched_joins.value >= n_short + 1
        assert metrics.sched_leaves.value >= n_short + 1

    def test_http_e2e_sched_server(self, sched_engine, retrace_guard):
        """The wire: deadline/priority on /predict, session frames as
        high-priority scheduled jobs, sched blocks in /healthz and
        /debug/vars, validator-clean sched_* metrics — all with zero XLA
        compiles (the module engine is already warm)."""
        from raftstereo_tpu.obs import Tracer, validate_prometheus
        from raftstereo_tpu.stream.runner import StreamRunner

        engine, cfg, metrics = sched_engine
        if not engine.is_sched_warm((64, 96), 1):
            engine.warmup_sched()
        # Controller thresholds pinned out of reach: random-weight update
        # magnitudes would trip the trained-checkpoint-scale cold-reset
        # threshold, and this test measures the scheduling path, not
        # controller policy.
        http_cfg = dataclasses.replace(
            cfg, stream=StreamConfig(ladder=(14, 7), session_ttl_s=300.0,
                                     demote_threshold=0.0,
                                     promote_threshold=1e6,
                                     cold_reset_threshold=2e6),
            request_timeout_ms=120000.0)
        tracer = Tracer(capacity=512)
        scheduler = IterationScheduler(engine, http_cfg, metrics,
                                       tracer=tracer).start()
        stream = StreamRunner(engine, http_cfg.stream, metrics,
                              tracer=tracer, scheduler=scheduler)
        server = StereoServer(http_cfg, engine, None, metrics,
                              stream=stream, tracer=tracer,
                              scheduler=scheduler)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        client = ServeClient("127.0.0.1", server.port, timeout=300)
        a, b = _img(60, 90, 3), _img(60, 90, 4)
        try:
            with retrace_guard(0, what="sched HTTP traffic is warm",
                               min_duration_s=0.5):
                disp, meta = client.predict(a, b, iters=9, priority="low")
                assert meta["iters"] == 9 and meta["priority"] == "low"
                assert disp.shape == (60, 90) and not meta["degraded"]
                # Arbitrary iteration targets are a sched-mode feature —
                # 9 is served by the same step executable (the monolithic
                # server would 400 it), zero compiles as guarded.
                disp, meta = client.predict(a, b, deadline_ms=1.0)
                assert meta["degraded"] and meta["iters"] \
                    < meta["target_iters"]
                for i in range(3):
                    disp, meta = client.predict(a, b, session_id="cam0",
                                                seq_no=i)
                assert meta["warm"] and meta["iters"] == 7
                health = client.healthz()
                assert health["sched"]["iters_per_step"] == 1
                assert set(health["sched"]["queue_depth_by_priority"]) \
                    == {"high", "normal", "low"}
                text = client.metrics_text()
                assert validate_prometheus(text) == []
                for family in ("sched_joins_total", "sched_leaves_total",
                               "sched_early_exits_total",
                               "sched_slots_active"):
                    assert any(line.startswith(family)
                               for line in text.splitlines()), family
                for kw in (dict(iters=10 ** 6), dict(priority="bogus"),
                           dict(session_id="cam0", priority="high")):
                    with pytest.raises(ServeError) as ei:
                        client.predict(a, b, **kw)
                    assert ei.value.status == 400
            client.close()
        finally:
            server.close()
            thread.join(10)

    def test_run_load_against_a_sched_server(self, sched_engine,
                                             retrace_guard):
        """The load generator's own counts through the scheduler path:
        closed-loop traffic at an iteration count no monolithic program
        was compiled for is all answered, none an error, with zero
        compiles (the step executable serves any count)."""
        from raftstereo_tpu.serve import run_load

        engine, cfg, metrics = sched_engine
        if not engine.is_sched_warm((64, 96), 1):
            engine.warmup_sched()
        http_cfg = dataclasses.replace(cfg, request_timeout_ms=120000.0)
        scheduler = IterationScheduler(engine, http_cfg, metrics).start()
        server = StereoServer(http_cfg, engine, None, metrics,
                              scheduler=scheduler)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        joins0 = metrics.sched_joins.value
        try:
            pair = (_img(60, 90, 5), _img(60, 90, 6))
            with retrace_guard(0, what="sched load-gen traffic is warm",
                               min_duration_s=0.5):
                stats = run_load("127.0.0.1", server.port, lambda i: pair,
                                 requests=8, concurrency=3, iters=5)
            assert stats["ok"] == 8 and stats["error"] == 0, stats
            assert stats["p99_ms"] > 0
            assert metrics.sched_joins.value - joins0 >= 8
        finally:
            server.close()
            thread.join(10)

    def test_monolithic_server_rejects_sched_fields(self, sched_model):
        """Without --sched, deadline_ms/priority are a clear 400, not a
        silent ignore."""
        from raftstereo_tpu.serve import build_server

        model, variables = sched_model
        cfg = _cfg(sched=None, warmup=False, request_timeout_ms=120000.0)
        server = build_server(model, variables, cfg, ServeMetrics())
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        client = ServeClient("127.0.0.1", server.port, timeout=300)
        try:
            with pytest.raises(ServeError) as ei:
                client.predict(_img(), _img(), priority="high")
            assert ei.value.status == 400
            assert "--sched" in str(ei.value)
        finally:
            client.close()
            server.close()
            thread.join(10)
