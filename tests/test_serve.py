"""Serving subsystem (raftstereo_tpu/serve, docs/serving.md).

Batcher policy tests run against a stub engine (no model cost) so timing
assertions stay tight; engine and end-to-end tests use a tiny real model.
The end-to-end test is the subsystem's acceptance gate: concurrent
mixed-shape requests over real HTTP, one compile per (bucket, row count)
met, responses bitwise-equal to the Evaluator at the dispatch's row
count, overload sheds rather than deadlocks, metrics non-zero.
"""

import contextlib
import json
import threading
import time
import types

import numpy as np
import pytest

import jax

from raftstereo_tpu.config import RAFTStereoConfig, ServeConfig
from raftstereo_tpu.ops.image import BucketPadder
from raftstereo_tpu.serve import (BatchEngine, DynamicBatcher, Overloaded,
                                  RequestTimedOut, ServeClient, ServeMetrics,
                                  build_server, decode_array, encode_array,
                                  run_load)

from test_wire import _tiles


# ----------------------------------------------------------------- fixtures

TINY = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
            corr_radius=2)


@pytest.fixture(scope="module")
def serve_model():
    from raftstereo_tpu.models import RAFTStereo

    model = RAFTStereo(RAFTStereoConfig(**TINY))
    variables = model.init(jax.random.key(0), (64, 96))
    return model, variables


class StubEngine:
    """Batcher-contract stand-in: records (size, iters) per dispatch."""

    def __init__(self, delay=0.0, gate=None, divis_by=32, bucket_multiple=32):
        self.batches = []
        self.delay = delay
        self.gate = gate  # threading.Event the dispatch blocks on
        self.divis_by = divis_by
        self.bucket_multiple = bucket_multiple

    def bucket_of(self, shape):
        return BucketPadder(shape, divis_by=self.divis_by,
                            bucket_multiple=self.bucket_multiple).bucket_hw

    def infer_batch(self, pairs, iters, mode=None):
        if self.gate is not None:
            self.gate.wait(10.0)
        if self.delay:
            time.sleep(self.delay)
        self.batches.append((len(pairs), iters))
        return [np.zeros(p[0].shape[:2], np.float32) for p in pairs]


def _img(h=60, w=90, seed=0):
    return np.random.default_rng(seed).integers(
        0, 255, (h, w, 3)).astype(np.float32)


def _cfg(**kw):
    base = dict(port=0, bucket_multiple=32, buckets=((60, 90),),
                warmup=False, max_batch_size=4, max_wait_ms=40.0,
                queue_limit=32, request_timeout_ms=5000.0, iters=8,
                degraded_iters=2, degrade_queue_depth=16)
    base.update(kw)
    return ServeConfig(**base)


# ------------------------------------------------------------------ batcher

class TestBatcher:
    def test_batch_coalesces_to_max_size_before_deadline(self):
        eng = StubEngine()
        with DynamicBatcher(eng, _cfg(max_wait_ms=2000.0)) as b:
            t0 = time.perf_counter()
            futs = [b.submit(_img(), _img()) for _ in range(4)]
            res = [f.result(timeout=10) for f in futs]
        # Size bound, not the 2 s deadline, closed the batch.
        assert time.perf_counter() - t0 < 1.0
        assert eng.batches == [(4, 8)]
        assert all(r.batch_size == 4 and not r.degraded for r in res)

    def test_partial_batch_flushes_at_deadline(self):
        eng = StubEngine()
        with DynamicBatcher(eng, _cfg(max_wait_ms=60.0,
                                      max_batch_size=8)) as b:
            t0 = time.perf_counter()
            futs = [b.submit(_img(), _img()) for _ in range(2)]
            for f in futs:
                f.result(timeout=10)
            elapsed = time.perf_counter() - t0
        assert eng.batches == [(2, 8)]
        assert elapsed >= 0.05  # held for the deadline, then flushed

    def test_mixed_buckets_batch_separately(self):
        eng = StubEngine()
        with DynamicBatcher(eng, _cfg(max_wait_ms=30.0)) as b:
            futs = [b.submit(_img(60, 90), _img(60, 90)) for _ in range(2)]
            futs += [b.submit(_img(70, 100), _img(70, 100))
                     for _ in range(2)]
            for f in futs:
                f.result(timeout=10)
        assert sorted(s for s, _ in eng.batches) == [2, 2]

    def test_full_queue_sheds_then_recovers(self):
        gate = threading.Event()
        eng = StubEngine(gate=gate)
        cfg = _cfg(queue_limit=4, max_batch_size=2, max_wait_ms=1.0)
        metrics = ServeMetrics()
        b = DynamicBatcher(eng, cfg, metrics).start()
        try:
            # The worker pops up to max_batch_size and blocks on the gate;
            # keep submitting until the queue itself is full.
            futs = []
            deadline = time.perf_counter() + 5.0
            with pytest.raises(Overloaded):
                while time.perf_counter() < deadline:
                    futs.append(b.submit(_img(), _img()))
            assert metrics.shed.value >= 1
            gate.set()  # un-block: everything admitted must complete
            res = [f.result(timeout=10) for f in futs]
            assert len(res) == len(futs)
            assert metrics.responses.value == len(futs)
        finally:
            gate.set()
            b.stop()

    def test_degraded_iters_kick_in_and_recover(self):
        gate = threading.Event()
        eng = StubEngine(gate=gate)
        cfg = _cfg(max_batch_size=2, max_wait_ms=1.0, iters=8,
                   degraded_iters=2, degrade_queue_depth=4, queue_limit=32)
        metrics = ServeMetrics()
        b = DynamicBatcher(eng, cfg, metrics).start()
        try:
            # Park the batcher: the sentinel blocks on the gate, a full
            # batch is launched behind it, and with two in flight nothing
            # more closes — the backlog below builds up deterministically.
            parked = []
            for n in (1, 2):
                parked += [b.submit(_img(), _img()) for _ in range(n)]
                deadline = time.perf_counter() + 5.0
                while b.queue_depth and time.perf_counter() < deadline:
                    time.sleep(0.002)
            futs = [b.submit(_img(), _img()) for _ in range(8)]
            gate.set()
            for f in parked:
                f.result(timeout=10)
            res = [f.result(timeout=10) for f in futs]
        finally:
            gate.set()
            b.stop()
        assert eng.batches[:2] == [(1, 8), (2, 8)]  # the parked two
        iters_used = [it for _, it in eng.batches[2:]]
        # Backlogs drain 8 -> 6 -> 4 -> 2 in batches of 2: the first three
        # cross the threshold (4) and degrade, the last recovers to full.
        assert iters_used == [2, 2, 2, 8]
        assert metrics.degraded_batches.value == 3
        assert [r.degraded for r in res] == [True] * 6 + [False] * 2
        assert all(r.iters == (2 if r.degraded else 8) for r in res)

    def test_request_timeout_fails_late_requests(self):
        eng = StubEngine()
        cfg = _cfg(max_batch_size=8, max_wait_ms=120.0,
                   request_timeout_ms=20.0)
        metrics = ServeMetrics()
        with DynamicBatcher(eng, cfg, metrics) as b:
            fut = b.submit(_img(), _img())
            # Alone in the queue: held for the 120 ms fill deadline, which
            # exceeds its own 20 ms timeout -> failed, never dispatched.
            with pytest.raises(RequestTimedOut):
                fut.result(timeout=10)
        assert metrics.timeouts.value == 1
        assert eng.batches == []

    def test_explicit_iters_respected_and_grouped(self):
        eng = StubEngine()
        with DynamicBatcher(eng, _cfg(max_wait_ms=30.0)) as b:
            f1 = [b.submit(_img(), _img(), iters=3) for _ in range(2)]
            f2 = [b.submit(_img(), _img()) for _ in range(2)]
            res1 = [f.result(timeout=10) for f in f1]
            [f.result(timeout=10) for f in f2]
        assert sorted(eng.batches) == [(2, 3), (2, 8)]
        assert all(r.iters == 3 and not r.degraded for r in res1)


class RowCountStub(StubEngine):
    """A stub that names its compiled row counts, as ``BatchEngine`` does,
    and records which requests (their left image's first value) rode in
    each dispatch."""

    def __init__(self, row_counts, **kw):
        super().__init__(**kw)
        if row_counts is not None:
            self.row_counts = row_counts
        self.rode = []

    def infer_batch(self, pairs, iters, mode=None):
        out = super().infer_batch(pairs, iters, mode=mode)
        self.rode.append([int(p[0][0, 0, 0]) for p in pairs])
        return out


def _tagged(i):
    """A pair whose left image carries ``i`` — the request's identity in
    ``RowCountStub.rode``."""
    return np.full((60, 90, 3), i, np.float32), _img()


class TestBatcherTakeRule:
    """A closing batch takes the largest compiled row count the queued
    rows fill and leaves the rest queued, first in first out."""

    @staticmethod
    @contextlib.contextmanager
    def _parked(eng, cfg, metrics=None):
        """A started batcher whose worker is parked in the engine on a
        sentinel request, so what is submitted inside queues up behind
        it; leaving releases the gate and waits the sentinel out."""
        b = DynamicBatcher(eng, cfg, metrics).start()
        try:
            sentinel = b.submit(*_tagged(-1))
            deadline = time.perf_counter() + 5.0
            while b.queue_depth and time.perf_counter() < deadline:
                time.sleep(0.002)
            yield b
            eng.gate.set()
            sentinel.result(timeout=10)
        finally:
            eng.gate.set()
            b.stop()

    @classmethod
    def _drain(cls, eng, cfg, n, metrics=None):
        """Queue ``n`` tagged requests behind a parked worker, release:
        the dispatches that answered them."""
        with cls._parked(eng, cfg, metrics) as b:
            futs = [b.submit(*_tagged(i)) for i in range(n)]
            time.sleep(0.02)  # every deadline (1 ms) has passed
        res = [f.result(timeout=10) for f in futs]
        assert eng.rode[0] == [-1]
        return eng.rode[1:], res

    @pytest.mark.parametrize("queued,want", [
        (1, [1]), (7, [1] * 7), (8, [8]), (11, [8, 1, 1, 1]),
        (17, [8, 8, 1])])
    def test_takes_largest_compiled_count_and_leaves_the_rest(
            self, queued, want):
        eng = RowCountStub((1, 8), gate=threading.Event())
        cfg = _cfg(max_batch_size=8, max_wait_ms=1.0, queue_limit=64,
                   degrade_queue_depth=64)
        metrics = ServeMetrics()
        rode, res = self._drain(eng, cfg, queued, metrics)
        assert [len(r) for r in rode] == want
        # first in, first out: across dispatches and inside one
        assert [i for r in rode for i in r] == list(range(queued))
        # a reply says the size of the dispatch it rode in
        assert [r.batch_size for r in res] \
            == [len(r) for r in rode for _ in r]
        assert metrics.responses.value == queued + 1
        assert metrics.batch_size.count == len(want) + 1

    @pytest.mark.parametrize("queued,want", [
        (3, [3]), (7, [7]), (11, [8, 3])])
    def test_engine_without_counts_takes_what_is_queued(self, queued, want):
        eng = RowCountStub(None, gate=threading.Event())
        assert not hasattr(eng, "row_counts")
        cfg = _cfg(max_batch_size=8, max_wait_ms=1.0, queue_limit=64,
                   degrade_queue_depth=64)
        rode, _ = self._drain(eng, cfg, queued)
        assert [len(r) for r in rode] == want
        assert [i for r in rode for i in r] == list(range(queued))

    def test_a_mid_size_count_is_taken_when_the_engine_has_one(self):
        """The rule is written over the tuple: an engine with a four-row
        program gets four of six queued rows, then one and one."""
        eng = RowCountStub((1, 4, 8), gate=threading.Event())
        cfg = _cfg(max_batch_size=8, max_wait_ms=1.0, queue_limit=64,
                   degrade_queue_depth=64)
        rode, _ = self._drain(eng, cfg, 6)
        assert [len(r) for r in rode] == [4, 1, 1]

    def test_backlog_counts_the_rows_left_queued(self):
        """Degradation reads the backlog at batch close, the rows a take
        leaves behind included: 7 queued run as singles at backlogs
        7, 6, ... 1 — degraded while the backlog is >= 4."""
        eng = RowCountStub((1, 8), gate=threading.Event())
        cfg = _cfg(max_batch_size=8, max_wait_ms=1.0, queue_limit=64,
                   iters=8, degraded_iters=2, degrade_queue_depth=4)
        metrics = ServeMetrics()
        _, res = self._drain(eng, cfg, 7, metrics)
        assert [r.degraded for r in res] == [True] * 4 + [False] * 3
        assert [it for _, it in eng.batches[1:]] == [2] * 4 + [8] * 3
        assert metrics.degraded_batches.value == 4

    def test_timed_out_rows_leave_with_the_batch_and_are_not_counted(self):
        """Requests past ``request_timeout_ms`` head the queue: they are
        failed with the batch that closes, and the rows taken are counted
        among the live ones — the engine still gets a compiled count."""
        eng = RowCountStub((1, 8), gate=threading.Event())
        cfg = _cfg(max_batch_size=8, max_wait_ms=1.0, queue_limit=64,
                   request_timeout_ms=150.0, degrade_queue_depth=64)
        metrics = ServeMetrics()
        with self._parked(eng, cfg, metrics) as b:
            stale = [b.submit(*_tagged(100 + i)) for i in range(3)]
            time.sleep(0.2)  # the three are past their time-out
            fresh = [b.submit(*_tagged(i)) for i in range(8)]
        for f in stale:
            with pytest.raises(RequestTimedOut):
                f.result(timeout=10)
        res = [f.result(timeout=10) for f in fresh]
        assert eng.rode == [[-1], list(range(8))]
        assert all(r.batch_size == 8 for r in res)
        assert metrics.timeouts.value == 3


class HalvesStub(StubEngine):
    """The engine contract in two halves.  ``launch_batch`` records the
    call and returns; ``finish_batch`` blocks on the gate of its batch
    (keyed by the tag of its first row, see ``_tagged``), then records
    itself.  ``calls`` is the order the batcher made them in."""

    def __init__(self, row_counts=(1, 4), fail_launch=(), fail_finish=()):
        super().__init__()
        if row_counts is not None:
            self.row_counts = row_counts
        self.fail_launch, self.fail_finish = fail_launch, fail_finish
        self.calls = []
        self._gates = {}
        self._lock = threading.Lock()

    def gate_of(self, tag):
        with self._lock:
            return self._gates.setdefault(tag, threading.Event())

    def open_all(self, tags):
        for tag in tags:
            self.gate_of(tag).set()

    def launch_batch(self, pairs, iters, mode=None):
        tags = [int(p[0][0, 0, 0]) for p in pairs]
        with self._lock:
            self.calls.append(("launch", tags))
        if tags[0] in self.fail_launch:
            raise RuntimeError(f"launch of {tags} failed")
        return types.SimpleNamespace(tags=tags, iters=iters, segments=None)

    def finish_batch(self, pending):
        assert self.gate_of(pending.tags[0]).wait(10.0)
        with self._lock:
            self.calls.append(("finish", pending.tags))
        if pending.tags[0] in self.fail_finish:
            raise RuntimeError(f"finish of {pending.tags} failed")
        return [np.full((60, 90), t, np.float32) for t in pending.tags]

    def launches(self):
        with self._lock:
            return [tags for what, tags in self.calls if what == "launch"]

    def most_in_flight(self):
        """Launched and not yet finished, at its highest over ``calls``
        (a failed launch never flew)."""
        flying = most = 0
        with self._lock:
            for what, tags in self.calls:
                if tags[0] in self.fail_launch:
                    continue
                flying += 1 if what == "launch" else -1
                most = max(most, flying)
        return most


def _until(cond, what, timeout=10.0):
    """Wait for the batcher's threads to get somewhere (no wall-clock
    assertion: the bound is only there to fail instead of hanging)."""
    deadline = time.perf_counter() + timeout
    while not cond():
        assert time.perf_counter() < deadline, f"never happened: {what}"
        time.sleep(0.002)


class TestLaunchAhead:
    """Launch-ahead of depth one: with one dispatch in flight a batch
    closes only when the queued rows fill the largest compiled count,
    and is launched behind the running one; at most two fly."""

    @staticmethod
    @contextlib.contextmanager
    def _running(eng, **cfg_kw):
        """A started batcher with request 0 launched alone and held in
        ``finish`` behind its gate; on leaving every gate opens."""
        cfg = _cfg(**{"max_batch_size": 4, "max_wait_ms": 1.0,
                      "queue_limit": 64, "degrade_queue_depth": 64,
                      **cfg_kw})
        metrics = ServeMetrics()
        b = DynamicBatcher(eng, cfg, metrics).start()
        try:
            first = b.submit(*_tagged(0))
            _until(lambda: eng.launches() == [[0]], "request 0 launched")
            yield b, metrics, first
        finally:
            eng.open_all(range(64))
            b.stop()

    @pytest.mark.parametrize("max_batch,row_counts", [
        (4, (1, 4)), (4, None), (1, (1,)), (8, (1, 4, 8))])
    def test_full_batch_is_launched_before_the_running_one_finishes(
            self, max_batch, row_counts):
        """Call order launch A, launch B, finish A, finish B — with
        ``max_batch_size`` 1 one row is a full batch."""
        eng = HalvesStub(row_counts)
        with self._running(eng, max_batch_size=max_batch) as (b, metrics,
                                                              first):
            tags = list(range(1, max_batch + 1))
            futs = [b.submit(*_tagged(t)) for t in tags]
            _until(lambda: eng.launches() == [[0], tags],
                   "the full batch launched behind the running one")
            assert not first.done()
            eng.open_all([0, 1])
            res = [f.result(timeout=10) for f in [first] + futs]
        assert eng.calls == [("launch", [0]), ("launch", tags),
                             ("finish", [0]), ("finish", tags)]
        assert [int(r.disparity[0, 0]) for r in res] == [0] + tags
        assert [r.batch_size for r in res] == [1] + [max_batch] * max_batch
        assert metrics.launched_ahead.value == 1
        assert metrics.batch_size.count == 2

    @pytest.mark.parametrize("row_counts,then", [
        ((1, 4), [[1], [2], [3]]), (None, [[1, 2, 3]]),
        ((1, 2, 4), [[1, 2], [3]])])
    def test_partial_queue_waits_and_is_taken_by_the_old_rule(
            self, row_counts, then):
        """Three rows of a possible four are NOT launched ahead; the
        moment the running dispatch is answered the rule for no
        dispatch in flight takes them (their deadline has passed)."""
        eng = HalvesStub(row_counts)
        with self._running(eng) as (b, metrics, first):
            futs = [b.submit(*_tagged(t)) for t in (1, 2, 3)]
            _until(lambda: b.queue_depth == 3, "three rows queued")
            time.sleep(0.05)  # fifty deadlines: time to get it wrong
            assert eng.calls == [("launch", [0])]
            eng.open_all([1, 2, 3])  # only request 0 holds anything up
            eng.gate_of(0).set()
            res = [f.result(timeout=10) for f in futs]
        want = [("launch", [0]), ("finish", [0])]
        for tags in then:
            want += [("launch", tags), ("finish", tags)]
        assert eng.calls == want
        assert [int(r.disparity[0, 0]) for r in res] == [1, 2, 3]
        assert metrics.launched_ahead.value == 0

    def test_never_more_than_two_in_flight_and_replies_in_launch_order(
            self):
        eng = HalvesStub((1, 4))
        answered = []
        with self._running(eng) as (b, metrics, first):
            first.add_done_callback(lambda f: answered.append(0))
            futs = []
            for t in range(1, 13):  # three full batches behind request 0
                futs.append(b.submit(*_tagged(t)))
                futs[-1].add_done_callback(
                    lambda f, t=t: answered.append(t))
            _until(lambda: len(eng.launches()) == 2, "one batch ahead")
            _until(lambda: b.queue_depth == 8, "two batches queued")
            time.sleep(0.05)
            assert eng.launches() == [[0], [1, 2, 3, 4]]  # and no third
            # one answered, one launched: each behind a batch still held
            for tag, n in ((0, 3), (1, 4)):
                eng.gate_of(tag).set()
                _until(lambda: len(eng.launches()) == n,
                       f"launch {n} once batch {tag} was answered")
            eng.open_all(range(13))
            for f in futs:
                f.result(timeout=10)
        assert eng.launches() == [[0], [1, 2, 3, 4], [5, 6, 7, 8],
                                  [9, 10, 11, 12]]
        assert eng.most_in_flight() == 2
        assert answered == list(range(13))
        # every full batch found one in flight when it closed
        assert metrics.launched_ahead.value == 3

    @pytest.mark.parametrize("where,failing", [
        ("finish", 0), ("finish", 1), ("launch", 1)])
    def test_a_failed_dispatch_fails_its_own_batch_and_nothing_else(
            self, where, failing):
        """Batch A = request 0, batch B = requests 1-4 launched ahead:
        whichever fails, in whichever half, the other is answered."""
        eng = HalvesStub((1, 4), **{f"fail_{where}": (failing,)})
        with self._running(eng) as (b, metrics, first):
            futs = [b.submit(*_tagged(t)) for t in (1, 2, 3, 4)]
            _until(lambda: len(eng.launches()) == 2, "B launched ahead")
            eng.open_all([0, 1])
            batches = {0: [first], 1: futs}
            for f in batches.pop(failing):
                with pytest.raises(RuntimeError, match=f"{where} of"):
                    f.result(timeout=10)
            (sound,) = batches.values()
            assert [int(f.result(timeout=10).disparity[0, 0])
                    for f in sound] == ([0] if failing else [1, 2, 3, 4])
            # and the batcher keeps serving
            again = b.submit(*_tagged(9))
            eng.gate_of(9).set()
            assert int(again.result(timeout=10).disparity[0, 0]) == 9
        assert metrics.errors.value == (4 if failing else 1)
        assert eng.most_in_flight() <= 2

    def test_many_submitters_lose_nothing_and_never_fly_three(self):
        """Sixteen submitting threads against the two batcher threads,
        the interpreter switching every 10 us: every request is answered
        with its own row, launched exactly once, batches are finished in
        the order they were launched, and never more than two fly."""
        import sys

        eng = HalvesStub((1, 4))
        n_threads, per_thread = 16, 20
        total = n_threads * per_thread
        eng.open_all(range(total))  # finish never blocks
        cfg = _cfg(max_batch_size=4, max_wait_ms=1.0, queue_limit=total,
                   degrade_queue_depth=total + 1)
        got, errors = {}, []

        def submitter(b, base):
            try:
                for t in range(base, base + per_thread):
                    got[t] = b.submit(*_tagged(t))
            except Exception as e:  # surfaced below
                errors.append(e)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            with DynamicBatcher(eng, cfg) as b:
                threads = [threading.Thread(target=submitter,
                                            args=(b, i * per_thread))
                           for i in range(n_threads)]
                for th in threads:
                    th.start()
                for th in threads:
                    th.join(30.0)
                    assert not th.is_alive()
                assert not errors and len(got) == total
                for t, f in got.items():
                    assert int(f.result(timeout=30).disparity[0, 0]) == t
        finally:
            sys.setswitchinterval(interval)
        launched = eng.launches()
        assert sorted(t for tags in launched for t in tags) \
            == list(range(total))
        assert [tags for what, tags in eng.calls if what == "finish"] \
            == launched
        assert eng.most_in_flight() <= 2

    @pytest.mark.parametrize("drain", [True, False])
    def test_stop_answers_both_in_flight(self, drain):
        """``stop(drain=True)`` answers the two in flight and the rows
        still queued; ``stop(drain=False)`` fails only the queued."""
        from raftstereo_tpu.serve.batcher import ShuttingDown

        eng = HalvesStub((1, 4))
        with self._running(eng) as (b, metrics, first):
            flying = [b.submit(*_tagged(t)) for t in (1, 2, 3, 4)]
            _until(lambda: len(eng.launches()) == 2, "B launched ahead")
            queued = [b.submit(*_tagged(t)) for t in (5, 6)]
            stopper = threading.Thread(target=b.stop,
                                       kwargs={"drain": drain})
            stopper.start()
            if not drain:  # failed at once, with both batches still held
                for f in queued:
                    with pytest.raises(ShuttingDown):
                        f.result(timeout=10)
                assert not first.done() and not flying[0].done()
            eng.open_all(range(7))
            stopper.join(10.0)
            assert not stopper.is_alive()
            assert [int(f.result(timeout=10).disparity[0, 0])
                    for f in [first] + flying] == [0, 1, 2, 3, 4]
            if drain:
                assert [int(f.result(timeout=10).disparity[0, 0])
                        for f in queued] == [5, 6]
        assert eng.launches() == [[0], [1, 2, 3, 4]] + (
            [[5], [6]] if drain else [])
        assert b._thread.is_alive() is b._finisher.is_alive() is False


# ------------------------------------------------------------------- engine

class TestEngine:
    def test_warmup_then_bucketed_cache_compiles_once_per_bucket(
            self, serve_model):
        """One engine through its whole compile lifecycle (one test: XLA
        compiles are the expensive part of this module, don't repeat them).
        """
        model, variables = serve_model
        cfg = _cfg(max_batch_size=2, iters=2, degraded_iters=1,
                   buckets=((60, 90),))
        eng = BatchEngine(model, variables, cfg)
        # Warmup compiles the configured bucket at BOTH iteration levels,
        # each at BOTH row counts (max_batch_size 2 -> 1, 2).
        warmed = eng.warmup()
        assert sorted(warmed) == [
            (64, 96, it, "batch", f"r{rows}", "passive", "fp32")
            for it in (1, 2) for rows in (1, 2)]
        assert eng.warmup() == []  # all warm: nothing left to compile
        a, b = _img(60, 90, 1), _img(64, 96, 2)  # same 64x96 bucket
        eng.infer_batch([(a, a)], iters=2)
        assert not eng.last_included_compile  # warmup paid the compile
        out = eng.infer_batch([(a, a), (b, b)], iters=2)
        assert not eng.last_included_compile  # two rows: warmed as well
        assert out[0].shape == (60, 90) and out[1].shape == (64, 96)
        # 96x128 bucket: one (bucket, row count) more, and only that one
        eng.infer_batch([(_img(70, 100, 3),) * 2], iters=2)
        assert eng.last_included_compile
        assert not eng.is_warm((96, 128), 2)  # its two-row program is cold
        assert eng.is_warm((96, 128), 2, rows=1)
        assert eng.cache_stats == {"compiled": 5}

    def test_rejects_mixed_buckets_and_oversize(self, serve_model):
        model, variables = serve_model
        eng = BatchEngine(model, variables, _cfg(max_batch_size=2))
        with pytest.raises(AssertionError, match="mixed buckets"):
            eng.infer_batch([(_img(60, 90),) * 2, (_img(70, 100),) * 2], 2)
        with pytest.raises(AssertionError, match="max_batch_size"):
            eng.infer_batch([(_img(),) * 2] * 3, 2)


class _KeyCaptured(Exception):
    """Raised by a dispatch spy in place of the device work, carrying the
    cache key the entry point built."""


def _capture_key(key, call):
    raise _KeyCaptured(key)


class TestRowCounts:
    """A plain dispatch holds exactly a compiled row count of real rows —
    one row or ``max_batch_size`` — and ``infer_batch`` runs any other
    ``n`` as the fewest such dispatches, in order.  No program runs: a spy
    takes the key and the staged batch where the device call would be."""

    COUNTS = {1: (1,), 2: (1, 2), 6: (1, 6), 8: (1, 8)}

    @pytest.mark.parametrize("max_batch", sorted(COUNTS))
    def test_row_counts_are_one_and_max_batch_size(self, max_batch):
        from raftstereo_tpu.serve.engine import row_counts, split_rows

        counts = row_counts(max_batch)
        assert counts == self.COUNTS[max_batch]
        assert BatchEngine(None, {}, _cfg(
            max_batch_size=max_batch)).row_counts == counts
        for n in range(1, 2 * max_batch + 1):
            split = split_rows(n, counts)
            assert sum(split) == n and set(split) <= set(counts)
            assert split == [max_batch] * (n // max_batch) \
                + [1] * (n % max_batch)

    @pytest.mark.parametrize("max_batch,n", [
        (m, n) for m in sorted(COUNTS) for n in range(1, m + 1)])
    def test_n_pairs_run_as_fewest_dispatches_with_no_zero_row(
            self, max_batch, n):
        metrics = ServeMetrics()
        eng = BatchEngine(None, {}, _cfg(max_batch_size=max_batch,
                                         queue_limit=32), metrics)
        seen = []

        def spy_launch(key, call, padders=()):
            seen.append((key, dict(eng._seg.pad_px)))
            return types.SimpleNamespace(key=key, padders=padders,
                                         segments=None)

        def spy_finish(launched):
            rows = int(launched.key[4][1:])
            return [np.zeros((rows, 32, 64, 1), np.float32)], False

        eng._launch, eng._finish = spy_launch, spy_finish
        # bucket-sized images (32x64): every staged pixel is a real one
        pairs = [(_img(32, 64, i), _img(32, 64, 50 + i)) for i in range(n)]
        out = eng.infer_batch(pairs, 3)
        assert len(out) == n and all(d.shape == (32, 64) for d in out)
        want = [n] if n == max_batch else [1] * n
        assert [k for k, _ in seen] == [
            (32, 64, 3, "batch", f"r{rows}", "passive", "fp32")
            for rows in want]
        for (key, px), rows in zip(seen, want):
            assert px == {"rows": rows, "real_px": rows * 32 * 64,
                          "bucket_px": rows * 32 * 64}
            assert eng._program_facts(key) == {"rows": rows}
        for rows in set(want):
            assert metrics.batch_rows.labels(rows=str(rows)).value \
                == want.count(rows)
        assert metrics.batch_rows.value == len(want)

    @pytest.mark.parametrize("n", [1, 2, 4])
    def test_plain_staging_holds_the_rows_in_order(self, n, retrace_guard):
        """What ``_stage_pairs`` hands the program at ``rows == n``: the
        ``n`` real rows in order, each padded as the device's
        ``BucketPadder.pad`` pads it, and nothing else — staged on the
        host, with no device program at any row count."""
        eng = BatchEngine(None, {}, _cfg(max_batch_size=4))
        pairs = [(_img(20, 40, i), _img(20, 40, 9 + i)) for i in range(n)]
        with retrace_guard(0, what="staging is host copies and a transfer"):
            padders, hw, i1, i2, pad_rows = eng._pad_pairs(pairs, n)
        assert hw == (32, 64) and pad_rows == 0
        assert i1.shape == i2.shape == (n, 32, 64, 3)
        assert isinstance(i1, jax.Array) and i1.dtype == np.float32
        for i, (padder, (a, b)) in enumerate(zip(padders, pairs)):
            np.testing.assert_array_equal(
                padder.unpad(np.asarray(i1[i:i + 1]))[0], a)
            np.testing.assert_array_equal(
                padder.unpad(np.asarray(i2[i:i + 1]))[0], b)
            np.testing.assert_array_equal(
                np.asarray(i1[i:i + 1]), np.asarray(padder.pad(a[None])))

    def test_stream_batch_still_pads_to_max_batch_size(self, serve_model):
        """The warm-start path keeps one program a ladder level: every
        occupancy is zero-padded to ``max_batch_size`` under the old
        key."""
        model, variables = serve_model
        eng = BatchEngine(model, variables, _cfg(max_batch_size=4))
        staged = []
        stage = eng._stage_pairs

        def spy_stage(pairs, rows):
            staged.append(stage(pairs, rows))
            return staged[-1]

        eng._stage_pairs = spy_stage
        eng._dispatch = _capture_key
        a = _img()
        for n in (1, 3):
            with pytest.raises(_KeyCaptured) as ei:
                eng.infer_stream_batch([(a, a)] * n, 3, [None] * n)
            assert ei.value.args == ((64, 96, 3, "stream", "passive",
                                      "fp32"),)
            assert eng._seg.pad_px == {"rows": 4, "real_px": n * 60 * 90,
                                       "bucket_px": 4 * 64 * 96}
            _, _, i1, i2, pad_rows = staged[-1]
            assert i1.shape == i2.shape == (4, 64, 96, 3)
            assert pad_rows == 4 - n
            assert np.asarray(i1[:n]).any()
            assert not np.asarray(i1[n:]).any()  # the rows nobody sent


class TestRowCountEngine:
    """One warmed engine (``max_batch_size`` 3 -> row counts 1, 3),
    through every ``n`` a direct caller can hand ``infer_batch``."""

    @pytest.fixture(scope="class")
    def warm_engine(self, serve_model):
        model, variables = serve_model
        metrics = ServeMetrics()
        cfg = _cfg(max_batch_size=3, iters=2, degraded_iters=2)
        eng = BatchEngine(model, variables, cfg, metrics)
        warmed = eng.warmup()
        assert warmed == [
            (64, 96, 2, "batch", f"r{rows}", "passive", "fp32")
            for rows in (1, 3)]
        assert metrics.compile_misses.value == 2
        return eng, metrics

    @pytest.mark.parametrize("n", [1, 2, 3])
    def test_warm_n_compiles_nothing_and_matches_evaluator(
            self, warm_engine, retrace_guard, n):
        """After ``warmup()`` no ``n`` meets a program for the first
        time — staging is host work and has none, so the guard has no
        duration floor — and every reply is bitwise the Evaluator's at
        ``batch_pad`` = the row count of the dispatch it rode in."""
        from raftstereo_tpu.eval import Evaluator

        eng, metrics = warm_engine
        rows = n if n in eng.row_counts else 1
        pairs = [(_img(seed=10 + i), _img(seed=20 + i)) for i in range(n)]
        before = metrics.batch_rows.labels(rows=str(rows)).value
        misses = metrics.compile_misses.value
        with retrace_guard(0, what=f"{n} pairs after warmup: staging "
                                   "and model programs all warm"):
            out = eng.infer_batch(pairs, 2)
        assert not eng.last_included_compile
        assert metrics.compile_misses.value == misses
        assert eng.compiled_keys == {
            (64, 96, 2, "batch", f"r{r}", "passive", "fp32")
            for r in (1, 3)}
        assert metrics.batch_rows.labels(rows=str(rows)).value \
            == before + n // rows
        assert eng.last_segments["pad_px"]["rows"] == rows
        ev = Evaluator(eng.model, eng.variables, iters=2, divis_by=32,
                       bucket_multiple=32, batch_pad=rows)
        for disp, pair in zip(out, pairs):
            np.testing.assert_array_equal(disp, ev(*pair))


    @pytest.mark.parametrize("n", [1, 3])
    def test_finish_of_launch_is_infer_batch_bit_for_bit(
            self, warm_engine, retrace_guard, n):
        eng, metrics = warm_engine
        pairs = [(_img(seed=30 + i), _img(seed=40 + i)) for i in range(n)]
        want = eng.infer_batch(pairs, 2)
        before = metrics.batch_rows.labels(rows=str(n)).value
        with retrace_guard(0, what="the two halves run infer_batch's "
                                   "programs"):
            launched = eng.launch_batch(pairs, 2)
            assert launched.segments is None  # nothing waited for yet
            got = eng.finish_batch(launched)
        assert len(got) == n
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)
        assert metrics.batch_rows.labels(rows=str(n)).value == before + 1
        seg = launched.segments
        assert seg["pad_px"]["rows"] == n and not seg["compile"]
        # nothing was in flight: the device was free at launch's end,
        # and device_compute is launch + device_wait as ever
        assert seg["device_queued"] == (seg["launch"][1],) * 2
        assert seg["dispatch"] == (seg["launch"][0], seg["device_wait"][1])
        assert launched.out_dev is None and launched.behind is None
        with pytest.raises(AssertionError, match="not a compiled row count"):
            eng.launch_batch(pairs[:1] * 2, 2)

    def test_two_launches_before_either_finish_keep_their_own_rows(
            self, warm_engine, retrace_guard):
        """Launch A (one row), launch B (three rows), finish A, finish
        B: each batch gets its own rows' answers, and B's windows say it
        lay behind A — ``device_queued`` from its launch's end to A's
        ``device_wait`` end, ``device_wait`` / ``device_compute`` from
        there."""
        eng, _ = warm_engine
        pa = [(_img(seed=50), _img(seed=51))]
        pb = [(_img(seed=60 + i), _img(seed=70 + i)) for i in range(3)]
        want_a, want_b = eng.infer_batch(pa, 2), eng.infer_batch(pb, 2)
        with retrace_guard(0, what="two dispatches in flight, both warm"):
            a = eng.launch_batch(pa, 2)
            b = eng.launch_batch(pb, 2)
            assert b.behind is a and a.ready_at is None
            got_a = eng.finish_batch(a)
            got_b = eng.finish_batch(b)
        for got, want in ((got_a, want_a), (got_b, want_b)):
            assert len(got) == len(want)
            for x, y in zip(got, want):
                np.testing.assert_array_equal(x, y)
        sa, sb = a.segments, b.segments
        assert sb["device_queued"] == (sb["launch"][1],
                                       sa["device_wait"][1])
        assert sb["device_queued"][1] > sb["device_queued"][0]
        assert sb["device_wait"][0] == sb["dispatch"][0] \
            == sb["device_queued"][1]
        assert sb["host_fetch"][0] == sb["dispatch"][1] \
            == sb["device_wait"][1] >= sb["device_wait"][0]
        assert b.behind is None  # the chain ends with the dispatch

    def test_batcher_counts_what_it_launched_ahead_on_the_real_engine(
            self, warm_engine):
        """One row in flight (held in ``finish``), three queued behind
        it: a full batch, launched ahead — the counter, ``ahead`` on the
        spans, ``closed_by=full_ahead``, and the same bits as
        ``infer_batch``."""
        from raftstereo_tpu.obs import Tracer

        eng, _ = warm_engine
        gate = threading.Event()

        class HeldInFinish:
            row_counts, bucket_of = eng.row_counts, eng.bucket_of
            launch_batch = staticmethod(eng.launch_batch)

            @staticmethod
            def finish_batch(launched):
                assert gate.wait(60.0)
                return eng.finish_batch(launched)

        pairs = [(_img(seed=80 + i), _img(seed=90 + i)) for i in range(4)]
        want = eng.infer_batch(pairs[:1], 2) + eng.infer_batch(pairs[1:], 2)
        metrics, tracer = ServeMetrics(), Tracer(capacity=256)
        cfg = _cfg(max_batch_size=3, iters=2, degraded_iters=2,
                   max_wait_ms=1.0)
        b = DynamicBatcher(HeldInFinish, cfg, metrics, tracer).start()
        try:
            futs = [b.submit(*pairs[0], trace_id="req0")]
            _until(lambda: b.queue_depth == 0 and len(b._flying) == 1,
                   "the single launched")
            futs += [b.submit(*p, trace_id=f"req{i + 1}")
                     for i, p in enumerate(pairs[1:])]
            _until(lambda: len(b._flying) == 2, "the full batch launched")
            assert not futs[0].done()
            gate.set()
            res = [f.result(timeout=60) for f in futs]
        finally:
            gate.set()
            b.stop()
        for r, d in zip(res, want):
            np.testing.assert_array_equal(r.disparity, d)
        assert [r.batch_size for r in res] == [1, 3, 3, 3]
        assert metrics.launched_ahead.value == 1
        spans = tracer.spans()
        assert {s.trace_id: s.attrs["ahead"] for s in spans
                if s.name == "dispatch"} == {
            "req0": False, "req1": True, "req2": True, "req3": True}
        by_batch = lambda name: [
            s.attrs for s in sorted(spans, key=lambda s: s.t0)
            if s.name == name and s.trace_id.startswith("batch:")]
        assert [a["ahead"] for a in by_batch("launch")] == [False, True]
        assert [(a["closed_by"], a["ahead"]) for a in by_batch(
            "batch_form")] == [("deadline", False), ("full_ahead", True)]
        queued = [s for s in spans if s.name == "device_queued"
                  and s.trace_id.startswith("batch:")]
        assert sorted(s.duration_s > 0 for s in queued) == [False, True]


class TestKeyKinds:
    """Every kind of program the engine can compile names its kind at
    position 3 of its cache key: no two kinds share a tuple at one
    (bucket, iters, input_mode, mode), and nothing that reads a key
    (``is_*_warm``, ``_dispatch``'s metric label, ``compiled_programs``)
    tells kinds apart by the key's length."""

    KINDS = ("batch", "stream", "spatial", "sched_prologue", "sched_step",
             "sched_epilogue", "sched_join", "cascade_prologue",
             "cascade_stage_join", "cascade_handoff", "cascade_delta")

    @pytest.fixture(scope="class")
    def engine_and_keys(self, serve_model):
        """One engine and, per kind, the key its OWN entry point hands to
        ``_launch`` (the plain path) / ``_dispatch`` / ``_dispatch_state``
        — captured by a spy, so no program compiles."""
        model, variables = serve_model
        metrics = ServeMetrics()
        eng = BatchEngine(model, variables,
                          _cfg(max_batch_size=2, spatial_shards=4), metrics)
        real = eng._launch, eng._dispatch, eng._dispatch_state

        def spy(via):
            def capture(key, call, *padders):
                raise _KeyCaptured(key, via)
            return capture

        eng._launch = spy("_launch")  # the plain path's first half
        eng._dispatch = spy("_dispatch")
        eng._dispatch_state = spy("_dispatch_state")
        a, hw, it = _img(), (64, 96), 3
        mask, pair = np.zeros(2, bool), dict(cheap_mode="bf16",
                                             cert_mode="fp32")
        entries = {
            "batch": lambda: eng.infer_batch([(a, a)], it),
            "stream": lambda: eng.infer_stream_batch([(a, a)], it, [None]),
            "spatial": lambda: eng.infer_spatial(a, a, it),
            "sched_prologue": lambda: eng.infer_sched_prologue(
                [(a, a)], [None], [0]),
            "sched_step": lambda: eng.infer_sched_step(hw, None, it),
            "sched_epilogue": lambda: eng.infer_sched_epilogue(hw, None),
            "sched_join": lambda: eng.infer_sched_join(hw, None, None, mask),
            "cascade_prologue": lambda: eng.infer_cascade_prologue(
                [(a, a)], [None], [0], **pair),
            "cascade_stage_join": lambda: eng.infer_cascade_stage_join(
                hw, None, None, mask, **pair),
            "cascade_handoff": lambda: eng.infer_cascade_handoff(
                hw, None, None, np.zeros(2, np.int32), **pair),
            "cascade_delta": lambda: eng.infer_cascade_delta(
                hw, None, None, **pair),
        }
        keys = {}
        for kind, entry in entries.items():
            with pytest.raises(_KeyCaptured) as ei:
                entry()
            keys[kind] = ei.value.args  # (key, the dispatcher it took)
        eng._launch, eng._dispatch, eng._dispatch_state = real
        return eng, metrics, keys

    @pytest.mark.parametrize("kind", KINDS)
    def test_kind_is_tagged_and_read_from_the_tag(self, engine_and_keys,
                                                  kind):
        eng, metrics, keys = engine_and_keys
        assert sorted(keys) == sorted(self.KINDS)
        key, via = keys[kind]
        assert key[:2] == (64, 96) and key[3] == kind
        if kind == "batch":  # the row count rides right after the kind
            assert key == (64, 96, 3, "batch", "r1", "passive", "fp32")
        assert [k for k, (other, _) in keys.items() if other == key] \
            == [kind]
        sorted(k for k, _ in keys.values())  # /healthz sorts the mixed set

        # Run the real bookkeeping on this key alone (a trivial device
        # call stands in for the program).
        with eng._stats_lock:
            eng._compiled.clear()
        if via == "_dispatch_state":  # the result stays on the device
            _, miss = eng._dispatch_state(key, lambda: jax.numpy.zeros(1))
        else:
            _, miss = eng._dispatch(key, lambda: [jax.numpy.zeros(1)])
        assert miss and eng.compiled_keys == {key}
        labels = dict(bucket="64x96", iters=str(key[2]), mode=kind,
                      tier=key[-1])
        assert metrics.compile_misses.labels(**labels).value == 1

        # The readers: only the kind's own predicate sees its key, and
        # only a plain batch program has facts to show.
        assert eng.is_warm((64, 96), 3, rows=1) == (kind == "batch")
        assert not eng.is_warm((64, 96), 3)  # never every row count
        assert eng.is_stream_warm((64, 96), 3) == (kind == "stream")
        assert eng.is_spatial_warm((64, 96), 3) == (kind == "spatial")
        assert bool(eng._program_facts(key)) == (kind == "batch")
        assert eng._program_facts(key).get("rows") \
            == (1 if kind == "batch" else None)
        assert len(eng.compiled_programs) == (kind == "batch")


class TestConvertCheckpoint:
    def test_eval_shape_template_gives_the_eager_templates_variables(
            self, tmp_path):
        """``convert_checkpoint`` takes structure, shapes and dtypes from a
        traced ``model.init`` (no device program runs at a server's
        start); the variables are bitwise those the eager template
        gives."""
        import os
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, repo)
        from benchmark.weights import make_weights, write_pth
        from raftstereo_tpu.models import RAFTStereo
        from raftstereo_tpu.utils.convert import (convert_checkpoint,
                                                  load_state_dict,
                                                  torch_to_variables)

        with open(os.path.join(repo, "benchmark", "configs",
                               "raftstereo_default.json")) as f:
            weights = make_weights(json.load(f)["model"], 11)
        path = str(tmp_path / "seeded.pth")
        write_pth(weights, path)
        config = RAFTStereoConfig()
        got = convert_checkpoint(path, config)
        eager = torch_to_variables(
            load_state_dict(path),
            RAFTStereo(config).init(jax.random.key(0), image_hw=(64, 96)),
            config)
        assert jax.tree.structure(got) == jax.tree.structure(eager)
        leaves = jax.tree.leaves(got)
        assert len(leaves) > 100
        for a, b in zip(leaves, jax.tree.leaves(eager)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ metrics + wire

class TestMetrics:
    def test_prometheus_render_parses(self):
        from raftstereo_tpu.obs import validate_prometheus

        m = ServeMetrics()
        m.requests.labels(endpoint="predict", outcome="ok").inc(3)
        m.queue_depth.set(2)
        m.latency.observe(0.05)
        m.batch_size.observe(4)
        m.batch_rows.labels(rows="4").inc()
        m.batch_rows.labels(rows="8").inc(2)
        m.compile_misses.labels(bucket="64x96", iters="8", mode="batch",
                                tier="fp32").inc()
        text = m.render()
        for line in text.strip().splitlines():
            if line.startswith("#"):
                assert line.startswith("# HELP") or line.startswith("# TYPE")
                continue
            name, value = line.rsplit(" ", 1)
            float(value)  # every sample line ends in a number
            assert name
        assert validate_prometheus(text) == []
        assert 'serve_requests_total{endpoint="predict",outcome="ok"} 3' \
            in text
        assert m.requests.value == 3  # label-blind total
        assert "serve_queue_depth 2" in text
        assert 'serve_request_latency_seconds_bucket{le="+Inf"} 1' in text
        assert "serve_batch_size_count 1" in text
        assert 'serve_batch_rows_total{rows="4"} 1' in text
        assert 'serve_batch_rows_total{rows="8"} 2' in text
        assert m.batch_rows.value == 3
        assert ('serve_compile_cache_misses_total{bucket="64x96",iters="8",'
                'mode="batch",tier="fp32"} 1') in text

    def test_batch_rows_family_passes_the_metrics_lint(self):
        """``serve_batch_rows_total`` is one of the families the analysis
        runner's metrics pass instantiates, populates and validates."""
        from raftstereo_tpu.analysis.metrics_lint import run_metrics_lint
        from raftstereo_tpu.obs import lint_registry

        m = ServeMetrics()
        m.batch_rows.labels(rows="2").inc()
        assert "# TYPE serve_batch_rows_total counter" in m.render()
        assert lint_registry(m.registry.entries()) == []
        assert run_metrics_lint() == []

    def test_duplicate_metric_name_rejected(self):
        from raftstereo_tpu.serve import MetricsRegistry

        r = MetricsRegistry()
        r.counter("x_total", "x")
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("x_total", "again")

    def test_array_codec_roundtrip(self, rng):
        a = rng.normal(size=(7, 9)).astype(np.float32)
        np.testing.assert_array_equal(decode_array(encode_array(a)), a)
        nested = decode_array([[1.0, 2.0], [3.0, 4.0]])
        assert nested.dtype == np.float32 and nested.shape == (2, 2)


# ----------------------------------------------------------------- config

class TestServeConfig:
    def test_arg_roundtrip(self):
        import argparse

        from raftstereo_tpu.config import add_serve_args, \
            serve_config_from_args

        p = argparse.ArgumentParser()
        add_serve_args(p)
        args = p.parse_args(["--port", "9999", "--buckets", "540x960",
                             "736x1280", "--max_batch_size", "4",
                             "--no_warmup"])
        cfg = serve_config_from_args(args)
        assert cfg.port == 9999
        assert cfg.buckets == ((540, 960), (736, 1280))
        assert cfg.max_batch_size == 4 and not cfg.warmup

    def test_validation(self):
        with pytest.raises(AssertionError, match="queue_limit"):
            ServeConfig(queue_limit=2, max_batch_size=8)
        # degraded_iters above iters clamps down (degradation can only
        # reduce work) — so e.g. --serve_iters 8 with the default
        # degraded_iters 16 just works.
        assert ServeConfig(iters=8, degraded_iters=9).degraded_iters == 8
        assert ServeConfig(iters=3).degraded_iters == 3


# ------------------------------------------------------------------ end2end

class TestEndToEnd:
    def test_server_concurrent_mixed_shapes(self, serve_model,
                                            retrace_guard):
        """Acceptance gate: concurrent mixed-shape traffic over real HTTP.

        Asserts (1) each (bucket, row count) the traffic met compiled
        exactly once — enforced both at the engine cache level and by
        the retrace guard counting actual XLA compiles (one per key met
        for the cold traffic, budget 0 once warm), (2) responses equal
        the Evaluator bitwise at the same iteration count and the row
        count of the dispatch, (3) overload sheds instead of deadlocking,
        (4) /metrics reports non-zero batch-size and latency histograms
        and the dispatches by row count.
        """
        from raftstereo_tpu.eval import Evaluator

        model, variables = serve_model
        # warmup=False (from _cfg): the compile misses must come from real
        # traffic for assertion (1); the generous timeout absorbs the
        # first-request XLA compiles that warmup would otherwise pay.
        cfg = _cfg(max_batch_size=4, max_wait_ms=30.0, queue_limit=8,
                   iters=3, degraded_iters=3, degrade_queue_depth=100,
                   request_timeout_ms=120000.0,
                   max_body_mb=1.0, max_image_dim=128)
        metrics = ServeMetrics()
        server = build_server(model, variables, cfg, metrics)
        port = server.port
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        try:
            shapes = [(60, 90), (64, 96), (70, 100)]  # 2 distinct buckets
            pairs = {s: (_img(*s, seed=s[0]), _img(*s, seed=s[1]))
                     for s in shapes}
            results, errors = {}, []

            def send(i, shape):
                try:
                    client = ServeClient("127.0.0.1", port, timeout=120)
                    l, r = pairs[shape]
                    disp, meta = client.predict(l, r)
                    results[(i, shape)] = (disp, meta)
                    client.close()
                except Exception as e:  # pragma: no cover - failure detail
                    errors.append(e)

            # (1) one compile per (bucket, iters, row count) met: the
            # executable depends on the row count of the dispatch (one
            # row, or a full batch of four when four were queued), and
            # on nothing else.  The retrace guard counts ACTUAL XLA
            # compiles (model-scale via the 0.5 s floor): 2 buckets x row
            # counts 1, 4 -> at most 4, however the 6 requests interleave.
            with retrace_guard(4, what="each (bucket, row count) met "
                                       "compiles exactly once",
                               min_duration_s=0.5) as cold_report:
                threads = [threading.Thread(target=send, args=(i, s))
                           for i in range(2) for s in shapes]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(120)
                assert not errors, errors
                assert len(results) == 6
            # The keys met are those of the dispatches the replies rode
            # in: meta's batch_size IS a compiled row count, no reply
            # rode beside a zero row.
            engine = server.engine
            assert engine.row_counts == (1, 4)
            assert {meta["batch_size"] for _, meta in results.values()} \
                <= set(engine.row_counts)
            met = {(*engine.bucket_of(shape + (3,)), 3, "batch",
                    f"r{meta['batch_size']}", "passive", "fp32")
                   for (_, shape), (_, meta) in results.items()}
            assert engine.compiled_keys == met
            assert {k[:2] for k in met} == {(64, 96), (96, 128)}
            # EXACTLY one a key, not just <= 4: if the 0.5 s floor ever
            # rises above the real compile time, the warm budget-0 guards
            # below would pass vacuously — this assert makes that loud.
            assert cold_report.compiles == len(met), cold_report.durations
            assert metrics.compile_misses.value == len(met)

            # (2) bitwise equality with the Evaluator under the same
            # shape policy: shared BucketPadder, same iters, and
            # batch_pad = the row count of the dispatch (XLA only
            # guarantees identical numerics for identical program shapes).
            evs = {rows: Evaluator(model, variables, iters=3, divis_by=32,
                                   bucket_multiple=32, batch_pad=rows)
                   for rows in engine.row_counts}
            for (_, shape), (disp, meta) in results.items():
                ev = evs[meta["batch_size"]]
                assert disp.shape == shape
                np.testing.assert_array_equal(disp, ev(*pairs[shape]))

            # The burst below may form any batch of the 64x96 bucket:
            # warm what is left of its row counts first, as a started
            # server has (``warmup`` skips what the traffic compiled).
            warmed = engine.warmup(buckets=[(60, 90)], iters_list=[3])
            assert engine.is_warm((64, 96), 3)
            assert not set(warmed) & met
            n_keys = len(met) + len(warmed)
            assert metrics.compile_misses.value == n_keys

            # (3) overload: a burst far past queue_limit must shed with
            # clean 503s, and every accepted request completes.  Warm
            # traffic must add ZERO model compiles — guarded for real,
            # not just via the engine's own bookkeeping.
            with retrace_guard(0, what="burst + explicit iters reuse "
                                       "warm executables",
                               min_duration_s=0.5):
                # 32 callers against 4 rows in flight + 8 queued: single
                # rows ride the one-row program and drain fast, so the
                # burst has to be well past what the queue holds
                burst_stats = run_load(
                    "127.0.0.1", port, lambda i: pairs[(60, 90)],
                    requests=64, concurrency=32, timeout=120)
                assert burst_stats["shed"] > 0, burst_stats
                assert burst_stats["ok"] + burst_stats["shed"] \
                    + burst_stats["timeout"] == 64
                assert burst_stats["error"] == 0
                # No new compiles: the burst reused the warm 64x96
                # executables.
                assert metrics.compile_misses.value == n_keys
                assert metrics.compile_hits.value >= 1

            # (4) observability: batch + latency histograms are non-zero
            # and the healthz endpoint agrees with engine state.
            client = ServeClient("127.0.0.1", port)
            text = client.metrics_text()
            assert "# TYPE serve_batch_size histogram" in text

            def sample(name):
                return float([l for l in text.splitlines()
                              if l.startswith(name + " ")][0].split()[-1])

            assert sample("serve_batch_size_count") > 0
            assert sample("serve_request_latency_seconds_count") > 0
            assert sample("serve_request_latency_seconds_sum") > 0
            assert sample("serve_responses_total") >= 6
            # every plain dispatch is counted under its row count
            by_rows = {lv[0]: c.value
                       for lv, c in metrics.batch_rows.series()}
            assert set(by_rows) <= {"1", "4"}
            assert sum(by_rows.values()) == sample("serve_batch_size_count")\
                + len(warmed)
            assert "# TYPE serve_batch_rows_total counter" in text

            # Explicit iters: configured levels are served (warm
            # executable), anything else is a 400 — never a fresh compile.
            disp, meta = client.predict(*pairs[(60, 90)], iters=3)
            assert meta["iters"] == 3 and meta["batch_size"] == 1
            np.testing.assert_array_equal(disp, evs[1](*pairs[(60, 90)]))
            from raftstereo_tpu.serve import ServeError
            with pytest.raises(ServeError) as ei:
                client.predict(*pairs[(60, 90)], iters=7)
            assert ei.value.status == 400
            assert metrics.compile_misses.value == n_keys  # and no more

            # Admission caps reject before any decode or compile: image
            # side over max_image_dim -> 400, body over max_body_mb -> 413.
            with pytest.raises(ServeError) as ei:
                client.predict(_img(150, 100), _img(150, 100))
            assert ei.value.status == 400
            import http.client as hc
            conn = hc.HTTPConnection("127.0.0.1", port)
            try:
                conn.request("POST", "/predict", body=b"x" * (2 * 2 ** 20),
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                # The server refuses without draining: depending on send
                # timing the client either reads the 413 or hits a broken
                # pipe mid-upload.  Both are the refusal.
                assert resp.status == 413
                resp.read()
            except (BrokenPipeError, ConnectionResetError):
                pass
            conn.close()
            assert metrics.compile_misses.value == n_keys  # caps cost none

            # A POSTed body to a wrong path must be drained, not parsed as
            # the next request on this keep-alive connection.
            conn = hc.HTTPConnection("127.0.0.1", port)
            conn.request("POST", "/nope", body=b"x" * 4096,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 404
            resp.read()
            conn.request("GET", "/healthz")  # same connection still clean
            resp = conn.getresponse()
            assert resp.status == 200
            resp.read()
            conn.close()
            health = client.healthz()
            assert health["status"] == "ok"
            assert sorted(tuple(k) for k in health["compiled_buckets"]) \
                == sorted(met | set(warmed))
            client.close()
        finally:
            server.close()
            thread.join(10)


# ------------------------------------------------- binary wire over HTTP

class TestWireHTTP:
    """The /predict dual dialect end-to-end (docs/wire_format.md) plus
    the pre-dispatch body-policy edges (411/413/length mismatches) —
    every case leaves keep-alive in a defined state."""

    @pytest.fixture(scope="class")
    def wire_server(self, serve_model):
        model, variables = serve_model
        cfg = _cfg(iters=3, degraded_iters=3, request_timeout_ms=120000.0,
                   max_body_mb=1.0, max_image_dim=128)
        metrics = ServeMetrics()
        server = build_server(model, variables, cfg, metrics)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        yield server, metrics
        server.close()
        thread.join(10)

    def test_binary_json_bitwise_parity(self, wire_server):
        """A JSON-only client against the binary-default server (and
        vice versa) round-trips BITWISE — the compat guarantee that lets
        the dialects deploy independently."""
        server, metrics = wire_server
        l, r = _img(60, 90, seed=11), _img(60, 90, seed=12)
        cb = ServeClient("127.0.0.1", server.port, timeout=120)
        cj = ServeClient("127.0.0.1", server.port, timeout=120,
                         wire_format="json")
        try:
            db, mb = cb.predict(l, r)
            dj, mj = cj.predict(l, r)
            np.testing.assert_array_equal(db, dj)
            assert db.dtype == np.float32
            assert mb["iters"] == mj["iters"]
            # The binary request/response really is smaller on the wire.
            assert cb.bytes_sent < cj.bytes_sent
            assert cb.bytes_received < cj.bytes_received
            # Negotiation observability: both dialect pairs counted.
            negos = {lv: c.value
                     for lv, c in metrics.wire_negotiations.series()}
            assert negos.get(("binary", "binary"), 0) >= 1
            assert negos.get(("json", "json"), 0) >= 1
            wired = {lv: c.value for lv, c in metrics.wire_bytes.series()}
            assert wired.get(("in", "binary"), 0) > 0
            assert wired.get(("out", "binary"), 0) > 0
        finally:
            cb.close()
            cj.close()

    def test_int16_manifest_over_http(self, wire_server):
        """response.encoding=int16: the reply carries the exactness
        manifest and the decoded disparity honors its error bound
        against the bitwise f32 answer."""
        server, _ = wire_server
        l, r = _img(60, 90, seed=11), _img(60, 90, seed=12)
        c32 = ServeClient("127.0.0.1", server.port, timeout=120)
        c16 = ServeClient("127.0.0.1", server.port, timeout=120,
                          response_encoding="int16")
        try:
            d32, _ = c32.predict(l, r)
            d16, m16 = c16.predict(l, r)
            man = m16["wire_manifest"]
            assert man["encoding"] == "int16_fixed"
            err = float(np.max(np.abs(d16 - d32)))
            assert err <= man["err_bound"] + 1e-12
            assert man["max_abs_err"] <= man["err_bound"] + 1e-12
            assert np.isclose(err, man["max_abs_err"], atol=1e-6)
            assert c16.bytes_received < c32.bytes_received
        finally:
            c32.close()
            c16.close()

    def test_tiles_counted_and_one_level_both_ways(self, wire_server):
        """One round trip whose request holds a plane that deflates and
        one that does not: ``serve_wire_tiles_total`` counts both
        codings, the ``wire_decode`` and ``reply`` spans carry the
        frames' census, and wherever either side deflated a tile it
        used the one level (docs/wire_format.md "Compression")."""
        import zlib

        from raftstereo_tpu import wire

        server, metrics = wire_server
        yy, xx = np.mgrid[0:60, 0:90].astype(np.float32)
        shaded = np.stack([np.rint(xx + yy)] * 3, -1)  # deflates
        grain = _img(60, 90, seed=12)                   # does not
        cb = ServeClient("127.0.0.1", server.port, timeout=120)
        assert cb.compress_level == wire.LEVEL
        bodies = {}
        inner = cb._request

        def spy(method, path, body=None, headers=None):
            status, resp, hdrs = inner(method, path, body, headers)
            bodies.update(request=body, reply=resp)
            return status, resp, hdrs

        cb._request = spy
        before = {lv: c.value for lv, c in metrics.wire_tiles.series()}
        try:
            disp, meta = cb.predict(shaded, grain)
        finally:
            cb.close()
        sent = wire.tile_census(bodies["request"])
        got = wire.tile_census(bodies["reply"])
        assert sent["tiles_stored"] == 1 and sent["tiles_deflated"] == 1
        # a float32 reply: one tile a byte plane, the exponent's deflated
        assert got["tiles_stored"] + got["tiles_deflated"] == 4
        assert got["tiles_deflated"] >= 1
        assert got["bytes_raw"] == disp.nbytes
        after = {lv: c.value for lv, c in metrics.wire_tiles.series()}
        delta = {lv: after[lv] - before.get(lv, 0) for lv in after}
        assert delta == {("in", "stored"): 1, ("in", "deflate"): 1,
                         ("out", "stored"): got["tiles_stored"],
                         ("out", "deflate"): got["tiles_deflated"]}
        for _ in range(200):  # the reply span closes after the client read
            spans = {sp.name: sp.attrs for sp in
                     server.tracer.spans(trace_id=meta["request_id"])}
            if "reply" in spans:
                break
            time.sleep(0.01)
        assert {k: spans["wire_decode"][k] for k in sent} == sent
        assert {k: spans["reply"][k] for k in got} == got
        # the level class in a deflated tile's zlib header
        want = zlib.compress(b"", wire.LEVEL)[:2]
        assert want != zlib.compress(b"", 6)[:2]
        for frame in bodies.values():
            for raw_len, comp in _tiles(frame):
                if len(comp) < raw_len:
                    assert comp[:2] == want

    def test_binary_replies_are_encoded_one_at_a_time(self, wire_server,
                                                      monkeypatch):
        """A batch's replies become ready together; the server encodes
        them in turn (``StereoServer.reply_encode``), never side by
        side, and every caller still gets its own answer."""
        from raftstereo_tpu import wire

        server, _ = wire_server
        real, lock = wire.encode_response, threading.Lock()
        seen = {"now": 0, "peak": 0, "calls": 0}

        def slow_encode(*a, **kw):
            with lock:
                seen["now"] += 1
                seen["calls"] += 1
                seen["peak"] = max(seen["peak"], seen["now"])
            time.sleep(0.03)        # long enough for the others to arrive
            try:
                return real(*a, **kw)
            finally:
                with lock:
                    seen["now"] -= 1

        monkeypatch.setattr(wire, "encode_response", slow_encode)
        pairs = [(_img(60, 90, seed=20 + i), _img(60, 90, seed=30 + i))
                 for i in range(4)]
        out = [None] * 4

        def call(i):
            c = ServeClient("127.0.0.1", server.port, timeout=120)
            try:
                out[i] = c.predict(*pairs[i])[0]
            finally:
                c.close()

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        assert seen["calls"] == 4 and seen["peak"] == 1, seen
        assert all(o is not None and o.shape == (60, 90) for o in out)
        # each caller got the answer to its own pair
        c = ServeClient("127.0.0.1", server.port, timeout=120)
        try:
            monkeypatch.setattr(wire, "encode_response", real)
            alone = c.predict(*pairs[2])[0]
            np.testing.assert_allclose(out[2], alone, atol=1e-3)
            assert np.abs(out[1] - alone).max() > 1e-2
        finally:
            c.close()

    def test_negotiation_matrix_never_500s(self, wire_server):
        """Binary in + JSON out (Accept without the wire type), bad
        response prefs, and a non-wire Accept all answer 4xx/200 — the
        negotiation layer never turns a client choice into a 500."""
        import http.client as hc

        from raftstereo_tpu import wire

        server, _ = wire_server
        l, r = _img(60, 90, seed=11), _img(60, 90, seed=12)
        conn = hc.HTTPConnection("127.0.0.1", server.port, timeout=120)
        try:
            # Binary request, JSON-only Accept -> base64 JSON response.
            frame = wire.encode_request(l, r)
            conn.request("POST", "/predict", body=frame,
                         headers={"Content-Type": wire.WIRE_CONTENT_TYPE,
                                  "Accept": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            assert resp.status == 200
            assert resp.headers["Content-Type"] == "application/json"
            assert "disparity" in json.loads(body)
            # Bad response prefs: clean 400 BEFORE inference, not a
            # post-compute 500.
            frame = wire.encode_request(
                l, r, fields={"response": {"encoding": "f64"}})
            conn.request("POST", "/predict", body=frame,
                         headers={"Content-Type": wire.WIRE_CONTENT_TYPE,
                                  "Accept": wire.WIRE_CONTENT_TYPE})
            resp = conn.getresponse()
            body = resp.read()
            assert resp.status == 400
            assert resp.headers["Content-Type"] == "application/json"
            assert "encoding" in json.loads(body)["error"]
        finally:
            conn.close()

    def test_unknown_wire_version_explicit_400(self, wire_server):
        """A future-version frame gets a 400 NAMING the supported range
        — the contract that lets old servers reject new clients
        legibly."""
        import http.client as hc
        import struct

        from raftstereo_tpu import wire

        server, _ = wire_server
        frame = bytearray(wire.encode_request(_img(60, 90), _img(60, 90)))
        struct.pack_into("<H", frame, 4, 99)  # version field
        conn = hc.HTTPConnection("127.0.0.1", server.port, timeout=30)
        try:
            conn.request("POST", "/predict", body=bytes(frame),
                         headers={"Content-Type": wire.WIRE_CONTENT_TYPE})
            resp = conn.getresponse()
            err = json.loads(resp.read())["error"]
            assert resp.status == 400
            assert "99" in err and "1..1" in err, err
        except (BrokenPipeError, ConnectionResetError):
            pytest.fail("version reject must reply, not just drop")
        finally:
            conn.close()

    def test_zero_length_post_keepalive_survives(self, wire_server):
        """Content-Length: 0 -> clean 400 with X-Request-Id and NO body
        to drain: the same connection serves the next request."""
        import http.client as hc

        server, _ = wire_server
        conn = hc.HTTPConnection("127.0.0.1", server.port, timeout=30)
        try:
            conn.request("POST", "/predict", body=b"",
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 400
            assert resp.headers.get("X-Request-Id")
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 200
        finally:
            conn.close()

    def test_content_length_longer_than_body_400_closes(self, wire_server):
        """Client promises more bytes than it sends: the short read is a
        400 (with X-Request-Id) and the connection closes — the stream
        position is undefined, nothing further could be framed."""
        import socket as sk

        server, _ = wire_server
        s = sk.create_connection(("127.0.0.1", server.port), timeout=30)
        try:
            s.sendall(b"POST /predict HTTP/1.1\r\nHost: t\r\n"
                      b"Content-Type: application/json\r\n"
                      b"Content-Length: 100\r\n\r\n{\"left\":")
            s.shutdown(sk.SHUT_WR)
            reply = b""
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break
                reply += chunk
            assert reply.split(b"\r\n", 1)[0].split(b" ")[1] == b"400"
            assert b"X-Request-Id:" in reply
            assert b"shorter than Content-Length" in reply
        finally:
            s.close()

    def test_content_length_shorter_than_body_defined_state(
            self, wire_server):
        """Client sends MORE bytes than Content-Length: the request is
        answered off the declared length and the trailing garbage can
        only desync THIS connection — the server survives and fresh
        connections are untouched."""
        import http.client as hc
        import socket as sk

        server, _ = wire_server
        s = sk.create_connection(("127.0.0.1", server.port), timeout=30)
        try:
            s.sendall(b"POST /predict HTTP/1.1\r\nHost: t\r\n"
                      b"Content-Type: application/json\r\n"
                      b"Content-Length: 2\r\n\r\n{}GARBAGE")
            reply = s.recv(65536)
            # {} parses but has no images -> a clean 400 for request 1.
            assert reply.split(b"\r\n", 1)[0].split(b" ")[1] == b"400"
        finally:
            s.close()
        conn = hc.HTTPConnection("127.0.0.1", server.port, timeout=30)
        try:
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 200
        finally:
            conn.close()

    def test_chunked_transfer_encoding_411(self, wire_server):
        """Satellite contract: Transfer-Encoding is refused with 411 +
        X-Request-Id and the connection closes (chunked frames can't be
        drained off a Content-Length reader)."""
        import socket as sk

        server, _ = wire_server
        s = sk.create_connection(("127.0.0.1", server.port), timeout=30)
        try:
            s.sendall(b"POST /predict HTTP/1.1\r\nHost: t\r\n"
                      b"X-Request-Id: te-test\r\n"
                      b"Content-Type: application/json\r\n"
                      b"Transfer-Encoding: chunked\r\n\r\n"
                      b"0\r\n\r\n")
            reply = b""
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break  # server closed: the 411 contract
                reply += chunk
            assert reply.split(b"\r\n", 1)[0].split(b" ")[1] == b"411"
            assert b"X-Request-Id: te-test" in reply
        finally:
            s.close()

    def test_413_carries_request_id(self, wire_server):
        """Pre-dispatch 413 replies are joinable to client logs."""
        import socket as sk

        server, _ = wire_server
        s = sk.create_connection(("127.0.0.1", server.port), timeout=30)
        try:
            s.sendall(b"POST /predict HTTP/1.1\r\nHost: t\r\n"
                      b"X-Request-Id: cap-test\r\n"
                      b"Content-Type: application/json\r\n"
                      b"Content-Length: 999999999\r\n\r\n")
            reply = s.recv(65536)
            assert reply.split(b"\r\n", 1)[0].split(b" ")[1] == b"413"
            assert b"X-Request-Id: cap-test" in reply
        finally:
            s.close()
