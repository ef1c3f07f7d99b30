"""Dynamic micro-batcher: coalesce concurrent requests into batches of a
row count the engine has compiled — a full batch when one is queued, one
row when not, never a zero row — and launch the next full batch while the
one before it runs.

Deadline-aware dynamic batching in the spirit of Clipper (Crankshaw et al.,
NSDI 2017): a launcher thread groups queued requests by (shape bucket,
requested iterations) and closes a batch when it reaches
``max_batch_size`` or when the OLDEST member has waited ``max_wait_ms``,
whichever comes first — so batching never adds more than one deadline of
latency at low load, and amortizes dispatch at high load.  A closed batch
takes the largest compiled row count (``engine.row_counts``: 1 and
``max_batch_size``) that the queued rows fill and leaves the rest queued,
first in first out: their deadline has passed, so the next cycle closes at
once.

**Launch-ahead of depth one.**  The engine's plain dispatch comes in two
halves (``launch_batch`` stages the rows and calls the program, which
returns at once; ``finish_batch`` waits for the result and fetches it).
The launcher never waits on the device: it hands each launched batch to a
second thread of the same batcher, the finisher, which waits, fetches and
resolves the futures, batch by batch in launch order.  JAX runs what it is
handed in the order of the launches, so a batch launched while another
runs lies staged on the device and starts the moment that one ends.  When
a batch closes follows from what the batcher can observe — the rows
queued, the compiled row counts, the dispatches in flight (launched, not
yet answered) — in three cases, and there is no option:

* **none in flight** — the rule above, exactly;
* **one in flight** — a batch closes only when the queued rows fill the
  LARGEST compiled row count (``max(row_counts)``), and is staged and
  launched at once, behind the running one (``closed_by=full_ahead``).
  A partial queue waits: launched ahead it would buy one staging time and
  cost the rows that would have joined; when the running dispatch is
  answered the launcher wakes and the first case applies (the deadline
  has passed: it closes at once, as it always did);
* **two in flight** — one running, one queued behind it on the device:
  the launcher waits for the first to be answered.

Robustness controls, all tested in tests/test_serve.py:

* admission control — a bounded queue; ``submit`` raises ``Overloaded``
  (HTTP 503) instead of queueing unbounded work, so overload sheds cleanly
  rather than growing latency without bound;
* per-request timeout — requests older than ``request_timeout_ms`` at
  dispatch time fail with ``RequestTimedOut`` instead of wasting a batch
  slot on an answer the client gave up on;
* graceful degradation — when the backlog crosses
  ``degrade_queue_depth``, batches run at ``degraded_iters`` instead of
  ``iters``.  RAFT-Stereo's iterative refinement makes this knob uniquely
  cheap: fewer ConvGRU iterations trade accuracy smoothly for ~linear
  latency, with no second model or resolution change.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from ..config import ServeConfig
from ..obs.trace import timed_phase
from .metrics import ServeMetrics

__all__ = ["DynamicBatcher", "Future", "Overloaded", "RequestTimedOut",
           "ServeResult", "ShuttingDown"]


# Batch trace ids ``batch:<n>``: one counter for the process, so the
# replicas of a cluster (one batcher each, one shared tracer) never collide.
_BATCH_SEQ = itertools.count(1)


class Overloaded(RuntimeError):
    """Admission control rejected the request: the queue is full."""


class RequestTimedOut(RuntimeError):
    """The request exceeded request_timeout_ms before dispatch."""


class ShuttingDown(RuntimeError):
    """The batcher is stopping and will not accept or answer requests."""


@dataclasses.dataclass
class ServeResult:
    """One answered request: the disparity plus how it was computed."""

    disparity: np.ndarray  # (H, W) float32, dataset sign convention
    iters: int
    degraded: bool
    batch_size: int
    latency_s: float
    # Which cluster replica answered (serve/cluster/dispatcher.py);
    # None on the single-engine path.
    replica: Optional[str] = None


class Future:
    """Minimal thread-safe single-assignment result slot."""

    def __init__(self):
        self._done = threading.Event()
        self._value: Optional[ServeResult] = None
        self._exc: Optional[BaseException] = None
        self._cb_lock = threading.Lock()
        self._callbacks = []  # guarded_by: _cb_lock

    def _resolve(self, value=None, exc=None) -> None:
        """Settle the future and run callbacks ON THIS THREAD.

        Never call while holding a lock a callback may need: the cluster
        dispatcher's settle callback reads every replica's queue depth
        (serve/cluster/dispatcher.py), so resolving under one replica's
        ``_cv`` while another worker does the same is an ABBA deadlock —
        collect futures under the lock, resolve after releasing it
        (asserted in tests/test_cluster.py)."""
        self._value, self._exc = value, exc
        self._done.set()
        with self._cb_lock:
            cbs, self._callbacks = self._callbacks, []
        for fn in cbs:
            fn(self)

    def add_done_callback(self, fn) -> None:
        """Run ``fn(self)`` when the future resolves (immediately if it
        already has).  Callbacks run on the resolving thread; a waiter
        blocked in ``result()`` may wake concurrently, so callers that
        must annotate the value before anyone reads it chain a second
        future from the callback (serve/cluster/dispatcher.py does)."""
        with self._cb_lock:
            if not self._done.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def done(self) -> bool:
        return self._done.is_set()

    def exception(self) -> Optional[BaseException]:
        """The failure, if resolved with one (None while pending)."""
        return self._exc if self._done.is_set() else None

    def result(self, timeout: Optional[float] = None) -> ServeResult:
        if not self._done.wait(timeout):
            raise TimeoutError("result not ready")
        if self._exc is not None:
            raise self._exc
        return self._value


@dataclasses.dataclass
class _Request:
    image1: np.ndarray
    image2: np.ndarray
    iters: Optional[int]
    future: Future
    t_enqueue: float
    seq: int
    # Trace id of the originating request (obs/trace.py): the dispatch
    # worker reconstructs queue-wait/dispatch/host-fetch spans under it.
    trace_id: Optional[str] = None
    # Resolved precision mode of the request's accuracy tier
    # (ops/quant.py; None = the engine's default path).
    mode: Optional[str] = None


# Group key: (bucket_h, bucket_w, explicit iters or None, precision mode
# or None).  Requests with an explicit per-request iteration count cannot
# share a batch with adaptive ones — iters is baked into the compiled
# executable — and neither can requests of different accuracy tiers: the
# mode selects a different program with different numerics.
_Key = Tuple[int, int, Optional[int], Optional[str]]


@dataclasses.dataclass
class _Whole:
    """The pending dispatch of a ``_WholeCall``: nothing has run yet."""

    pairs: list
    iters: int
    mode: Optional[str]
    segments: Optional[Dict[str, object]] = None


class _WholeCall:
    """An engine that offers only ``infer_batch`` (the test doubles), in
    the two-halves shape the loop is written for: the launch does
    nothing, the finish is the whole call."""

    def __init__(self, engine):
        self.engine = engine

    def launch_batch(self, pairs, iters, mode=None) -> _Whole:
        return _Whole(pairs, iters, mode)

    def finish_batch(self, pending: _Whole):
        out = self.engine.infer_batch(pending.pairs, pending.iters,
                                      mode=pending.mode)
        pending.segments = getattr(self.engine, "last_segments", None)
        return out


@dataclasses.dataclass
class _Flight:
    """A launched batch the finisher has yet to answer."""

    key: _Key
    batch: List[_Request]  # the live requests, in dispatch order
    iters: int
    degraded: bool
    t_run0: float  # batch closed and handed to the engine
    btid: Optional[str]
    ahead: bool  # closed and launched while another was in flight
    pending: object  # the engine's, between launch_batch and finish_batch


class DynamicBatcher:
    """Thread-safe request queue + a launcher and a finisher thread over
    an engine.

    The engine contract is ``bucket_of(shape) -> (h, w)`` and the plain
    dispatch in two halves, ``launch_batch(pairs, iters, mode=None) ->
    pending`` and ``finish_batch(pending) -> [disparity]`` (see
    engine.BatchEngine; ``pending.segments`` holds the dispatch's phase
    windows once finished) — or only ``infer_batch(pairs, iters,
    mode=None) -> [disparity]``, which is wrapped into the two (tests
    substitute stubs; ``mode`` is the request's resolved precision mode,
    always passed by keyword) — and optionally ``row_counts``: the batch
    sizes it has programs for.
    """

    def __init__(self, engine, config: ServeConfig,
                 metrics: Optional[ServeMetrics] = None, tracer=None):
        self.engine = engine
        self._halves = (engine if hasattr(engine, "launch_batch")
                        else _WholeCall(engine))
        self.cfg = config
        self.metrics = metrics or ServeMetrics()
        self.tracer = tracer  # obs.Tracer or None (tracing is optional)
        self._cv = threading.Condition()
        self._queues: Dict[_Key, Deque[_Request]] = {}  # guarded_by: _cv
        self._depth = 0  # guarded_by: _cv
        self._seq = 0  # guarded_by: _cv
        self._closed = False  # guarded_by: _cv
        # Launched and not yet answered, in launch order: at most two,
        # one running and one queued behind it on the device.
        self._flying: Deque[_Flight] = collections.deque()  # guarded_by: _cv
        self._launcher_done = False  # guarded_by: _cv
        self._thread: Optional[threading.Thread] = None
        self._finisher: Optional[threading.Thread] = None

    # ------------------------------------------------------------- lifecycle

    def start(self) -> "DynamicBatcher":
        assert self._thread is None, "batcher already started"
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="serve-batcher")
        self._finisher = threading.Thread(target=self._finish_loop,
                                          daemon=True,
                                          name="serve-batcher-finish")
        self._thread.start()
        self._finisher.start()
        return self

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop both threads.  ``drain=True`` answers everything still
        queued first; ``drain=False`` fails queued requests with
        ``ShuttingDown``.  A batch already launched is answered either
        way."""
        to_fail = []
        with self._cv:
            self._closed = True
            if not drain:
                for q in self._queues.values():
                    to_fail.extend(r.future for r in q)
                self._queues.clear()
                self._depth = 0
                self.metrics.queue_depth.set(0)
            self._cv.notify_all()
        # Outside _cv: resolving runs done-callbacks that may read this
        # (or another replica's) queue depth — see Future._resolve.
        for fut in to_fail:
            fut._resolve(exc=ShuttingDown("batcher stopped"))
        t_end = time.perf_counter() + timeout
        for thread in (self._thread, self._finisher):
            if thread is not None:
                thread.join(max(t_end - time.perf_counter(), 0.0))

    def __enter__(self) -> "DynamicBatcher":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------- admission

    @property
    def queue_depth(self) -> int:
        with self._cv:  # vs a concurrent submit/close mutating the count
            return self._depth

    def submit(self, image1: np.ndarray, image2: np.ndarray,
               iters: Optional[int] = None,
               trace_id: Optional[str] = None,
               mode: Optional[str] = None) -> Future:
        """Enqueue one stereo pair; returns a ``Future`` for the result.

        Raises ``Overloaded`` immediately when the queue is at
        ``queue_limit`` — the caller maps this to HTTP 503 so clients see a
        clear shed signal instead of an unbounded wait.  ``trace_id`` tags
        the request's spans (queue wait, dispatch, host fetch) in the
        tracer ring.  ``mode`` is the request's resolved precision mode
        (accuracy tier): it joins the grouping key, so tiers never share
        a dispatched batch.
        """
        key: _Key = (*self.engine.bucket_of(image1.shape), iters, mode)
        fut = Future()
        with self._cv:
            if self._closed:
                raise ShuttingDown("batcher stopped")
            if self._depth >= self.cfg.queue_limit:
                self.metrics.shed.inc()
                raise Overloaded(
                    f"queue full ({self._depth}/{self.cfg.queue_limit})")
            self._seq += 1
            self._queues.setdefault(key, collections.deque()).append(
                _Request(image1, image2, iters, fut, time.perf_counter(),
                         self._seq, trace_id, mode))
            self._depth += 1
            self.metrics.queue_depth.set(self._depth)
            self._cv.notify_all()
        return fut

    # --------------------------------------------------------------- worker

    def _oldest_key(self) -> _Key:  # guarded_by: _cv
        """Key whose head request has waited longest (caller holds lock)."""
        return min(self._queues, key=lambda k: self._queues[k][0].seq)

    def _take(self, queued: int) -> int:
        """How many of ``queued`` rows a closing batch takes: the largest
        row count the engine has compiled that they fill, so no dispatch
        holds a zero row; an engine that names no counts (the test
        doubles) takes what is there, up to ``max_batch_size``."""
        n = min(queued, self.cfg.max_batch_size)
        counts = getattr(self.engine, "row_counts", None) or ()
        return max((c for c in counts if c <= n), default=n)

    def _timed_out(self, r: _Request, now: float) -> bool:
        return now - r.t_enqueue > self.cfg.request_timeout_ms / 1000.0

    def _phase(self, name: str, btid: Optional[str],
               start: Optional[float] = None, **attrs):
        """One phase of the worker's cycle: a ring span under the batch's
        trace plus a profiler annotation (``Tracer.phase``); without a
        tracer the annotation alone."""
        if self.tracer is None:
            return timed_phase(name, start, **attrs)
        return self.tracer.phase(name, trace_id=btid, start=start, **attrs)

    def _await_close(self, max_wait_s: float) -> Optional[str]:  # guarded_by: _cv
        """Block until the oldest group's batch may close; returns why
        (``closed_by``), or None when a non-drain stop emptied the queue
        meanwhile.  The three cases of the module docstring, read anew
        at every wake-up (a submit, a stop, a dispatch answered)."""
        full_rows = self._take(self.cfg.max_batch_size)
        while self._depth:
            q = self._queues[self._oldest_key()]
            if not self._flying:
                # Hold the batch open until it fills or the oldest
                # member's deadline passes.
                if len(q) >= self.cfg.max_batch_size:
                    return "full"
                if self._closed:
                    return "shutdown"
                remaining = (q[0].t_enqueue + max_wait_s
                             - time.perf_counter())
                if remaining <= 0:
                    return "deadline"
                self._cv.wait(remaining)
            elif (len(self._flying) == 1
                  and len(q) - self._expired(q) >= full_rows):
                return "full_ahead"
            else:
                self._cv.wait()
        return None

    def _expired(self, q: Deque[_Request]) -> int:
        """Requests past ``request_timeout_ms`` at the head of ``q`` (one
        time-out, FIFO: the expired are a prefix)."""
        now = time.perf_counter()
        return sum(1 for _ in itertools.takewhile(
            lambda r: self._timed_out(r, now), q))

    def _loop(self) -> None:
        """The launcher's cycle, in phases that leave no hole on its
        thread, each recorded ONCE per dispatch under the batch's own
        trace ``batch:<seq>``: ``queue_empty`` (nothing queued) ->
        ``batch_form`` (first request queued -> batch closed; the wait
        for a dispatch in flight is in it) -> ``pad_bucket`` -> ``launch``
        (the engine's, handed over in ``pending.segments``).  The
        finisher's follow in ``_finish_loop``.  ``queue_empty`` ends and
        ``batch_form`` starts at one clock read, the enqueue of the
        request that ended the wait: the launcher's own wake-up is
        batch_form's, and the two meet exactly."""
        max_wait_s = self.cfg.max_wait_ms / 1000.0
        try:
            while True:
                btid = f"batch:{next(_BATCH_SEQ)}"
                with self._cv:
                    t_first = None
                    if not self._closed and self._depth == 0:
                        with self._phase("queue_empty", btid) as empty:
                            while not self._closed and self._depth == 0:
                                self._cv.wait()
                            if self._depth:
                                t_first = self._queues[
                                    self._oldest_key()][0].t_enqueue
                                empty.ends_at(t_first)
                    if self._depth == 0:  # closed and drained
                        return
                    with self._phase("batch_form", btid,
                                     start=t_first) as form:
                        closed_by = self._await_close(max_wait_s)
                        if closed_by is None:  # drained by a non-drain stop
                            continue
                        key = self._oldest_key()
                        q = self._queues[key]
                        # Requests past request_timeout_ms head the queue:
                        # they leave with this batch to be failed, and the
                        # rows taken are counted among the live ones
                        # behind them, so what reaches the engine is
                        # still a compiled row count.
                        expired = self._expired(q)
                        batch = [q.popleft() for _ in range(
                            expired + self._take(len(q) - expired))]
                        if not q:
                            del self._queues[key]
                        self._depth -= len(batch)
                        # Backlog measured at batch close, including this
                        # batch: the signal that decides graceful
                        # degradation.
                        backlog = self._depth + len(batch)
                        self.metrics.queue_depth.set(self._depth)
                        ahead = closed_by == "full_ahead"
                        form.attrs.update(closed_by=closed_by, ahead=ahead,
                                          batch_size=len(batch),
                                          bucket=f"{key[0]}x{key[1]}")
                self._launch(key, batch, backlog, btid, ahead)
        finally:
            with self._cv:
                self._launcher_done = True
                self._cv.notify_all()

    def _finish_loop(self) -> None:
        """The finisher's cycle, one flight at a time in launch order:
        ``device_wait`` -> ``host_fetch`` (the engine's) ->
        ``reply_handoff``.  A flight counts as in flight until its
        futures are resolved; then the launcher is woken."""
        while True:
            with self._cv:
                while not self._flying and not self._launcher_done:
                    self._cv.wait()
                if not self._flying:
                    return
                flight = self._flying[0]
            try:
                self._answer(flight)
            finally:
                with self._cv:
                    self._flying.popleft()
                    self._cv.notify_all()

    def _trace_batch(self, flight: _Flight, t_done: float,
                     error=None) -> None:
        """Reconstruct each request's phase spans from the dispatch just
        answered: queue wait (enqueue -> batch close), dispatch (engine
        call through device compute) and host fetch — siblings under the
        request's trace id, so their durations sum to the server-side
        latency (asserted in tests/test_obs.py); under ``dispatch`` its
        parts in order, ``pad_bucket``, ``launch``, ``device_queued``
        (behind the dispatch before it; zero-length when the device was
        free) and ``device_compute``.  The engine's phases of the
        dispatch itself go ONCE under the batch's trace, from the same
        windows."""
        key, batch, t_run0 = flight.key, flight.batch, flight.t_run0
        seg = (getattr(flight.pending, "segments", None)
               if error is None else None)
        bucket = f"{key[0]}x{key[1]}"
        if seg is not None and flight.btid is not None:
            # the batch's size and bucket are on its batch_form
            battrs = {"request_ids": [r.trace_id for r in batch
                                      if r.trace_id is not None]}
            # pad_bucket also says what the dispatch was staged at (rows
            # / real_px / bucket_px, engine._pad_pairs), its two parts
            # the row count; launch whether it ran ahead of the dispatch
            # before it; every timed phase its thread's cpu_ms / runq_ms
            px = seg.get("pad_px") or {}
            rows = {"rows": px["rows"]} if "rows" in px else {}
            extra = {"pad_bucket": px, "stage_copy": rows, "h2d_put": rows,
                     "launch": {"ahead": flight.ahead}}
            thread_ms = seg.get("thread_ms") or {}
            for name, window in (("pad_bucket", seg.get("pad")),
                                 ("stage_copy", seg.get("stage_copy")),
                                 ("h2d_put", seg.get("h2d_put")),
                                 ("launch", seg.get("launch")),
                                 ("device_queued", seg.get("device_queued")),
                                 ("device_wait", seg.get("device_wait")),
                                 ("host_fetch", seg.get("host_fetch"))):
                if window:
                    self.tracer.record(
                        name, *window, flight.btid,
                        attrs={**battrs, **extra.get(name, {}),
                               **thread_ms.get(name, {})})
        for r in batch:
            if r.trace_id is None:
                continue
            self.tracer.record("queue_wait", r.t_enqueue, t_run0,
                               r.trace_id)
            attrs = {"bucket": bucket, "iters": flight.iters,
                     "degraded": flight.degraded, "batch_size": len(batch),
                     "ahead": flight.ahead}
            if error is not None:
                attrs["error"] = str(error)
            if seg is None:
                self.tracer.record("dispatch", t_run0, t_done, r.trace_id,
                                   attrs=attrs)
                continue
            attrs["compile"] = seg["compile"]
            if seg.get("pad_px"):
                # the compiled row count of the dispatch
                attrs["rows"] = seg["pad_px"].get("rows")
            parent = self.tracer.record(
                "dispatch", t_run0, seg["dispatch"][1], r.trace_id,
                attrs=attrs)
            for name, window, cattrs in (
                    ("pad_bucket", seg.get("pad"), seg.get("pad_px")),
                    ("launch", seg.get("launch"), None),
                    ("device_queued", seg.get("device_queued"), None),
                    ("device_compute", seg["dispatch"], None)):
                if window:
                    self.tracer.record(name, *window, r.trace_id,
                                       parent_id=parent, attrs=cattrs)
            self.tracer.record("host_fetch", *seg["host_fetch"], r.trace_id)

    def _fail(self, flight: _Flight, error: Exception) -> None:
        """A failed dispatch fails its own batch and nothing else."""
        self.metrics.errors.inc(len(flight.batch))
        if self.tracer is not None:
            self._trace_batch(flight, time.perf_counter(), error=error)
        for r in flight.batch:
            r.future._resolve(exc=error)

    def _launch(self, key: _Key, batch, backlog: int, btid: Optional[str],
                ahead: bool) -> None:
        """Fail the timed-out, stage and launch the rest, and hand the
        flight to the finisher — without waiting on the device."""
        now = time.perf_counter()
        alive = []
        for r in batch:
            if self._timed_out(r, now):
                self.metrics.timeouts.inc()
                if self.tracer is not None and r.trace_id is not None:
                    self.tracer.record(
                        "queue_wait", r.t_enqueue, now, r.trace_id,
                        attrs={"outcome": "timeout"})
                r.future._resolve(exc=RequestTimedOut(
                    f"queued {now - r.t_enqueue:.3f}s > "
                    f"{self.cfg.request_timeout_ms / 1000.0:.3f}s limit"))
            else:
                alive.append(r)
        if not alive:
            return
        explicit_iters = key[2]
        if explicit_iters is not None:
            iters, degraded = explicit_iters, False
        else:
            degraded = backlog >= self.cfg.degrade_queue_depth
            iters = (self.cfg.degraded_iters if degraded
                     else self.cfg.iters)
        if degraded:
            self.metrics.degraded_batches.inc()
        flight = _Flight(key, alive, iters, degraded, time.perf_counter(),
                         btid, ahead, None)
        try:
            flight.pending = self._halves.launch_batch(
                [(r.image1, r.image2) for r in alive], iters, mode=key[3])
        except Exception as e:  # fail the batch, keep serving
            self._fail(flight, e)
            return
        if ahead:
            self.metrics.launched_ahead.inc()
        with self._cv:
            self._flying.append(flight)
            self._cv.notify_all()

    def _answer(self, flight: _Flight) -> None:
        try:
            disps = self._halves.finish_batch(flight.pending)
        except Exception as e:  # fail the batch, keep serving
            self._fail(flight, e)
            return
        alive = flight.batch
        # reply_handoff: from the engine's return (disparities un-padded)
        # until every future of the batch is resolved.
        with self._phase("reply_handoff", flight.btid):
            done = time.perf_counter()
            if self.tracer is not None:
                self._trace_batch(flight, done)
            self.metrics.batch_size.observe(len(alive))
            for r, d in zip(alive, disps):
                latency = done - r.t_enqueue
                self.metrics.latency.observe(latency)
                self.metrics.responses.inc()
                r.future._resolve(value=ServeResult(
                    disparity=d, iters=flight.iters,
                    degraded=flight.degraded,
                    batch_size=len(alive), latency_s=latency))
