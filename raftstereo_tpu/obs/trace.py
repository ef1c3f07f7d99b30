"""In-process span tracer with Chrome trace-event (Perfetto) export.

The XLA profiler (utils/profiling.py) answers "what did the device do" for
a pre-scheduled window; this module answers "where did THIS request/step
go" continuously: lightweight host-side spans (trace id, parent id, name,
attrs, wall-time) recorded into a bounded ring buffer, always on, cheap
enough for every request (one dict + one deque append per span, a few
microseconds — measured in tests/test_obs.py).

Spans are exportable as Chrome trace-event JSON — the format Perfetto and
``chrome://tracing`` open directly, and the same family of viewers the XLA
trace lands in, so a request trace and a ``jax.profiler`` capture can be
eyeballed side by side.  ``GET /debug/trace`` on the serving front-end and
the train-side telemetry exporter both serve this export
(docs/observability.md).

Two recording styles:

* ``with tracer.span("admission", trace_id=rid):`` — live nesting via a
  thread-local stack (children inherit trace/parent ids automatically);
* ``tracer.record("queue_wait", t0, t1, rid)`` — after-the-fact, for
  phases measured by another component (the batcher reconstructs each
  request's queue-wait/dispatch/host-fetch from the dispatch worker);
* ``with tracer.phase("launch", trace_id=btid):`` — a ``span`` that is
  ALSO a ``jax.profiler.TraceAnnotation`` for its length, so while a
  profile runs the same phase is an event on the host plane of the
  ``.xplane.pb``, stamped by the profiler on the clock it stamps the
  device with.  Components without a tracer (the engine) time a phase
  with ``timed_phase`` and hand its ``window`` to whoever records it.
  ``clock_annotation`` ties the two clocks (docs/observability.md).

Every phase also reads its thread's CPU time and run-queue delay at both
ends (``thread_ms``: ``cpu_ms``, and ``runq_ms`` where
``/proc/thread-self/schedstat`` can be read); a phase's span carries them
in its attrs.  Wall − cpu − runq is the time the thread was blocked: a
lock, the GIL, a socket, a condition.

Timestamps are ``time.perf_counter`` values (monotonic, ns-resolution);
the export converts them to epoch microseconds with one process-wide
offset so spans from every thread share a clock.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import threading
import time
import uuid
from collections import deque
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = ["Span", "Tracer", "clock_annotation", "timed_phase",
           "to_chrome_trace"]

# perf_counter -> unix epoch seconds, fixed at import so every span (and
# every thread) converts identically.
_EPOCH_OFFSET = time.time() - time.perf_counter()


@dataclasses.dataclass(frozen=True)
class Span:
    """One completed span (immutable once recorded)."""

    trace_id: str
    span_id: str
    parent_id: Optional[str]
    name: str
    t0: float  # time.perf_counter at start
    t1: float  # time.perf_counter at end
    thread: str
    attrs: Dict[str, object]

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    @property
    def wall_t0(self) -> float:
        """Start as unix epoch seconds."""
        return self.t0 + _EPOCH_OFFSET


def _no_annotation(name: str, **attrs):
    """Stands in for ``TraceAnnotation`` where JAX is not installed."""
    return contextlib.nullcontext()


_annotation_cls = None


def _annotation(name: str, attrs: Dict):
    """``jax.profiler.TraceAnnotation(name, **attrs)``.  JAX is imported
    on first use, so ``obs`` stays importable without it.  With no
    capture running the constructor returns before it looks at
    ``attrs`` (``TraceMe`` checks the profiler's level first), so pass
    values as they are and format nothing for it."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation as cls
        except ImportError:
            cls = _no_annotation
        _annotation_cls = cls
    return _annotation_cls(name, **attrs)


# The run-queue delay is the second field of the thread's schedstat
# (ns spent runnable but waiting for a core).  Opened once per thread.
_SCHEDSTAT = "/proc/thread-self/schedstat"


class _RunQueue:
    """This thread's schedstat file, held open; ``read()`` is the run-queue
    delay in ns so far, or None where the file cannot be read (not Linux,
    or a kernel without schedstat)."""

    __slots__ = ("fd",)

    def __init__(self):
        try:
            self.fd = os.open(_SCHEDSTAT, os.O_RDONLY)
        except OSError:
            self.fd = -1

    def read(self) -> Optional[int]:
        if self.fd < 0:
            return None
        try:
            return int(os.pread(self.fd, 64, 0).split()[1])
        except (OSError, IndexError, ValueError):
            return None

    def __del__(self, _close=os.close):  # the thread's locals die with it
        if self.fd >= 0:
            _close(self.fd)


_threads = threading.local()


def _forget_threads() -> None:
    # a forked child's threads are not its parent's: open anew
    global _threads
    _threads = threading.local()


os.register_at_fork(after_in_child=_forget_threads)


def _runq_ns() -> Optional[int]:
    rq = getattr(_threads, "runq", None)
    if rq is None:
        rq = _threads.runq = _RunQueue()
    return rq.read()


class timed_phase:
    """``with timed_phase("launch", batch_size=8) as ph:`` holds a
    ``TraceAnnotation`` open and reads ``perf_counter`` just inside it
    at both ends: ``ph.window`` is the ``(t0, t1)`` a ring span of the
    same phase is recorded from (``Tracer.record(name, *ph.window,
    ...)``), so the annotation and the span bracket the same code.

    Inside the two ``perf_counter`` reads it reads the thread's CPU time
    and run-queue delay; ``ph.thread_ms`` is their change over the phase,
    ``{"cpu_ms", "runq_ms"}`` (``runq_ms`` left out where schedstat
    cannot be read), so each is at most the window's length.

    Adjacent phases meet on one clock read: ``start`` begins the window
    at a time read before (the end of the phase before), and
    ``ends_at(t)`` ends it at a time read elsewhere — the window then no
    longer brackets the thread reads, and ``thread_ms`` is empty."""

    __slots__ = ("name", "attrs", "start", "t0", "t1", "thread_ms",
                 "_ann", "_end", "_c0", "_r0")

    def __init__(self, name: str, start: Optional[float] = None, **attrs):
        self.name = name
        self.attrs = attrs
        self.start = start
        self.t0 = self.t1 = 0.0
        self.thread_ms: Dict[str, float] = {}
        self._end = None

    def ends_at(self, t: float) -> None:
        self._end = t

    def __enter__(self) -> "timed_phase":
        self._ann = _annotation(self.name, self.attrs)
        self._ann.__enter__()
        t0 = time.perf_counter()
        self._c0 = time.thread_time_ns()
        self._r0 = _runq_ns()
        self.t0 = t0 if self.start is None else self.start
        return self

    def __exit__(self, *exc) -> bool:
        c1 = time.thread_time_ns()
        r1 = _runq_ns() if self._r0 is not None else None
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if self._end is not None:
            self.t1 = self._end
            return False
        self.thread_ms["cpu_ms"] = (c1 - self._c0) * 1e-6
        if r1 is not None:
            self.thread_ms["runq_ms"] = (r1 - self._r0) * 1e-6
        return False

    @property
    def window(self) -> Tuple[float, float]:
        return self.t0, self.t1


class _Wall:
    """The clock of a plain ``span``: two ``perf_counter`` reads."""

    __slots__ = ("t0", "t1")
    thread_ms: Dict[str, float] = {}

    def __enter__(self) -> "_Wall":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        return False


def clock_annotation():
    """``TraceAnnotation("obs.clock", unix_ns=, perf_counter_ns=)``: one
    instant on both of the program's clocks, stamped by the profiler on
    its own.  ``perf_counter_ns`` is the clock of ``Span.t0``;
    ``unix_ns`` is the same instant as the Chrome export writes it
    (``ts`` = perf_counter + the process's fixed offset).  The
    profilers (utils/profiling.py) emit one when a capture starts and
    one when it stops; a reader lays the ring's spans onto the trace
    from the pair: ``trace_ns = event.start_ns + (ts_us * 1e3 -
    unix_ns)``."""
    now = time.perf_counter()
    return _annotation("obs.clock",
                       {"unix_ns": int((now + _EPOCH_OFFSET) * 1e9),
                        "perf_counter_ns": int(now * 1e9)})


class _Live:
    """Handle yielded by ``Tracer.span`` — mutate ``attrs`` mid-span;
    ``t0`` is the span's start, ``t1`` its end once it has closed."""

    __slots__ = ("trace_id", "span_id", "attrs", "t0", "t1", "_clock")

    def __init__(self, trace_id: str, span_id: str, attrs: Dict, clock):
        self.trace_id = trace_id
        self.span_id = span_id
        self.attrs = attrs
        self._clock = clock
        self.t0 = self.t1 = 0.0

    def ends_at(self, t: float) -> None:
        """A phase's ``timed_phase.ends_at``."""
        self._clock.ends_at(t)


class Tracer:
    """Thread-safe bounded span recorder.

    ``capacity`` bounds memory: the ring keeps the most recent spans and
    silently drops the oldest — telemetry must never be the thing that
    OOMs the server.  Dropped spans are counted (``dropped``).
    """

    def __init__(self, capacity: int = 4096):
        assert capacity >= 1, capacity
        self.capacity = capacity
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=capacity)  # guarded_by: _lock
        self._recorded = 0  # guarded_by: _lock
        self._tls = threading.local()

    # ------------------------------------------------------------------ ids

    @staticmethod
    def new_trace_id() -> str:
        return uuid.uuid4().hex

    @staticmethod
    def new_span_id() -> str:
        """Public span-id mint: callers that must know a span's id BEFORE
        the span completes (the router emits the hop span's id in the
        outbound ``X-Trace-Context`` header, then records the span after
        the forward returns) mint here and pass it to ``record``."""
        return uuid.uuid4().hex[:16]

    def current(self) -> Optional[Tuple[str, str]]:
        """(trace_id, span_id) of this thread's innermost open span."""
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    # ------------------------------------------------------------ recording

    def record(self, name: str, t0: float, t1: float,
               trace_id: Optional[str],
               parent_id: Optional[str] = None,
               attrs: Optional[Dict] = None,
               span_id: Optional[str] = None) -> str:
        """Record a span measured elsewhere (``t0``/``t1`` are
        ``time.perf_counter`` values).  Returns the span id so callers can
        parent further spans under it.

        A falsy ``trace_id`` records NOTHING and returns "" — this is the
        central ``sampled=0`` guard: hops that continue an unsampled
        trace context pass ``trace_id=None`` downstream (batcher,
        scheduler, stream) and every span silently vanishes without
        per-component flag plumbing.  ``span_id`` lets the caller use a
        pre-minted id (``new_span_id``) that already left the process in
        a trace-context header."""
        if not trace_id:
            return ""
        sid = span_id or self.new_span_id()
        span = Span(trace_id=trace_id, span_id=sid, parent_id=parent_id,
                    name=name, t0=t0, t1=t1,
                    thread=threading.current_thread().name,
                    attrs=dict(attrs or {}))
        with self._lock:
            self._recorded += 1
            self._spans.append(span)
        return sid

    def span(self, name: str, trace_id: Optional[str] = None,
             parent_id: Optional[str] = None, **attrs):
        """Context-managed span; nests via a thread-local stack.

        With no explicit ``trace_id`` the span joins this thread's current
        trace (becoming a child of the innermost open span) or starts a
        fresh trace when there is none.
        """
        return self._open(name, trace_id, parent_id, attrs, _Wall())

    def phase(self, name: str, trace_id: Optional[str] = None,
              parent_id: Optional[str] = None,
              start: Optional[float] = None, **attrs):
        """``span`` timed by a ``timed_phase``: one ring span, and while a
        profile runs one ``jax.profiler.TraceAnnotation`` event of the
        same name on the trace's host plane, held open for its length.
        The span's attrs gain the phase's ``cpu_ms`` / ``runq_ms``.  The
        annotation sees the attrs given here; attrs set on the yielded
        handle later go to the ring only.  ``start`` and the handle's
        ``ends_at`` are ``timed_phase``'s."""
        return self._open(name, trace_id, parent_id, attrs,
                          timed_phase(name, start, **attrs))

    @contextlib.contextmanager
    def _open(self, name: str, trace_id: Optional[str],
              parent_id: Optional[str], attrs: Dict,
              clock) -> Iterator[_Live]:
        cur = self.current()
        if trace_id is None:
            if cur is not None:
                trace_id = cur[0]
                if parent_id is None:
                    parent_id = cur[1]
            else:
                trace_id = self.new_trace_id()
        elif parent_id is None and cur is not None and cur[0] == trace_id:
            parent_id = cur[1]
        sid = self.new_span_id()
        live = _Live(trace_id, sid, dict(attrs), clock)
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append((trace_id, sid))
        try:
            with clock:
                live.t0 = clock.t0
                yield live
        finally:
            stack.pop()
            live.t0, live.t1 = clock.t0, clock.t1
            live.attrs.update(clock.thread_ms)
            span = Span(trace_id=trace_id, span_id=sid, parent_id=parent_id,
                        name=name, t0=clock.t0, t1=clock.t1,
                        thread=threading.current_thread().name,
                        attrs=live.attrs)
            with self._lock:
                self._recorded += 1
                self._spans.append(span)

    # -------------------------------------------------------------- reading

    @property
    def recorded(self) -> int:
        """Spans ever recorded (including ones the ring has dropped)."""
        with self._lock:  # vs a concurrent record() increment
            return self._recorded

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._recorded - len(self._spans)

    def spans(self, last: Optional[int] = None,
              trace_id: Optional[str] = None) -> List[Span]:
        """Most recent spans, oldest first; optionally the last ``last``
        and/or only one trace."""
        with self._lock:
            out = list(self._spans)
        if trace_id is not None:
            out = [s for s in out if s.trace_id == trace_id]
        if last is not None:
            out = out[-max(int(last), 0):]
        return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def to_chrome(self, last: Optional[int] = None,
                  trace_id: Optional[str] = None) -> Dict:
        return to_chrome_trace(self.spans(last=last, trace_id=trace_id))

    def export_json(self, last: Optional[int] = None,
                    trace_id: Optional[str] = None) -> str:
        return json.dumps(self.to_chrome(last=last, trace_id=trace_id))


def to_chrome_trace(spans: List[Span]) -> Dict:
    """Chrome trace-event JSON (the ``traceEvents`` array form).

    Every span becomes one complete ("ph": "X") event; trace/span/parent
    ids and attrs ride in ``args`` so Perfetto's query/filter UI can slice
    by request id.  Open at https://ui.perfetto.dev or chrome://tracing.
    """
    pid = os.getpid()
    threads = {}  # name -> stable synthetic tid (Perfetto wants ints)
    events = []
    for s in spans:
        tid = threads.setdefault(s.thread, len(threads) + 1)
        events.append({
            "ph": "X",
            "name": s.name,
            "cat": "obs",
            "ts": round(s.wall_t0 * 1e6, 3),
            "dur": round(s.duration_s * 1e6, 3),
            "pid": pid,
            "tid": tid,
            "args": {"trace_id": s.trace_id, "span_id": s.span_id,
                     "parent_id": s.parent_id, **s.attrs},
        })
    for name, tid in threads.items():
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": name}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}
