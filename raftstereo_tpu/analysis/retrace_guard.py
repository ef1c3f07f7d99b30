"""Runtime retrace guard: fail tests whose XLA compile count exceeds a
declared budget.

The static checkers (RSA1xx) catch retrace hazards they can see in the
AST; this is the runtime backstop that catches the rest: a context
manager (and pytest fixture, tests/conftest.py) that counts **actual
XLA backend compiles** through ``jax.monitoring``'s
``/jax/core/compile/backend_compile_duration`` event stream and raises
:class:`RetraceBudgetExceeded` when a guarded block compiles more than
its budget.

Two knobs::

    with retrace_guard(budget=2, what="2 buckets compile once"):
        ...                      # every compile counts

    with retrace_guard(0, min_duration_s=0.5, what="warm traffic"):
        ...                      # only model-scale compiles count

``min_duration_s`` exists because *any* first-seen host-side jnp op
(a new pad/concat shape) is a real-but-tiny XLA compile (milliseconds);
a model retrace is seconds.  E2e tests guard warm traffic with a 0.5 s
floor — far above op compiles, far below the tiny test models'
2-4 s compiles — so their budgets measure exactly the "zero compiles
beyond warmup" invariants (serve PR 1, stream PR 3, obs PR 5).  The
seeded-hazard unit tests use the default floor of 0 and count
everything.

The guard counts process-wide (any thread): e2e budgets deliberately
include compiles triggered on the batcher/stream worker threads.  It
REFUSES to run when a persistent JAX compilation cache is configured —
deserialized executables skip the backend-compile event, so the count
would be meaningless (CPU runs, where the guard is used, set no cache:
utils/platform.setup_compile_cache).

The one ``jax.monitoring`` listener this module registers also feeds
``subscribe``: the serving front-end counts every program the process
builds (``compile``) or reads back from the persistent cache
(``cache_load``) through it (``serve_xla_compiles_total``) — one listener,
however many guards and servers a process holds.  A cache read reports as
``/jax/compilation_cache/cache_retrieval_time_sec``, the duration event
JAX records beside ``/jax/compilation_cache/cache_hits``.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable, Iterator, List

__all__ = ["RetraceBudgetExceeded", "retrace_guard", "compile_events",
           "subscribe"]

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_lock = threading.Lock()
_installed = False
_durations: List[float] = []  # every backend compile since install
# fn(kind, duration_s), kind in {"compile", "cache_load"}; under _lock
_subscribers: List[Callable[[str, float], None]] = []


class RetraceBudgetExceeded(AssertionError):
    """A guarded block compiled more XLA executables than its budget."""


def _listener(event: str, duration: float, **kwargs) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        kind = "compile"
    elif event == _CACHE_LOAD_EVENT:
        kind = "cache_load"
    else:
        return
    with _lock:
        if kind == "compile":
            _durations.append(duration)
        subscribers = list(_subscribers)
    for fn in subscribers:  # outside the lock: they take their own
        fn(kind, duration)


def subscribe(fn: Callable[[str, float], None]) -> Callable[[], None]:
    """Call ``fn(kind, duration_s)`` for every program this process
    builds (``"compile"``) or loads from the persistent cache
    (``"cache_load"``) from now on, on whichever thread compiled.
    Returns the function that ends the subscription."""
    _ensure_installed()
    with _lock:
        _subscribers.append(fn)

    def unsubscribe() -> None:
        with _lock:
            if fn in _subscribers:
                _subscribers.remove(fn)

    return unsubscribe


def _ensure_installed() -> None:
    global _installed
    with _lock:
        if _installed:
            return
        # Flag flips only AFTER successful registration: a failure here
        # must stay loud on the next guard use, never leave the guard
        # silently counting zero compiles (registration itself only
        # appends to a listener list — it fires no events, so holding
        # the lock across it cannot deadlock with _listener).
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_listener)
        _installed = True


def compile_events() -> int:
    """Backend compiles observed since the guard was first installed."""
    _ensure_installed()
    with _lock:
        return len(_durations)


class GuardReport:
    """Filled in when the guarded block exits."""

    def __init__(self, budget: int, min_duration_s: float, what: str):
        self.budget = budget
        self.min_duration_s = min_duration_s
        self.what = what
        self.compiles = 0        # compiles >= min_duration_s
        self.all_compiles = 0    # every backend compile in the window
        self.durations: List[float] = []


@contextlib.contextmanager
def retrace_guard(budget: int, what: str = "",
                  min_duration_s: float = 0.0) -> Iterator[GuardReport]:
    """Fail with :class:`RetraceBudgetExceeded` when the block compiles
    more than ``budget`` XLA executables (of at least
    ``min_duration_s`` each).  Yields a :class:`GuardReport` whose
    counts are valid after the block exits."""
    assert budget >= 0, budget
    from ..utils.platform import compile_cache_dir

    cache_dir = compile_cache_dir()
    if cache_dir:
        raise RuntimeError(
            f"retrace_guard requires no persistent JAX compile cache "
            f"(JAX_COMPILATION_CACHE_DIR={cache_dir!r}): deserialized "
            "executables skip the backend-compile event, so budgets "
            "would not measure compiles (CPU runs set no cache: "
            "utils/platform.setup_compile_cache)")
    _ensure_installed()
    with _lock:
        start = len(_durations)
    report = GuardReport(budget, min_duration_s, what)
    yield report
    with _lock:
        window = _durations[start:]
    report.durations = window
    report.all_compiles = len(window)
    relevant = [d for d in window if d >= min_duration_s]
    report.compiles = len(relevant)
    if report.compiles > budget:
        label = f" [{what}]" if what else ""
        raise RetraceBudgetExceeded(
            f"retrace budget exceeded{label}: {report.compiles} XLA "
            f"compile(s) >= {min_duration_s:g}s against a budget of "
            f"{budget} ({report.all_compiles} total in the window; "
            f"durations "
            f"{[round(d, 3) for d in sorted(window, reverse=True)[:8]]})"
            " — a shape/closure/executable-cache key is retracing; see "
            "docs/static_analysis.md")
