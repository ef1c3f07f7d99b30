"""Iteration-level continuous batching (docs/serving.md, "Scheduling").

RAFT-Stereo's anytime property makes GRU iteration count a per-request
serving knob; this package makes it a *scheduling* knob.  Instead of one
monolithic executable per request, the engine exposes the forward pass as
three phase executables (prologue / single-iteration step / epilogue,
``serve/engine.py``) and the :class:`IterationScheduler` advances one
running batch per shape bucket boundary by boundary — requests join free
slots and leave finished ones at iteration boundaries, LLM-continuous-
batching style.

* ``policy``    — pure priority/aging/deadline decisions (injected-clock
                  testable).
* ``scheduler`` — the running-batch state machine, admission control and
                  the scheduling worker thread.

Enable with ``--sched`` on ``python -m raftstereo_tpu.cli.serve``.
"""

from .policy import PRIORITIES, priority_class, should_exit  # noqa: F401
from .scheduler import IterationScheduler, SchedResult  # noqa: F401
