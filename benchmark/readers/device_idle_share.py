"""1 - (union of the intervals in which an operation ran on the device) over
the seconds of the profiler's trace in which its host tracer no longer ran
(``trace_reduce.host_tracer_end``): under the host tracer the gaps between
dispatches are ten times their untraced length.  A trace with no such
seconds still gives a reading, over the whole of it and so with the tracer's
slowing in it; the run says so in a ``[trace]`` line (PERF.md section 3)."""


def read(ctx, run, params):
    tr = run.get("trace")
    if not tr or tr.get("idle_share") is None:
        return None
    return 100.0 * tr["idle_share"]
