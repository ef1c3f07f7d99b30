"""How the named spans' threads spent their wall time, in %: with part
"cpu" the summed ``cpu_ms`` (on a core), with part "runq" the summed
``runq_ms`` (runnable, waiting for a core), over the summed length of the
spans called any of ``spans`` that start inside the window and carry that
attr (``obs.trace.timed_phase`` puts both on every phase; ``runq_ms`` only
where the host's schedstat can be read).  The rest of the wall time the
threads were blocked: a lock, the GIL, a socket.  Nothing to read gives
nothing."""

from benchmark.readers import spans_in_window

_ATTR = {"cpu": "cpu_ms", "runq": "runq_ms"}


def read(ctx, run, params):
    attr = _ATTR[params["part"]]
    spans = [s for name in params["spans"]
             for s in spans_in_window(run, name)
             if attr in s.get("args", {})]
    wall_ms = sum(s["dur"] for s in spans) * 1e-3
    if wall_ms <= 0:
        return None
    return 100.0 * sum(s["args"][attr] for s in spans) / wall_ms
