"""The share of the device's idle seconds that lie under none of the program's
annotations on the trace's host plane (``Tracer.phase`` /
``obs.trace.timed_phase``), over the capture as the program bracketed it: from
the ``obs.clock`` annotation its profiler emits when the capture starts to the
one it emits when it stops (the device is recorded past that; no host event
is), narrowed to the dispatch worker's first and last whole phase (a phase
open at either end of a capture leaves no event).  The host plane and the device plane are on one clock, so nothing is
fitted.  params: phases, the annotation names in three classes: ``work`` (the
dispatch worker's phases while it has a batch in hand), ``wire`` (the HTTP
threads' phases of one request) and ``wait`` (the worker with no batch to
run).  Threads overlap without nesting, so an idle instant goes to a ``work``
phase over it if there is one, else to a ``wire`` phase (why the worker
waits), else to a ``wait`` phase; within a class to the shortest.

Prints the idle seconds by phase, and, where the run has the program's ring spans, how far the ring's
``launch`` spans laid onto the trace through ``obs.clock`` lie from the
annotations of the same name.  A trace without ``obs.clock`` or without any of
the phases (a program that has neither) gives nothing."""

from benchmark import stages as st
from benchmark.child import say
from benchmark.loadgen.stats import percentile


def read(ctx, run, params):
    if not run.get("xplane"):
        return None
    rank = {name: i for i, cls in enumerate(("work", "wire", "wait"))
            for name in params["phases"][cls]}
    spans, clocks = st.host_annotations(run["xplane"], list(rank))
    if len(clocks) < 2 or not spans:
        return None
    # A phase that is open when the capture starts or stops leaves no event,
    # so the worker's first and last phase in the capture bound the window.
    cycle = [sp for sp in spans if rank[sp["name"]] != 1]
    if not cycle:
        return None
    window = (max(clocks[0]["t"], min(sp["t0"] for sp in cycle)),
              min(clocks[-1]["t"], max(sp["t1"] for sp in cycle)))
    by = st.attribute_gaps(st.device_gaps(run["xplane"]), spans, window,
                           rank)
    idle = sum(by.values())
    if idle <= 0:
        return None
    say(f"[idle] {window[1] - window[0]:.3f} s of the capture (obs.clock "
        f"pair {clocks[-1]['t'] - clocks[0]['t']:.3f} s apart) lie between "
        f"the worker's first and last whole phase; idle {idle:.4f} s, by "
        "the phase over it: "
        + ", ".join(f"{k} {v:.4f}" for k, v in
                    sorted(by.items(), key=lambda kv: -kv[1])))
    _check_clock(run, spans, clocks[0])
    return 100.0 * by.get("unattributed", 0.0) / idle


def _check_clock(run, spans, clock):
    """Ring spans laid onto the trace from the obs.clock pair: trace seconds
    = clock's trace time + (span's exported ts - clock's unix_ns)."""
    ring = [s for s in run.get("spans", []) if s["name"] == "launch"]
    trace = sorted(s["t0"] for s in spans if s["name"] == "launch")
    if not ring or not trace or "unix_ns" not in clock:
        return
    off = [min(abs(clock["t"] + (s["ts"] * 1e3 - clock["unix_ns"]) * 1e-9
                   - t) for t in trace) for s in ring]
    inside = [d for d in off if d < 0.1]        # a span of another moment
    if inside:
        say(f"[idle] obs.clock: {len(inside)} ring `launch` spans lie "
            f"{percentile(inside, 50) * 1e6:.0f} us (median), "
            f"{max(inside) * 1e6:.0f} us (max) from the host-plane "
            "annotation of the same name")
