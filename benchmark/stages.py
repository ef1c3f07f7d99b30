"""Device time by the program's ``jax.named_scope`` stages, and the program's
annotations on the trace's host plane (``Tracer.phase``), both read from the
``.xplane.pb`` through ``xplane_wire``.

An operation's stage is the innermost of the stage names on its ``op_name``
path, which the profiler stores as the ``tf_op`` statistic of the operation's
*metadata* (a fusion carries its root's path; PERF.md section 3).  An
operation under no stage, or with no path at all (copies the compiler
inserted, the staging programs' operations), is ``unnamed``.  A program that
has no scopes (the parent of the PR that added them) has every operation
``unnamed``: ``stage_breakdown`` then returns None and the readers built on it
return nothing.
"""

from __future__ import annotations

import functools
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import xplane_wire
from benchmark.trace_reduce import CONTAINERS, DEVICE_PLANE, HOST_PLANE, _union

OP_LINE = "XLA Ops"
CLOCK = "obs.clock"
UNNAMED = "unnamed"


_WRAPPERS = re.compile(r"^(?:\w+\()+|\)+$")   # transpose(jvp(gru)) -> gru


def stage_of(op_name: str, stages: Sequence[str]) -> str:
    """The innermost stage on the path; a scope reads ``jvp(x)`` or
    ``transpose(jvp(x))`` under differentiation and is still ``x``."""
    for part in reversed(op_name.rstrip(":").split("/")):
        part = _WRAPPERS.sub("", part)
        if part in stages:
            return part
    return UNNAMED


@functools.lru_cache(maxsize=2)
def _planes(path: str) -> List[xplane_wire.Plane]:
    return xplane_wire.planes(path)     # one read of a file a run


@functools.lru_cache(maxsize=2)
def _device_events(path: str) -> Tuple[Tuple[str, str, float, float], ...]:
    """(name, op_name path, start_s, dur_s) of the first device's
    operations, in time order, without those that only hold others."""
    for plane in _planes(path):
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for e in plane.events(OP_LINE):
            name = e["meta"]["display"] or e["meta"]["name"].split(" = ")[0]
            if not CONTAINERS.match(name):
                ops.append((name, e["meta"]["stats"].get("tf_op") or "",
                            e["start_s"], e["dur_s"]))
        if ops:
            return tuple(sorted(ops, key=lambda o: o[2]))
    return ()


def device_ops(path: str, stages: Sequence[str]) -> List[Dict]:
    """[{name, stage, start_s, dur_s}] of the first device's operations."""
    return [{"name": name, "stage": stage_of(op_name, stages),
             "start_s": t0, "dur_s": dur}
            for name, op_name, t0, dur in _device_events(path)]


def stage_breakdown(path: str, stages: Sequence[str],
                    split_s: float = 0.01) -> Optional[Dict]:
    """Per whole dispatch in the trace: seconds by stage, ``unnamed``
    seconds, busy seconds (the union of the operations) and the model
    burst's length; ``unnamed_ops`` names what fell under no stage.

    A *model burst* is a run of operations with no gap over ``split_s``
    that holds staged operations; a dispatch is everything from the end of
    the model burst before it (so the staging copies that precede a program
    count to it) to its own end.  The first and the last model burst of a
    trace may be cut by its edges and are left out where two more remain."""
    ops = device_ops(path, stages)
    if not ops or all(o["stage"] == UNNAMED for o in ops):
        return None
    bursts: List[List[Dict]] = [[ops[0]]]
    end = ops[0]["start_s"] + ops[0]["dur_s"]
    for o in ops[1:]:
        if o["start_s"] - end > split_s:
            bursts.append([])
        bursts[-1].append(o)
        end = max(end, o["start_s"] + o["dur_s"])
    model = [i for i, b in enumerate(bursts)
             if any(o["stage"] != UNNAMED for o in b)]
    whole = model[1:-1] if len(model) >= 4 else model
    if not whole:
        return None
    by_stage: Dict[str, float] = defaultdict(float)
    unnamed_ops: Dict[str, float] = defaultdict(float)
    busy = length = 0.0
    for i in whole:
        prev = max((j for j in model if j < i), default=-1)
        mine = [o for b in bursts[prev + 1:i + 1] for o in b]
        for o in mine:
            by_stage[o["stage"]] += o["dur_s"]
            if o["stage"] == UNNAMED:
                unnamed_ops[o["name"]] += o["dur_s"]
        busy += _union([(o["start_s"], o["start_s"] + o["dur_s"])
                        for o in mine])[0]
        b = bursts[i]
        length += max(o["start_s"] + o["dur_s"] for o in b) - b[0]["start_s"]
    n = len(whole)
    return {"dispatches": n, "model_bursts": len(model),
            "stage_s": {s: by_stage.get(s, 0.0) / n for s in stages},
            "unnamed_s": by_stage.get(UNNAMED, 0.0) / n,
            "busy_s": busy / n, "burst_s": length / n,
            "unnamed_ops": sorted(((k, v / n) for k, v in
                                   unnamed_ops.items()),
                                  key=lambda kv: -kv[1])[:8]}


def host_annotations(path: str, names: Sequence[str]
                     ) -> Tuple[List[Dict], List[Dict]]:
    """The host plane's events called one of ``names`` as [{name, t0, t1}],
    and its ``obs.clock`` events as [{t, unix_ns, perf_counter_ns}] (the
    pair the program's profilers emit when a capture starts and stops)."""
    want = set(names) | {CLOCK}
    found, clocks = [], []
    for plane in _planes(path):
        if plane.name != HOST_PLANE:
            continue
        for e in plane.events(keep=lambda m: m["name"] in want,
                              with_stats=True):
            if e["meta"]["name"] == CLOCK:
                clocks.append({"t": e["start_s"], **e["stats"]})
            else:
                found.append({"name": e["meta"]["name"], "t0": e["start_s"],
                              "t1": e["start_s"] + e["dur_s"]})
    return found, sorted(clocks, key=lambda c: c["t"])


def device_gaps(path: str) -> List[Tuple[float, float]]:
    """The first device's idle intervals between its first operation's
    start and its last one's end."""
    ops = device_ops(path, ())
    _, merged = _union([(o["start_s"], o["start_s"] + o["dur_s"])
                        for o in ops])
    return [(a1, b0) for (_, a1), (b0, _) in zip(merged, merged[1:])]


def attribute_gaps(gaps: List[Tuple[float, float]], spans: List[Dict],
                   window: Tuple[float, float],
                   rank: Optional[Dict[str, int]] = None) -> Dict[str, float]:
    """Idle seconds inside ``window`` by the span ({name, t0, t1}) over each
    instant: of those that cover it the one of the lowest ``rank`` (spans of
    several threads overlap without nesting: the thread that feeds the
    device comes first), then the shortest; instants no span covers go to
    ``unattributed``.  One sweep over all the boundaries."""
    lo, hi = window
    rank = rank or {}
    marks = []          # (time, order, kind, payload)
    for a, b in gaps:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            marks += [(a, 1, "gap+", None), (b, 0, "gap-", None)]
    for i, s in enumerate(spans):
        if s["t1"] > lo and s["t0"] < hi:
            marks += [(s["t0"], 1, "span+", i), (s["t1"], 0, "span-", i)]
    marks.sort(key=lambda m: (m[0], m[1]))
    out: Dict[str, float] = defaultdict(float)
    active: Dict[int, Tuple[int, float]] = {}
    in_gap, t_prev = False, lo
    for t, _, kind, i in marks:
        if in_gap and t > t_prev:
            if active:
                j = min(active, key=active.get)
                out[spans[j]["name"]] += t - t_prev
            else:
                out["unattributed"] += t - t_prev
        t_prev = t
        if kind == "gap+":
            in_gap = True
        elif kind == "gap-":
            in_gap = False
        elif kind == "span+":
            active[i] = (rank.get(spans[i]["name"], 0),
                         spans[i]["t1"] - spans[i]["t0"])
        else:
            active.pop(i, None)
    return dict(out)
