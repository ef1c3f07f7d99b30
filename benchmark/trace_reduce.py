"""``.xplane.pb`` -> the numbers the per-layer metrics read.

Reads the profiler's file with nothing but JAX (``jax.profiler.ProfileData``;
no backend is initialised).  Only what is stable today is reduced: the union
of the intervals in which an operation ran on a device (busy), the gaps
between them, device time by operation name, and device time of the Pallas
kernels by the names a metric file lists.  The split by model stage needs
``jax.named_scope`` inside the program and is a later PR's.

    python -m benchmark.trace_reduce <file.xplane.pb>      # print a summary
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OP_LINES = ("XLA Ops",)            # one event per executed operation
# operations that only hold others (their time is their children's)
CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\d]*$")


def short_name(hlo: str) -> str:
    """The trace names an operation by its whole HLO line; the instruction
    name before `` = `` is what a reader wants, with the custom call's
    target beside it where there is one."""
    name = hlo.split(" = ", 1)[0].strip()
    m = re.search(r'custom_call_target="([^"]+)"', hlo)
    return f"{name} [{m.group(1)}]" if m else name
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"
MIN_FREE_S = 1.0    # less recorded past the host tracer's end is not a window


def _union(iv: List[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Total covered length and the merged intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(iv):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def _open(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def load_events(data) -> Dict[str, List[Dict]]:
    """{device plane: [{name, start_s, dur_s, detail}]} of every operation
    that ran on a device; ``detail`` joins the event's string statistics
    (a Pallas kernel's function name sits there, not in the name)."""
    out: Dict[str, List[Dict]] = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        evs = []
        for line in plane.lines:
            if line.name not in OP_LINES:
                continue
            for e in line.events:
                detail = " ".join(v for _, v in e.stats
                                  if isinstance(v, str))
                evs.append({"name": short_name(e.name),
                            "start_s": e.start_ns * 1e-9,
                            "dur_s": e.duration_ns * 1e-9,
                            "detail": e.name + " " + detail})
        if evs:
            out[plane.name] = evs
    return out


def host_tracer_end(data) -> Optional[float]:
    """When the profiler's host tracer stopped, on the trace's clock: the
    last end of an event on a host thread that is not the Python tracer's
    (those are named ``$...``).  While it runs, the runtime's host threads
    log millions of events (a ``Transpose`` for every tile the engine
    stages) and the host phases between two dispatches take ten times as
    long; the device is recorded for some seconds after it has stopped
    (PR 25, PERF.md section 3).  None where the file has no host plane."""
    last = None
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith("$"):
                    end = e.start_ns + e.duration_ns
                    if last is None or end > last:
                        last = end
    return None if last is None else last * 1e-9


def load_trace(path: str) -> Tuple[Dict[str, List[Dict]], Optional[float]]:
    """The device planes' operations and ``host_tracer_end``."""
    data = _open(path)
    return load_events(data), host_tracer_end(data)


def reduce_events(planes: Dict[str, List[Dict]],
                  host_spans: Optional[List[Dict]] = None,
                  top: int = 10, free_from: Optional[float] = None) -> Dict:
    """Busy and idle seconds averaged over the devices, the longest idle
    gaps, and device seconds by operation name (summed over the devices).

    Device seconds by name are taken over the whole trace (the host tracer
    does not change how long an operation runs).  Busy, window and gaps are
    taken over the seconds after ``free_from`` (``host_tracer_end``) where
    at least ``MIN_FREE_S`` of them were recorded, and ``tracer_free`` says
    so; else over the whole trace, each device's first operation start to
    its last end, with the host tracer's slowing in them (the same figures
    are always under ``traced``).  ``host_spans`` (``{name, t0, t1}`` on the
    trace's clock) name the gaps: a gap goes to the innermost span that
    covers its middle, or to ``uncovered``."""
    if not planes:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0,
                "idle_share": None, "tracer_free": False, "device_ops": [],
                "idle_gaps": []}
    by_name: Dict[str, float] = defaultdict(float)
    counts: Dict[str, int] = defaultdict(int)
    for evs in planes.values():
        for e in evs:
            if not CONTAINERS.match(e["name"]):
                by_name[e["name"]] += e["dur_s"]
                counts[e["name"]] += 1
    n = len(planes)
    traced = _busy_window(planes, None)
    free = _busy_window(planes, free_from) if free_from is not None else None
    tracer_free = free is not None and free[1] / n >= MIN_FREE_S
    busy, window, gaps = free if tracer_free else traced
    gap_by: Dict[str, float] = defaultdict(float)
    for length, a, b in gaps:
        gap_by[_covering(host_spans, (a + b) / 2)] += length
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "devices": n, "busy_s": busy / n, "window_s": window / n,
        "idle_share": 1.0 - busy / window if window > 0 else None,
        "tracer_free": tracer_free, "free_from_s": free_from,
        "traced": {"busy_s": traced[0] / n, "window_s": traced[1] / n},
        "device_ops": [[k, v / n] for k, v in ops],
        "device_op_calls": {k: counts[k] for k, _ in ops},
        "idle_gaps": [[k, v / n] for k, v in
                      sorted(gap_by.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gap_s": max((g[0] for g in gaps), default=0.0),
    }


def _busy_window(planes: Dict[str, List[Dict]], start: Optional[float]
                 ) -> Tuple[float, float, List[Tuple[float, float, float]]]:
    """Busy seconds, window seconds and the gaps (length, from, to), summed
    over the devices; from ``start`` on where that is given, else from each
    device's first operation; to each device's last end."""
    busy = window = 0.0
    gaps: List[Tuple[float, float, float]] = []
    for evs in planes.values():
        iv = [(e["start_s"], e["start_s"] + e["dur_s"]) for e in evs]
        if start is not None:
            iv = [(max(a, start), b) for a, b in iv if b > start]
        if not iv:
            continue
        b, merged = _union(iv)
        t0 = merged[0][0] if start is None else start
        busy += b
        window += merged[-1][1] - t0
        if merged[0][0] > t0:
            gaps.append((merged[0][0] - t0, t0, merged[0][0]))
        gaps += [(b0 - a1, a1, b0) for (_, a1), (b0, _) in
                 zip(merged, merged[1:])]
    return busy, window, gaps


def bursts(planes: Dict[str, List[Dict]], split_s: float = 0.01
           ) -> List[Tuple[float, float]]:
    """The first device's busy time as bursts: runs of operations with no
    gap longer than ``split_s`` (one dispatched program, as a rule)."""
    if not planes:
        return []
    evs = next(iter(planes.values()))
    _, merged = _union([(e["start_s"], e["start_s"] + e["dur_s"])
                        for e in evs])
    out = [list(merged[0])]
    for a, b in merged[1:]:
        if a - out[-1][1] > split_s:
            out.append([a, b])
        else:
            out[-1][1] = b
    return [(a, b) for a, b in out]


def clock_offset(planes: Dict[str, List[Dict]], sync_ends: List[float],
                 started_at: Optional[float] = None,
                 min_burst_s: float = 0.0) -> Optional[float]:
    """Seconds to add to the trace's clock to get the host's.

    The profiler stamps device events from its own start, the program's
    spans are on the host's wall clock.  ``sync_ends`` are host times at
    which the host saw a device program finish (the end of a span that ends
    in ``block_until_ready``); the end of each burst of at least
    ``min_burst_s`` (the staging copies before a dispatch are short bursts of
    their own) is one of them.  The offset
    that lays the bursts' ends closest onto such times is taken, among
    those within a few seconds of ``started_at`` (the host time at which
    the trace was asked for) where that is known: a saturated server's
    bursts are nearly periodic, and a whole period off fits almost as
    well."""
    ends = [b for a, b in bursts(planes)[1:-1]      # whole bursts only
            if b - a >= min_burst_s]
    if not ends or not sync_ends:
        return None
    best, best_cost = None, float("inf")
    for s in sync_ends:
        off = s - ends[0]
        if started_at is not None and not -0.5 <= off - started_at <= 4.0:
            continue
        cost = sum(min(abs(e + off - t) for t in sync_ends) for e in ends)
        if cost < best_cost:
            best, best_cost = off, cost
    return best if best_cost / len(ends) < 0.05 else None


def _covering(spans: Optional[List[Dict]], t: float) -> str:
    best, width = "uncovered", float("inf")
    for s in spans or ():
        if s["t0"] <= t <= s["t1"] and s["t1"] - s["t0"] < width:
            best, width = s["name"], s["t1"] - s["t0"]
    return best


def kernel_time(planes: Dict[str, List[Dict]], patterns: List[str]) -> Dict:
    """Device seconds and calls of the operations whose name or detail
    matches one of ``patterns`` (regular expressions), per device."""
    rx = [re.compile(p) for p in patterns]
    sec, calls = 0.0, 0
    for evs in planes.values():
        for e in evs:
            if any(r.search(e["name"]) or r.search(e["detail"]) for r in rx):
                sec += e["dur_s"]
                calls += 1
    n = max(len(planes), 1)
    return {"seconds": sec / n, "calls": calls / n}


def main(argv) -> int:
    planes, free_from = load_trace(argv[0])
    red = reduce_events(planes, top=25, free_from=free_from)
    print(json.dumps(red, indent=1))
    seen = set()
    for evs in planes.values():
        for e in sorted(evs, key=lambda e: -e["dur_s"]):
            if e["name"] not in seen and len(seen) < 25:
                seen.add(e["name"])
                print(f"{e['name']!r}: dur={e['dur_s']:.6f} "
                      f"detail={e['detail'][:300]!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
