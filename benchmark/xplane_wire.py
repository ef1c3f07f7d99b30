"""The profiler's ``.xplane.pb`` read from the protobuf wire format.

``jax.profiler.ProfileData`` (what ``trace_reduce`` reads) gives an event's
name, times and its own statistics, but not the statistics of the event's
*metadata* — and on a TPU that is where an operation's ``tf_op`` sits: the
``op_name`` path with the program's ``jax.named_scope`` stages in it (PERF.md
section 3).  No xplane bindings are installed, so this reads the few message
types needed with a varint loop:

    XSpace.planes=1
    XPlane.name=2 lines=3 event_metadata=4 (map) stat_metadata=5 (map)
    XLine.name=2 timestamp_ns=3 events=4
    XEvent.metadata_id=1 offset_ps=2 duration_ps=3 stats=4
    XEventMetadata.id=1 name=2 display_name=4 stats=5
    XStat.metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6 ref=7
    XStatMetadata.id=1 name=2

Times come back in seconds on the trace's one clock (a line's
``timestamp_ns`` plus the event's ``offset_ps``), which the host plane and
the device planes share.
"""

from __future__ import annotations

import gzip
import struct
from typing import Callable, Dict, Iterator, List, Optional, Tuple


def varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if not b & 0x80:
            return x, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value): an int for varints, else the raw
    bytes (a memoryview slice: nothing is copied)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = varint(buf, i)
            yield num, wt, v
        elif wt == 2:
            ln, i = varint(buf, i)
            yield num, wt, buf[i:i + ln]
            i += ln
        elif wt == 1:
            yield num, wt, buf[i:i + 8]
            i += 8
        elif wt == 5:
            yield num, wt, buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wt}")


def read_bytes(path: str) -> memoryview:
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return memoryview(f.read())
    with open(path, "rb") as f:
        return memoryview(f.read())


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, object]:
    mid, val = 0, None
    for num, wt, v in fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", bytes(v))[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif num == 6:
            val = bytes(v)
        elif num == 7:          # a reference to a stat metadata's name
            val = stat_names.get(v, v)
    return stat_names.get(mid, str(mid)), val


class Plane:
    """One XPlane: ``name``, ``event_meta`` {id: {"name", "display",
    "stats"}} and ``lines`` [(name, timestamp_ns, raw line bytes)]; events
    are parsed on demand (``events``)."""

    def __init__(self, buf):
        self.name = ""
        self.stat_names: Dict[int, str] = {}
        self.lines: List[Tuple[str, int, object]] = []
        self._meta_raw: Dict[int, object] = {}
        self._meta: Dict[int, Dict] = {}
        raw_lines = []
        for num, wt, v in fields(buf):
            if num == 2:
                self.name = bytes(v).decode()
            elif num == 3:
                raw_lines.append(v)
            elif num == 4:
                key, val = 0, None
                for n2, _, v2 in fields(v):
                    if n2 == 1:
                        key = v2
                    elif n2 == 2:
                        val = v2
                self._meta_raw[key] = val
            elif num == 5:
                for n2, _, v2 in fields(v):
                    if n2 == 2:
                        sid, sname = 0, ""
                        for n3, _, v3 in fields(v2):
                            if n3 == 1:
                                sid = v3
                            elif n3 == 2:
                                sname = bytes(v3).decode()
                        self.stat_names[sid] = sname
        for ln in raw_lines:
            name, ts = "", 0
            for n2, wt2, v2 in fields(ln):
                if n2 == 2:
                    name = bytes(v2).decode()
                elif n2 == 3:
                    ts = v2
                elif n2 == 4:
                    continue
            self.lines.append((name, ts, ln))

    def meta(self, mid: int) -> Dict:
        m = self._meta.get(mid)
        if m is None:
            m = {"name": "", "display": "", "stats": {}}
            raw = self._meta_raw.get(mid)
            if raw is not None:
                for num, _, v in fields(raw):
                    if num == 2:
                        m["name"] = bytes(v).decode("utf-8", "replace")
                    elif num == 4:
                        m["display"] = bytes(v).decode("utf-8", "replace")
                    elif num == 5:
                        k, val = _stat(v, self.stat_names)
                        m["stats"][k] = val
            self._meta[mid] = m
        return m

    def events(self, line_name: Optional[str] = None,
               keep: Optional[Callable[[Dict], bool]] = None,
               with_stats: bool = False,
               line_index: Optional[int] = None) -> Iterator[Dict]:
        """{line, meta (the event's metadata dict), start_s, dur_s, stats}
        of the plane's events: of the lines called ``line_name`` (threads
        may share a name), of the one line ``line_index``, or of all;
        ``keep(meta)`` drops an event before its statistics are read."""
        for i, (name, ts_ns, raw) in enumerate(self.lines):
            if line_name is not None and name != line_name:
                continue
            if line_index is not None and i != line_index:
                continue
            for num, _, ev in fields(raw):
                if num != 4:
                    continue
                mid = off = dur = 0
                stats = []
                for n2, _, v2 in fields(ev):
                    if n2 == 1:
                        mid = v2
                    elif n2 == 2:
                        off = v2
                    elif n2 == 3:
                        dur = v2
                    elif n2 == 4 and with_stats:
                        stats.append(v2)
                meta = self.meta(mid)
                if keep is not None and not keep(meta):
                    continue
                yield {"line": name, "meta": meta,
                       "start_s": ts_ns * 1e-9 + off * 1e-12,
                       "dur_s": dur * 1e-12,
                       "stats": dict(_stat(s, self.stat_names)
                                     for s in stats)}


def planes(path: str) -> List[Plane]:
    return [Plane(v) for num, _, v in fields(read_bytes(path)) if num == 1]
