"""Per-request result rows + the thread-safe recorder they land in.

One ``RequestRow`` per request is the harness's unit of truth: the SLO
report (slo.py), the capacity fit (capacity.py) and ``run_load``'s
legacy summary are all pure functions over the recorded rows — no
aggregate is maintained anywhere else, so every number in a verdict can
be re-derived from the rows it cites.

Deliberately stdlib-only (dataclasses + threading + math): the recorder
is imported by ``serve/client.py`` (whose ``run_load`` summarises
through it) and must not drag the rest of the harness — let alone the
model stack — into client-side tooling.
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Recorder", "RequestRow", "percentile", "summarize",
           "wire_bytes"]

#: Row outcomes, in the order the legacy ``run_load`` counted them.
OUTCOMES = ("ok", "shed", "timeout", "error")


@dataclasses.dataclass(frozen=True)
class RequestRow:
    """One replayed request, fully described.

    Times are milliseconds.  ``t_sched_ms``/``t_send_ms`` are offsets
    from the replay's t=0 (``nan`` for closed-loop traffic, which has no
    schedule); ``send_lag_ms`` is how late the send left relative to the
    schedule (0.0 = on time or early).  ``latency_ms`` is send-to-reply
    wall clock (``nan`` when no reply arrived).  ``deadline_hit`` is
    None when the request carried no deadline.
    """

    index: int
    outcome: str                      # ok | shed | timeout | error
    latency_ms: float
    t_sched_ms: float = math.nan
    t_send_ms: float = math.nan
    send_lag_ms: float = 0.0
    status: int = 0                   # HTTP status (0 = transport error)
    tier: str = "default"
    priority: str = ""
    deadline_ms: Optional[float] = None
    deadline_hit: Optional[bool] = None
    iters: Optional[int] = None       # requested target (None = default)
    iters_done: Optional[int] = None  # from response meta
    height: int = 0
    width: int = 0
    session: str = ""
    seq_no: Optional[int] = None
    warm: Optional[bool] = None       # session frames: warm-start engaged
    # Speculative tier cascade (serve/cascade/): the canonical schedule
    # that served this request ("" = single-tier path) and whether the
    # divergence trigger promoted it to the certified tier early.
    cascade: str = ""
    promoted_early: Optional[bool] = None
    degraded: bool = False
    backend: str = ""                 # X-Backend via the router
    request_id: str = ""
    wire: str = "json"                # request dialect: json | binary
    bytes_sent: int = 0               # request body bytes on the wire
    bytes_received: int = 0           # response body bytes on the wire

    def bucket(self) -> str:
        """Capacity-model bucket key: tier|iters|HxW (docs/slo_harness.md)."""
        iters = "auto" if self.iters is None else str(self.iters)
        return f"{self.tier}|{iters}|{self.height}x{self.width}"


class Recorder:
    """Append-only, thread-safe row store (load-gen workers share one)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rows: List[RequestRow] = []  # guarded_by: _lock

    def add(self, row: RequestRow) -> None:
        with self._lock:
            self._rows.append(row)

    def rows(self) -> Tuple[RequestRow, ...]:
        """Snapshot in append order (NOT request-index order under
        concurrency — sort by ``index`` for stream comparisons)."""
        with self._lock:
            return tuple(self._rows)

    def __len__(self) -> int:
        with self._lock:
            return len(self._rows)


def percentile(values: Sequence[float], q: float) -> float:
    """Exact q-th percentile (q in [0, 100], linear interpolation
    between order statistics — numpy's default, without numpy)."""
    assert 0.0 <= q <= 100.0, q
    vs = sorted(values)
    if not vs:
        return math.nan
    if len(vs) == 1:
        return vs[0]
    pos = (q / 100.0) * (len(vs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(vs) - 1)
    frac = pos - lo
    return vs[lo] * (1.0 - frac) + vs[hi] * frac


def outcome_counts(rows: Sequence[RequestRow]) -> Dict[str, int]:
    counts = {k: 0 for k in OUTCOMES}
    for r in rows:
        counts[r.outcome] = counts.get(r.outcome, 0) + 1
    return counts


def backend_split(rows: Sequence[RequestRow]) -> Dict[str, int]:
    """ok rows per answering backend (empty when not behind a router)."""
    split: Dict[str, int] = {}
    for r in rows:
        if r.outcome == "ok" and r.backend:
            split[r.backend] = split.get(r.backend, 0) + 1
    return split


def summarize(rows: Sequence[RequestRow], *, mode: str, requests: int,
              concurrency: int, wall_s: float,
              rate: Optional[float] = None,
              sequence_len: Optional[int] = None) -> Dict:
    """The legacy ``run_load`` stats dict, computed from rows.

    Key set and presence conditions are the historical contract
    (cli.serve --loadgen and its tests consume it):
    percentiles only when ok rows exist; ``late_sends``/
    ``send_lag_p99_ms`` only for rate-driven traffic; ``warm_frames``/
    ``cold_frames``/``sequence_len`` only under sequence replay.
    Percentiles are exact over the rows (the old path interpolated
    histogram buckets — same keys, sharper values).
    """
    counts = outcome_counts(rows)
    stats = {
        "mode": mode, "requests": requests, "concurrency": concurrency,
        "wall_s": round(wall_s, 3),
        "pairs_per_sec": (round(counts["ok"] / wall_s, 4)
                          if wall_s else 0.0),
        **counts,
    }
    if sequence_len is not None:
        stats["warm_frames"] = sum(1 for r in rows
                                   if r.outcome == "ok" and r.warm)
        stats["cold_frames"] = sum(1 for r in rows
                                   if r.outcome == "ok" and not r.warm)
        stats["sequence_len"] = sequence_len
    if rate:
        late = [r.send_lag_ms for r in rows if r.send_lag_ms > 0.0]
        stats["offered_rate"] = rate
        # How far behind schedule sends fell (0 = on time): large values
        # mean concurrency was too low for the offered rate and the run
        # degraded toward closed-loop.
        stats["late_sends"] = len(late)
        stats["send_lag_p99_ms"] = (round(percentile(late, 99), 2)
                                    if late else 0.0)
    lats = [r.latency_ms for r in rows if r.outcome == "ok"
            and not math.isnan(r.latency_ms)]
    if lats:
        stats.update(p50_ms=round(percentile(lats, 50), 2),
                     p90_ms=round(percentile(lats, 90), 2),
                     p99_ms=round(percentile(lats, 99), 2))
    wb = wire_bytes(rows)
    if wb is not None:
        stats.update(wb)
    split = backend_split(rows)
    if split:
        stats["backends"] = dict(sorted(split.items()))
    return stats


def wire_bytes(rows: Sequence[RequestRow]) -> Optional[Dict]:
    """Wire-byte summary over ok rows (None when nothing was counted —
    rows recorded by a pre-wire client).  ``wire_bytes_per_pair`` is the
    round-trip mean (request body + response body), the number the SLO
    verdict states alongside latency (docs/wire_format.md)."""
    ok = [r for r in rows
          if r.outcome == "ok" and (r.bytes_sent or r.bytes_received)]
    if not ok:
        return None
    total = sum(r.bytes_sent + r.bytes_received for r in ok)
    return {
        "wire_format": ok[0].wire,
        "wire_bytes_per_pair": round(total / len(ok), 1),
        "wire_mb_sent": round(sum(r.bytes_sent for r in ok) / 2 ** 20, 3),
        "wire_mb_received": round(sum(r.bytes_received for r in ok)
                                  / 2 ** 20, 3),
    }
