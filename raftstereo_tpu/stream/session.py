"""Per-stream session state and its bounded store.

A :class:`Session` is everything the warm-start policy carries between the
frames of one video stream: the previous frame's low-resolution disparity
(kept at the PADDED bucket's 1/factor grid, so it is already the shape the
next dispatch's ``flow_init`` needs), the next expected sequence number, the
EMA of the per-frame update magnitude that drives the adaptive iteration
controller, and the controller's current ladder level.

The :class:`SessionStore` is deliberately forgiving: hitting the session
limit evicts the least-recently-used session, and an idle session past its
TTL expires — in both cases the client's next frame simply runs COLD (full
iterations, zero init) and re-establishes state.  Losing a session is a
performance event, never a correctness error, so the store never raises at
a client.  Evictions/expirations/active count are exported through
``ServeMetrics`` (``/metrics``).
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["STATE_VERSION", "Session", "SessionStore"]

# Versioned snapshot format for export_state/import_state.  Bump when the
# Session fields carried across replicas change shape or meaning; an
# importer seeing an unknown version falls back cold, never errors.
STATE_VERSION = 1

# The engine-level keys of the state-schema fingerprint.  Two stores may
# exchange warm state only when these agree: ``factor`` fixes the 1/f
# grid ``prev_disp_low`` lives on, ``input_mode`` fixes which executables
# the state feeds (a bucket served by one engine and not the other simply
# re-buckets cold at the next frame, so the bucket itself rides along
# informationally, not as a hard gate).  Only these keys are compared: a
# snapshot whose schema carries more (an older build's) imports warm.
_SCHEMA_KEYS = ("factor", "input_mode")

# Fixed accounted overhead of one session beyond the disparity plane:
# the controller scalars carried across frames (next_seq, frame_idx,
# ema, level, force_cold, warm/cold frame counters) at 8 bytes each.
_SESSION_OVERHEAD = 56


@dataclasses.dataclass
class Session:
    """Warm-start state for one stream (mutated under ``lock``)."""

    sid: str
    last_used: float = 0.0
    next_seq: int = 0
    frame_idx: int = 0
    # Previous frame's disparity at the padded bucket's 1/factor grid
    # ((H/f, W/f) float32, dataset sign convention); None until the first
    # frame completes.
    prev_disp_low: Optional[np.ndarray] = None
    bucket_hw: Optional[Tuple[int, int]] = None
    # EMA of mean |refined - warm-start init| (low-res px) and the
    # controller's current ladder level for the NEXT warm frame.
    ema: float = 0.0
    level: int = 1
    # Set by the controller when the EMA says the warm start lost the
    # scene: the next frame re-runs cold even though state exists.
    force_cold: bool = False
    warm_frames: int = 0
    cold_frames: int = 0
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)


class SessionStore:
    """Bounded LRU + TTL map of ``session_id -> Session``.

    ``now_fn`` is injectable so TTL tests don't sleep.  Thread-safe: the
    store lock covers only lookup/eviction bookkeeping; per-frame work
    serializes on each session's own lock (two frames of one session never
    interleave, while different sessions only contend on the engine).
    """

    def __init__(self, limit: int, ttl_s: float, metrics=None,
                 now_fn=time.monotonic, budget_mb: float = 0.0):
        assert limit >= 1, limit
        assert budget_mb >= 0, budget_mb
        self.limit = limit
        self.ttl_s = ttl_s
        # Byte budget over the accounted state total; 0 disables the
        # byte bound (count cap stays either way).
        self.budget_bytes = int(budget_mb * 2 ** 20)
        self.metrics = metrics
        self._now = now_fn
        self._lock = threading.Lock()
        # guarded_by: _lock
        self._sessions: "collections.OrderedDict[str, Session]" = \
            collections.OrderedDict()
        self._bytes: Dict[str, int] = {}    # guarded_by: _lock
        self._total_bytes = 0               # guarded_by: _lock

    def __len__(self) -> int:
        with self._lock:
            return len(self._sessions)

    def total_bytes(self) -> int:
        """Accounted bytes of all live session state (the value of the
        ``stream_session_bytes`` gauge)."""
        with self._lock:
            return self._total_bytes

    @staticmethod
    def _state_bytes(sess: Session) -> int:
        """Exact accounted bytes of one session's warm-start state: the
        disparity plane's nbytes plus the fixed controller overhead and
        the key.  Caller holds ``sess.lock`` (the plane is mutated
        under it)."""
        n = _SESSION_OVERHEAD + len(sess.sid.encode())
        if sess.prev_disp_low is not None:
            n += int(sess.prev_disp_low.nbytes)
        return n

    def account(self, sess: Session) -> None:
        """Re-account one session's state bytes after its plane changed
        (``StreamRunner.step`` / ``import_state`` call this right after
        writing ``prev_disp_low``).  Caller holds ``sess.lock``; the
        store lock is only ever taken after a session lock, never the
        reverse, so the order is deadlock-free.  May byte-budget-evict
        LRU sessions (never the one being accounted — it was just
        touched, so it is most-recent)."""
        n = self._state_bytes(sess)
        with self._lock:
            if sess.sid not in self._sessions:
                return  # evicted while its frame ran; nothing to track
            self._total_bytes += n - self._bytes.get(sess.sid, 0)
            self._bytes[sess.sid] = n
            self._evict_over_limits()
            self._refresh_bytes_gauge()

    def _forget_bytes(self, sid: str) -> None:  # guarded_by: _lock
        self._total_bytes -= self._bytes.pop(sid, 0)

    def _evict_over_limits(self) -> None:  # guarded_by: _lock
        """LRU-evict while over the count cap OR the byte budget.  The
        byte bound never evicts the last live session: a single
        over-budget stream is served (and surfaced on the gauge), not
        erroneously dropped mid-use."""
        while (len(self._sessions) > self.limit
               or (self.budget_bytes > 0
                   and self._total_bytes > self.budget_bytes
                   and len(self._sessions) > 1)):
            sid, _ = self._sessions.popitem(last=False)
            self._forget_bytes(sid)
            if self.metrics is not None:
                self.metrics.stream_evicted.inc()
                self.metrics.stream_active.add(-1)

    def _refresh_bytes_gauge(self) -> None:  # guarded_by: _lock
        if self.metrics is not None:
            self.metrics.stream_session_bytes.set(float(self._total_bytes))

    def get_or_create(self, sid: str) -> Tuple[Session, bool]:
        """Return ``(session, created)``, touching LRU order.

        An expired session is dropped and replaced by a fresh one
        (``created=True`` — the caller runs the frame cold); exceeding the
        limit evicts the least-recently-used session.  Never raises.
        """
        with self._lock:
            now = self._now()
            sess = self._sessions.get(sid)
            if sess is not None:
                if now - sess.last_used > self.ttl_s:
                    del self._sessions[sid]
                    self._forget_bytes(sid)
                    if self.metrics is not None:
                        self.metrics.stream_expired.inc()
                        self.metrics.stream_active.add(-1)
                    sess = None
                else:
                    sess.last_used = now
                    self._sessions.move_to_end(sid)
                    return sess, False
            sess = Session(sid, last_used=now)
            self._sessions[sid] = sess
            if self.metrics is not None:
                # Gauge.add is locked: concurrent HTTP threads create and
                # expire sessions in parallel, and an unlocked
                # read-modify-write would lose counts.
                self.metrics.stream_active.add(1)
            self._evict_over_limits()
            self._refresh_bytes_gauge()
            return sess, True

    def drop(self, sid: str) -> bool:
        """Explicitly end a session; True if it existed."""
        with self._lock:
            existed = self._sessions.pop(sid, None) is not None
            self._forget_bytes(sid)
            self._refresh_bytes_gauge()
            if existed and self.metrics is not None:
                self.metrics.stream_active.add(-1)
            return existed

    # ------------------------------------------------- migration (PR 13)

    def session_ids(self) -> List[str]:
        """Live session ids, LRU order (drain-time handoff iterates this)."""
        with self._lock:
            return list(self._sessions)

    def export_state(self, sid: str,
                     schema: Optional[Dict] = None) -> Optional[Dict]:
        """Versioned host-side snapshot of one session's warm-start state,
        or ``None`` when there is nothing warm to move (unknown session,
        or no completed frame yet — a session without ``prev_disp_low``
        re-establishes itself cold anywhere, so there is no asset).

        ``schema`` is the exporting engine's state-schema fingerprint
        (``BatchEngine.session_schema()``); the importer refuses a
        mismatched snapshot with a cold fallback, never an error.  The
        export serializes on the session's own lock, so a frame in
        flight completes first and the snapshot is always consistent
        (and the disparity copy is bitwise — a warm import is
        indistinguishable from having stayed)."""
        with self._lock:
            sess = self._sessions.get(sid)
        if sess is None:
            return None
        with sess.lock:
            if sess.prev_disp_low is None:
                return None
            return {
                "version": STATE_VERSION,
                "schema": dict(schema or {},
                               bucket=(list(sess.bucket_hw)
                                       if sess.bucket_hw else None)),
                "session_id": sess.sid,
                "next_seq": int(sess.next_seq),
                "frame_idx": int(sess.frame_idx),
                "prev_disp_low": np.ascontiguousarray(
                    sess.prev_disp_low).copy(),
                "bucket_hw": (tuple(sess.bucket_hw)
                              if sess.bucket_hw else None),
                "ema": float(sess.ema),
                "level": int(sess.level),
                "force_cold": bool(sess.force_cold),
                "warm_frames": int(sess.warm_frames),
                "cold_frames": int(sess.cold_frames),
            }

    def import_state(self, snapshot: Dict,
                     schema: Optional[Dict] = None) -> str:
        """Install an exported snapshot; returns the handoff outcome:

        * ``"warm"`` — state installed (or already at least as fresh
          here); the session's next in-order frame runs warm;
        * ``"cold_schema"`` — version or schema-fingerprint mismatch
          (documented cold fallback: nothing is installed, the next
          frame re-establishes state cold).

        Never raises at a caller: a malformed snapshot is a cold
        fallback, exactly like a lost session."""
        try:
            if int(snapshot.get("version", -1)) != STATE_VERSION:
                return "cold_schema"
            theirs = snapshot.get("schema") or {}
            ours = schema or {}
            if any(theirs.get(k) != ours.get(k) for k in _SCHEMA_KEYS):
                return "cold_schema"
            sid = str(snapshot["session_id"])
            prev = np.ascontiguousarray(snapshot["prev_disp_low"],
                                        dtype=np.float32)
            next_seq = int(snapshot["next_seq"])
            bucket = snapshot.get("bucket_hw")
            bucket = tuple(int(x) for x in bucket) if bucket else None
        except Exception:
            return "cold_schema"
        with self._lock:
            now = self._now()
            sess = self._sessions.get(sid)
            if sess is None:
                sess = Session(sid, last_used=now)
                self._sessions[sid] = sess
                if self.metrics is not None:
                    self.metrics.stream_active.add(1)
                self._evict_over_limits()
            else:
                sess.last_used = now
                self._sessions.move_to_end(sid)
        with sess.lock:
            # Monotonic guard: a concurrent per-frame handoff (or a frame
            # that already ran here) may have produced FRESHER state than
            # this snapshot — a stale import would rewind next_seq and
            # turn the client's next in-order frame cold (out_of_order).
            if sess.prev_disp_low is not None and sess.next_seq >= next_seq:
                return "warm"
            sess.next_seq = next_seq
            sess.frame_idx = int(snapshot["frame_idx"])
            sess.prev_disp_low = prev
            sess.bucket_hw = bucket
            sess.ema = float(snapshot["ema"])
            sess.level = int(snapshot["level"])
            sess.force_cold = bool(snapshot["force_cold"])
            sess.warm_frames = int(snapshot["warm_frames"])
            sess.cold_frames = int(snapshot["cold_frames"])
            self.account(sess)
        return "warm"
