"""Temporal warm-start sequence inference over the serving engine.

One :class:`StreamRunner` turns the serve layer's stateless per-request
engine into stateful video inference: each frame of a session is
initialized from the previous frame's disparity, forward-warped on the host
by ``ops/image.forward_interpolate`` (the RAFT warm-start policy — Teed &
Deng, ECCV 2020; see PAPERS.md) and fed through the model's ``flow_init``
hook at an adaptively reduced iteration count (controller.py).  All device
work goes through ``BatchEngine.infer_stream_batch``, so streams share the
serve layer's per-(bucket, iters) compile cache and shape policy — the HTTP
session path (serve/server.py) and the offline ``cli/stream.py`` runner
produce bitwise-identical disparities on the same frames (tested).

``run_sequence`` / ``compare_warm_cold`` are the offline evaluation
harness shared by ``cli/stream.py`` and the tier-1 acceptance tests:
warm-start streaming vs a cold-start full-iteration baseline on the same
frames, reporting EPE, temporal-consistency EPE, and the
iterations/latency saved.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config import StreamConfig
from ..ops.image import forward_interpolate
from .controller import AdaptiveIterController
from .session import SessionStore

__all__ = ["StreamResult", "StreamRunner", "build_stream_engine",
           "run_sequence", "compare_warm_cold"]


@dataclasses.dataclass
class StreamResult:
    """One answered frame: the disparity plus how it was computed."""

    disparity: np.ndarray  # (H, W) float32, dataset sign convention
    iters: int
    warm: bool
    frame_idx: int
    seq_no: int
    session_id: str
    update_ema: float
    latency_s: float
    included_compile: bool
    # Which cluster replica answered (serve/cluster/dispatcher.py);
    # None on the single-engine path.
    replica: Optional[str] = None


class StreamRunner:
    """Session-aware frame stepper over a ``BatchEngine``.

    The engine contract is ``bucket_of``, ``low_hw`` and
    ``infer_stream_batch`` (serve/engine.py).  Frames of one session
    serialize on the session lock; different sessions contend only on the
    engine's dispatch lock.
    """

    def __init__(self, engine, cfg: StreamConfig, metrics=None,
                 store: Optional[SessionStore] = None, tracer=None,
                 scheduler=None, publisher=None):
        self.engine = engine
        self.cfg = cfg
        self.metrics = metrics
        self.tracer = tracer  # obs.Tracer or None (tracing is optional)
        # Iteration-level scheduler (serve/sched/): when set, frames are
        # submitted as HIGH-priority short jobs through the shared
        # scheduler instead of dispatching batch-size-1 on the engine —
        # so a long plain request never head-of-line blocks a stream.
        self.scheduler = scheduler
        # Write-behind publisher to the durable session tier
        # (stream/tier.TierPublisher): completed frames enqueue their
        # session id, never block on the tier (docs/streaming.md
        # "Durable sessions").
        self.publisher = publisher
        self.controller = AdaptiveIterController(cfg)
        self.store = store or SessionStore(
            cfg.session_limit, cfg.session_ttl_s, metrics,
            budget_mb=cfg.session_budget_mb)

    # ---------------------------------------------- migration (PR 13)
    #
    # The replica-to-replica handoff seam: the cluster dispatcher and the
    # /debug/sessions HTTP endpoints move warm-start state between
    # StreamRunners through these two calls.  Pure host-side numpy plus
    # engine metadata — no device dispatch, no compiles, so migration is
    # invisible to the retrace guard.

    def export_session(self, session_id: str) -> Optional[Dict]:
        """Versioned snapshot of one session's warm-start state stamped
        with this engine's schema fingerprint, or None when there is
        nothing warm to move."""
        return self.store.export_state(
            session_id, schema=self.engine.session_schema())

    def import_session(self, snapshot: Dict) -> str:
        """Install a snapshot exported elsewhere; returns ``"warm"`` or
        the documented ``"cold_schema"`` fallback (never raises)."""
        return self.store.import_state(
            snapshot, schema=self.engine.session_schema())

    def evict_all(self) -> int:
        """Drop every live session (the ``evict_sessions`` chaos hook:
        session-store pressure as one event).  Returns sessions
        dropped.  Losing state is the store's documented cold fallback
        — each stream's next frame re-anchors cold, nothing errors.  A
        frame racing the sweep either finishes first (its session drops
        a moment later) or re-creates the session cold."""
        dropped = 0
        for sid in self.store.session_ids():
            if self.store.drop(sid):
                dropped += 1
        return dropped

    def step(self, session_id: str, seq_no: Optional[int],
             left: np.ndarray, right: np.ndarray,
             trace_id: Optional[str] = None,
             mode: Optional[str] = None) -> StreamResult:
        """Run one frame of a session; always answers (cold on any session
        miss — new, expired, evicted, out-of-sequence, or resized).
        ``trace_id`` tags the frame's warp/forward spans in the tracer.
        ``mode`` is the frame's resolved precision mode (accuracy tier,
        ops/quant.py): it selects the executable only — session state is
        a plain fp32 disparity field, so frames of one session may move
        between tiers without losing the warm start."""
        sess, _ = self.store.get_or_create(session_id)
        ctl = self.controller
        tracer = self.tracer
        if tracer is not None and trace_id is None:
            trace_id = tracer.new_trace_id()
        with sess.lock:
            t0 = time.perf_counter()
            if seq_no is None:
                seq_no = sess.next_seq  # implicit in-order client
            bucket = self.engine.bucket_of(left.shape)
            warm = (sess.prev_disp_low is not None
                    and not sess.force_cold
                    and seq_no == sess.next_seq
                    and sess.bucket_hw == bucket)
            if warm:
                init = forward_interpolate(sess.prev_disp_low)
                t_warp = time.perf_counter()
                if tracer is not None:
                    tracer.record("warp", t0, t_warp, trace_id,
                                  attrs={"session_id": session_id,
                                         "seq_no": seq_no})
                iters = ctl.warm_iters(sess.level)
                cold_reason = None
            elif sess.prev_disp_low is None:
                # Includes expired/evicted sessions: the store already
                # re-created them, so to this frame they are new.
                init, iters, cold_reason = None, ctl.cold_iters, "new"
            elif sess.force_cold:
                init, iters, cold_reason = None, ctl.cold_iters, "reset"
            elif seq_no != sess.next_seq:
                init, iters, cold_reason = None, ctl.cold_iters, \
                    "out_of_order"
            else:
                init, iters, cold_reason = None, ctl.cold_iters, "resized"
            t_fwd0 = time.perf_counter()
            if self.scheduler is not None:
                # High-priority short job through the shared scheduler:
                # the frame joins the running batch at the next iteration
                # boundary (its join/step/epilogue spans are recorded by
                # the scheduler under this trace id).
                res = self.scheduler.submit(
                    left, right, iters=iters, flow_init=init,
                    priority="high", trace_id=trace_id,
                    mode=mode).result(timeout=600)
                disp, low, compiled = (res.disparity, res.disp_low,
                                       res.included_compile)
                if tracer is not None:
                    tracer.record("forward", t_fwd0, time.perf_counter(),
                                  trace_id,
                                  attrs={"session_id": session_id,
                                         "seq_no": seq_no, "iters": iters,
                                         "warm": warm, "compile": compiled,
                                         "sched": True})
            else:
                disp, low, compiled = self.engine.infer_stream_batch(
                    [(left, right)], iters, [init], mode=mode)[0]
                if tracer is not None:
                    seg = getattr(self.engine, "last_segments", None)
                    fwd_end = (seg["dispatch"][1] if seg
                               else time.perf_counter())
                    tracer.record("forward", t_fwd0, fwd_end, trace_id,
                                  attrs={"session_id": session_id,
                                         "seq_no": seq_no, "iters": iters,
                                         "warm": warm, "compile": compiled})
                    if seg is not None:
                        tracer.record("host_fetch", *seg["host_fetch"],
                                      trace_id)
            if warm:
                delta = float(np.mean(np.abs(low - init)))
                sess.ema = ctl.update_ema(sess.ema, delta)
                sess.level, sess.force_cold = ctl.next_level(sess.level,
                                                             sess.ema)
                sess.warm_frames += 1
            else:
                sess.ema = 0.0
                sess.level = ctl.first_warm_level
                sess.force_cold = False
                sess.cold_frames += 1
            sess.prev_disp_low = low
            sess.bucket_hw = bucket
            sess.next_seq = seq_no + 1
            frame_idx = sess.frame_idx
            sess.frame_idx += 1
            ema = sess.ema
            # Byte-accurate store accounting: the plane just changed
            # (session lock held; the store lock nests strictly inside).
            self.store.account(sess)
            latency = time.perf_counter() - t0
        if self.publisher is not None:
            # Write-behind durability: enqueue the SID only — the
            # publisher's worker exports the freshest snapshot at send
            # time (natural per-session coalescing), so the frame's
            # request path never touches the tier.
            self.publisher.enqueue(session_id)
        if self.metrics is not None:
            if warm:
                self.metrics.stream_warm_frames.inc()
            else:
                self.metrics.stream_cold_frames.labels(
                    reason=cold_reason).inc()
            self.metrics.stream_frame_iters.observe(iters)
            if not compiled:
                self.metrics.stream_frame_latency.observe(latency)
        return StreamResult(
            disparity=disp, iters=iters, warm=warm, frame_idx=frame_idx,
            seq_no=seq_no, session_id=session_id, update_ema=ema,
            latency_s=latency, included_compile=compiled)


def build_stream_engine(model, variables, image_hw: Tuple[int, int],
                        stream_cfg: StreamConfig, *,
                        max_batch_size: int = 1, divis_by: int = 32,
                        bucket_multiple: int = 64, metrics=None):
    """An offline ``BatchEngine`` matching the serving shape policy.

    For bitwise parity with an HTTP server, pass the SAME ``divis_by``,
    ``bucket_multiple`` and ``max_batch_size`` the server runs — XLA only
    guarantees identical numerics for identical program shapes, and the
    engine pads every warm-start batch to ``max_batch_size``.
    """
    from ..config import ServeConfig
    from ..serve.engine import BatchEngine

    cfg = ServeConfig(
        port=0, divis_by=divis_by, bucket_multiple=bucket_multiple,
        buckets=(tuple(image_hw),), warmup=False,
        max_batch_size=max_batch_size,
        queue_limit=max(8 * max_batch_size, 16),
        iters=stream_cfg.ladder[0], degraded_iters=stream_cfg.ladder[-1],
        stream=stream_cfg)
    return BatchEngine(model, variables, cfg, metrics)


def _epe(pred: np.ndarray, gt: Optional[np.ndarray]) -> Optional[float]:
    if gt is None:
        return None
    return float(np.mean(np.abs(pred - gt[..., 0])))


def run_sequence(engine, frames: Sequence[Tuple], stream_cfg: StreamConfig,
                 warm: bool = True, session_id: str = "offline",
                 metrics=None, tracer=None) -> Dict:
    """Drive ``frames`` (``(left, right, gt?)`` tuples) through a fresh
    ``StreamRunner`` on ``engine``.

    ``warm=True`` replays them as ONE session (frame 0 cold, the rest
    warm-started); ``warm=False`` is the cold-start baseline — every frame
    in its own session, so each runs at ``ladder[0]`` with a zero init
    through the SAME executable (no extra compiles, directly comparable
    latencies).  Returns per-frame records plus the predictions (kept for
    temporal-consistency metrics and parity tests).
    """
    runner = StreamRunner(engine, stream_cfg, metrics, tracer=tracer)
    records: List[Dict] = []
    preds: List[np.ndarray] = []
    for t, frame in enumerate(frames):
        left, right, gt = (frame + (None,))[:3]
        sid = session_id if warm else f"{session_id}-cold-{t}"
        res = runner.step(sid, t if warm else 0, left, right)
        preds.append(res.disparity)
        records.append({
            "frame": t, "iters": res.iters, "warm": res.warm,
            "latency_ms": round(res.latency_s * 1e3, 3),
            "included_compile": res.included_compile,
            "update_ema": round(res.update_ema, 4),
            "epe": _epe(res.disparity, gt),
        })
    return {"records": records, "preds": preds}


def _tc_epe(preds: Sequence[np.ndarray],
            frames: Sequence[Tuple]) -> Optional[float]:
    """Temporal-consistency EPE: how far the predicted frame-to-frame
    disparity CHANGE strays from the ground-truth change, averaged over
    consecutive pairs — flicker that plain per-frame EPE cannot see."""
    if len(preds) < 2 or len(frames[0]) < 3 or frames[0][2] is None:
        return None
    errs = []
    for t in range(1, len(preds)):
        dp = preds[t] - preds[t - 1]
        dg = frames[t][2][..., 0] - frames[t - 1][2][..., 0]
        errs.append(float(np.mean(np.abs(dp - dg))))
    return float(np.mean(errs))


def _mean_latency(records: Sequence[Dict]) -> Optional[float]:
    """Mean over compile-free frames only (an FPS protocol must not charge
    the model for XLA compiles — same rule as eval/runner.py)."""
    xs = [r["latency_ms"] for r in records if not r["included_compile"]]
    return round(float(np.mean(xs)), 3) if xs else None


def compare_warm_cold(engine, frames: Sequence[Tuple],
                      stream_cfg: StreamConfig, metrics=None,
                      tracer=None) -> Dict:
    """Warm-start streaming vs the cold full-iteration baseline on the same
    frames; the summary is what ``cli/stream.py`` reports and what the
    acceptance test asserts."""
    # Cold first: it compiles only ladder[0]; the warm pass then adds the
    # warm levels, so each pass's first-frame compile flags are honest.
    cold = run_sequence(engine, frames, stream_cfg, warm=False,
                        session_id="baseline", metrics=metrics,
                        tracer=tracer)
    warm = run_sequence(engine, frames, stream_cfg, warm=True,
                        session_id="stream", metrics=metrics,
                        tracer=tracer)
    wr, cr = warm["records"], cold["records"]
    warm_iters_after_first = [r["iters"] for r in wr[1:]]
    warm_epe = wr[-1]["epe"]
    cold_epe = cr[-1]["epe"]
    summary = {
        "frames": len(frames),
        "ladder": list(stream_cfg.ladder),
        "warm_frames": sum(1 for r in wr if r["warm"]),
        "cold_iters_per_frame": float(stream_cfg.ladder[0]),
        "warm_mean_iters_after_first": (
            round(float(np.mean(warm_iters_after_first)), 3)
            if warm_iters_after_first else None),
        "warm_final_epe": warm_epe,
        "cold_final_epe": cold_epe,
        "final_epe_ratio": (round(warm_epe / cold_epe, 4)
                            if warm_epe is not None and cold_epe else None),
        "warm_tc_epe": _tc_epe(warm["preds"], frames),
        "cold_tc_epe": _tc_epe(cold["preds"], frames),
        "warm_mean_latency_ms": _mean_latency(wr),
        "cold_mean_latency_ms": _mean_latency(cr),
    }
    if warm_iters_after_first:
        summary["iters_saved_frac"] = round(
            1.0 - float(np.mean(warm_iters_after_first))
            / stream_cfg.ladder[0], 4)
    if summary["warm_mean_latency_ms"] and summary["cold_mean_latency_ms"]:
        summary["latency_saved_frac"] = round(
            1.0 - summary["warm_mean_latency_ms"]
            / summary["cold_mean_latency_ms"], 4)
    return {"summary": summary, "warm": wr, "cold": cr,
            "warm_preds": warm["preds"], "cold_preds": cold["preds"]}
