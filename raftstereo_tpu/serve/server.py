"""HTTP front-end for the serving subsystem (stdlib ``http.server`` only).

Endpoints:

* ``POST /predict`` — two request dialects, negotiated per request
  (docs/wire_format.md):

  - JSON (``Content-Type: application/json``): body ``{"left": <array>,
    "right": <array>, "iters": optional int}``; an ``<array>`` is either
    a nested JSON list or the compact form ``{"shape": [H, W, 3],
    "dtype": "float32", "data_b64": "..."}``.
  - binary (``Content-Type: application/x-raftstereo-frame``): one wire
    frame (raftstereo_tpu/wire), decoded chunk-at-a-time straight into
    plane staging — the request never exists as body + decoded copies
    at once.  Responses are binary iff the request's ``Accept`` names
    the wire type; error replies are always JSON.

  ``iters`` must
  be one of the server's configured levels (``iters`` /
  ``degraded_iters`` — those executables are warmed; arbitrary values
  would compile under load).  Replies 200 with ``{"disparity": <array>,
  "meta": {...}}``, 503 ``overloaded`` when admission control sheds, 504
  on a per-request timeout, 400 on a malformed body.  Every reply carries
  an ``X-Request-Id`` header (also ``meta.request_id``) — the trace id of
  the request's spans in ``/debug/trace``.  Under the iteration-level
  scheduler (``--sched``, docs/serving.md) the body also accepts
  ``deadline_ms`` (deadline-aware early exit: the reply carries the
  anytime result with ``meta.degraded`` true) and ``priority``
  (``high``/``normal``/``low``), and ``iters`` may be any multiple of
  ``iters_per_step`` up to ``max_iters``.  On a spatially-sharded
  server (``--spatial_shards``, docs/serving.md "Spatial sharding")
  the body also accepts ``"spatial": true/false`` — pairs above the
  single-chip ``max_image_dim`` ceiling auto-route spatial when the
  capability is advertised on ``/healthz``.
* ``GET /metrics`` — Prometheus text exposition (serve/metrics.py).
* ``GET /healthz`` — JSON liveness: queue depth, compiled buckets, config.
* ``GET /debug/trace?last=N`` — recent spans as downloadable Chrome
  trace-event JSON (open at ui.perfetto.dev); ``trace_id=`` filters to
  one request.
* ``POST /debug/profile`` — body ``{"seconds": S}``: on-demand
  ``jax.profiler`` window; 409 while a capture is already running.
* ``GET /debug/threads`` — all-thread stack dump (the batcher/HTTP
  deadlock surface earns this).
* ``GET /debug/vars`` — resolved ServeConfig + build info + engine state.
* ``GET /debug/sessions/<id>`` / ``POST /debug/sessions`` — export /
  import one streaming session's warm-start state (the wire half of
  session migration, docs/serving.md "Session migration"): the router
  moves state between backends through these on drain, restart, or
  backend loss.  The disparity rides as raw base64 bytes
  (``encode_array``), so a warm import is bitwise-identical to having
  stayed.

``ThreadingHTTPServer`` gives one thread per connection; they all funnel
into the single ``DynamicBatcher`` queue, which is where concurrency is
actually managed (admission control + micro-batching), so the HTTP layer
stays dumb on purpose.
"""

from __future__ import annotations

import base64
import contextlib
import dataclasses
import json
import logging
import threading
import time
from http.server import ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple, Union
from urllib.parse import unquote, urlparse

import numpy as np

from .. import wire
from ..config import ServeConfig
from ..obs import Tracer, build_info, dump_threads, trace_response
from ..obs.trace import timed_phase
from ..utils.faults import FaultPlan
from ..utils.profiling import OnDemandProfiler, ProfilerBusy
from .batcher import DynamicBatcher, Overloaded, RequestTimedOut, ShuttingDown
from .engine import BatchEngine
from .httpbase import JsonRequestHandler
from .metrics import ServeMetrics, device_memory
from .sched import IterationScheduler
from .spatial import (SPATIAL_ENDPOINT, admit_spatial, route_spatial,
                      capability as spatial_capability)

logger = logging.getLogger(__name__)

__all__ = ["StereoServer", "UnsupportedSnapshotCodec", "build_server",
           "decode_array", "encode_array", "snapshot_to_wire",
           "wire_to_snapshot"]


def encode_array(a: np.ndarray) -> Dict:
    """Compact JSON-safe array encoding (raw bytes, base64)."""
    a = np.ascontiguousarray(a)
    return {"shape": list(a.shape), "dtype": str(a.dtype),
            "data_b64": base64.b64encode(a.tobytes()).decode("ascii")}


def decode_array(obj: Union[Dict, list]) -> np.ndarray:
    """Inverse of ``encode_array``; nested JSON lists also accepted."""
    if isinstance(obj, list):
        return np.asarray(obj, np.float32)
    a = np.frombuffer(base64.b64decode(obj["data_b64"]),
                      dtype=np.dtype(obj["dtype"]))
    return a.reshape(obj["shape"]).astype(np.float32, copy=False)


class UnsupportedSnapshotCodec(ValueError):
    """A snapshot wire form carries a disparity codec this build cannot
    decode.  Mixed-fleet contract (docs/streaming.md "Durable
    sessions"): the importer answers the documented ``cold_schema``
    fallback — never garbage state, never a hard error."""


def _quantize_plane_int8(x: np.ndarray):
    """Host-side numpy mirror of ``ops/quant.quantize_rows`` (per-row
    symmetric int8 over the last axis, zero-amax rows pinned to scale
    1.0).  Returns ``(q, scale, max_abs_err)``; the dequant
    ``q.astype(f32) * scale`` is the EXACT array a decoder reproduces
    (same single multiply, so encoder-measured error is decoder truth
    — the per-snapshot exactness manifest rides on it)."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    amax = np.max(np.abs(x), axis=-1)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(x / scale[..., None]), -127, 127).astype(np.int8)
    deq = q.astype(np.float32) * scale[..., None]
    return q, scale, float(np.max(np.abs(deq - x)))


def snapshot_to_wire(snapshot: Dict, compress: str = "off",
                     compress_bound: float = 0.05) -> Dict:
    """JSON form of a ``SessionStore.export_state`` snapshot.

    ``compress="off"`` encodes the disparity as raw f32 base64 bytes so
    the round trip is bitwise (the warm-handoff parity assertion
    depends on it).  ``compress="int8"`` rides the ops/quant.py per-row
    symmetric int8 scheme (~4x fewer snapshot bytes) and carries a
    per-snapshot exactness manifest ``{max_abs_err, bound}``; a plane
    whose quantization error would exceed ``compress_bound`` (low-res
    px) falls back to the bitwise raw form — compression never costs
    more warmth than the manifest certifies.  The schema fingerprint
    grows a ``snapshot_codec`` field when int8 is actually used, so a
    peer that cannot decode it refuses cleanly (``cold_schema``).  The
    router and the session tier relay these bodies verbatim without
    decoding."""
    wire = dict(snapshot)
    plane = np.ascontiguousarray(snapshot["prev_disp_low"], np.float32)
    wire["prev_disp_low"] = encode_array(plane)
    if compress == "int8":
        q, scale, err = _quantize_plane_int8(plane)
        if err <= compress_bound:
            wire["prev_disp_low"] = {
                "codec": "int8",
                "shape": list(plane.shape),
                "q_b64": base64.b64encode(q.tobytes()).decode("ascii"),
                "scale_b64": base64.b64encode(
                    scale.tobytes()).decode("ascii"),
                "manifest": {"max_abs_err": err,
                             "bound": float(compress_bound)},
            }
            wire["schema"] = dict(snapshot.get("schema") or {},
                                  snapshot_codec="int8-v1")
    if snapshot.get("bucket_hw"):
        wire["bucket_hw"] = list(snapshot["bucket_hw"])
    return wire


def _decode_plane(prev) -> np.ndarray:
    """Decode a wire disparity plane: raw f32 (``decode_array`` form),
    nested lists, or the int8 codec.  Unknown codecs raise
    :class:`UnsupportedSnapshotCodec` (mixed fleets fall back
    ``cold_schema``, never garbage)."""
    if isinstance(prev, dict) and "codec" in prev:
        if prev["codec"] != "int8":
            raise UnsupportedSnapshotCodec(
                f"unknown snapshot codec {prev['codec']!r}")
        shape = tuple(int(s) for s in prev["shape"])
        q = np.frombuffer(base64.b64decode(prev["q_b64"]),
                          dtype=np.int8).reshape(shape)
        scale = np.frombuffer(base64.b64decode(prev["scale_b64"]),
                              dtype=np.float32).reshape(shape[:-1])
        return q.astype(np.float32) * scale[..., None]
    return decode_array(prev)


def wire_to_snapshot(obj: Dict) -> Dict:
    """Inverse of ``snapshot_to_wire`` (tolerates nested-list arrays —
    same contract as ``decode_array``; int8-codec planes are exactly
    dequantized here)."""
    snap = dict(obj)
    prev = obj.get("prev_disp_low")
    if isinstance(prev, (dict, list)):
        snap["prev_disp_low"] = _decode_plane(prev)
    if obj.get("bucket_hw"):
        snap["bucket_hw"] = tuple(int(x) for x in obj["bucket_hw"])
    return snap


def _response_prefs(obj) -> Dict:
    """Wire-encode kwargs for a negotiated binary response.

    The optional request field ``response`` selects the disparity
    encoding: ``{"encoding": "f32"|"int16", "compress": bool}``.
    Anything unrecognized raises — surfacing as the caller's clean 400,
    never a mid-encode 500 after inference already ran."""
    prefs = {"encoding": "f32", "compress": True}
    if obj is None:
        return prefs
    if not isinstance(obj, dict):
        raise ValueError("response preferences must be an object")
    unknown = set(obj) - {"encoding", "compress"}
    if unknown:
        raise ValueError(
            f"unknown response preference(s) {sorted(unknown)}")
    enc = obj.get("encoding", "f32")
    if enc not in ("f32", "int16"):
        raise ValueError(
            f"unknown response encoding {enc!r} (choose f32 or int16)")
    prefs["encoding"] = enc
    prefs["compress"] = bool(obj.get("compress", True))
    return prefs


def _resolve_cascade(srv: "StereoServer", text: str):
    """Resolve an explicit ``accuracy=cascade:<schedule>`` request to an
    advertised ``CascadeSchedule``.  Raises ``ValueError`` (the caller's
    clean 400) on a grammar defect or an unadvertised/uncertified
    schedule — the message names the certification manifest so the
    operator knows exactly which gate refused it."""
    from .cascade.schedule import parse_schedule

    try:
        canonical = parse_schedule(text).schedule
    except ValueError as e:
        raise ValueError(f"bad cascade schedule: {e}") from None
    sched = srv.cascades.get(canonical)
    if sched is None:
        reason = srv.cascade_reasons.get(
            canonical, "schedule not offered by this server (--cascades)")
        manifest = srv.config.cert_manifest or "none configured"
        raise ValueError(
            f"cascade {canonical!r} not advertised: {reason} "
            f"(certification manifest: {manifest})")
    return sched


def _outcome(code: int, obj: Dict) -> str:
    """Label value for ``serve_requests_total{outcome=}``."""
    if code == 200:
        return "ok"
    if code == 400:
        return "bad_request"
    if code == 404:
        return "not_found"
    if code == 411:
        return "length_required"
    if code == 413:
        return "too_large"
    if code == 503:
        return "shed" if obj.get("error") == "overloaded" else "unavailable"
    if code == 504:
        return "timeout"
    return "error"


class _Handler(JsonRequestHandler):
    server_version = "raftstereo-serve/1.0"
    _log = logger  # request chatter to this module's logger, not stderr

    # Response-format negotiation for the CURRENT /predict request:
    # None = JSON reply, else the wire-encode kwargs.  Handler instances
    # are reused across keep-alive requests, so do_POST resets this at
    # the top of every /predict before any dispatch can read it.
    _wire_ctx: Optional[Dict] = None

    # (trace_id, parent_span_id) continued from the CURRENT /predict
    # request's X-Trace-Context (httpbase.trace_of) — trace_id None
    # means the upstream said sampled=0 and every span this request
    # records silently no-ops (obs/trace.py).  Reset per request for
    # the same keep-alive reuse reason as _wire_ctx.
    _trace: Optional[Tuple[Optional[str], Optional[str]]] = None

    # Pre-minted id of the CURRENT /predict request's ``admission`` span
    # (its ``wire_decode`` child names it before it is recorded).
    _adm_span: Optional[str] = None

    # ------------------------------------------------------------- plumbing
    # (_send/_json/_reject_body come from JsonRequestHandler, shared
    # byte-for-byte with the cluster router's handler.)
    def _finish(self, code: int, obj: Dict, endpoint: str, rid: str,
                t0: float,
                extra_headers: Optional[Dict[str, str]] = None) -> None:
        """Terminal JSON reply for a /predict request: attach the
        request id, count the labeled outcome, close the root trace
        span.  Error replies always land here — whatever was negotiated,
        an error body stays JSON (wire/negotiate.py)."""
        srv: "StereoServer" = self.server
        if code == 200 and "meta" in obj:
            obj["meta"]["request_id"] = rid
        headers = {"X-Request-Id": rid}
        headers.update(extra_headers or {})
        # Count + close the span BEFORE writing: a client that hangs up
        # mid-reply (BrokenPipeError out of _json) must still be counted,
        # and its trace must still have a root span.  The request span
        # therefore excludes the response write itself.
        outcome = _outcome(code, obj)
        srv.metrics.requests.labels(endpoint=endpoint, outcome=outcome).inc()
        tid, parent = self._trace if self._trace is not None else (rid, None)
        srv.tracer.record("request", t0, time.perf_counter(), tid,
                          parent_id=parent,
                          attrs={"endpoint": endpoint, "status": code,
                                 "outcome": outcome})
        body = json.dumps(obj).encode()
        if code == 200 and "disparity" in obj:
            srv.metrics.wire_bytes.labels(
                direction="out", format="json").inc(len(body))
        self._send(code, body, "application/json", headers)

    def _finish_ok(self, srv: "StereoServer", disparity: np.ndarray,
                   meta: Dict, endpoint: str, rid: str,
                   t0: float) -> None:
        """Terminal 200 for /predict: encode the disparity in whichever
        response format this request negotiated (``_wire_ctx``).  The
        whole of it — encode and write — is the request's ``reply``
        phase; it closes in a ``finally``, so a client that hangs up
        mid-write still leaves the span."""
        with self._req_phase(srv, "reply", rid) as ph:
            self._reply_ok(srv, disparity, meta, endpoint, rid, t0, ph)

    def _req_phase(self, srv: "StereoServer", name: str, rid: str,
                   parent_id: Optional[str] = None,
                   start: Optional[float] = None):
        """``Tracer.phase`` under this request's (continued) trace id;
        an unsampled request (trace id None) keeps the profiler
        annotation and records no span.  A child phase passes ``start``,
        where the phase before it (or its parent) began or ended, so the
        children of one phase tile it on shared clock reads."""
        tid = (self._trace or (rid, None))[0]
        if tid is None:
            return timed_phase(name, start)
        return srv.tracer.phase(name, trace_id=tid, parent_id=parent_id,
                                start=start)

    @contextlib.contextmanager
    def _wait_phase(self, srv: "StereoServer", name: str, point: str,
                    rid: str, start: float):
        """A ``_req_phase`` around the acquisition of one of the server's
        serial points (a child of the phase open on this thread); its
        seconds also go to ``serve_host_wait_seconds_total{point=}``."""
        with self._req_phase(srv, name, rid, start=start) as ph:
            yield ph
        srv.metrics.host_wait.labels(point=point).inc(ph.t1 - ph.t0)

    @staticmethod
    def _count_tiles(srv: "StereoServer", direction: str, ph,
                     census: Dict[str, int]) -> None:
        """A binary frame's tile census onto its phase's span
        (``tiles_stored``, ``tiles_deflated``, ``bytes_raw``,
        ``bytes_wire``) and into ``serve_wire_tiles_total``."""
        ph.attrs.update(census)
        for coding, key in (("stored", "tiles_stored"),
                            ("deflate", "tiles_deflated")):
            srv.metrics.wire_tiles.labels(
                direction=direction, coding=coding).inc(census[key])

    def _reply_ok(self, srv: "StereoServer", disparity: np.ndarray,
                  meta: Dict, endpoint: str, rid: str, t0: float,
                  ph) -> None:
        ctx = self._wire_ctx
        if ctx is None:
            self._finish(200, {"disparity": encode_array(disparity),
                               "meta": meta}, endpoint, rid, t0)
            return
        meta = dict(meta)
        meta["request_id"] = rid
        # reply_wait -> reply_encode -> reply_write tile `reply`
        with self._wait_phase(srv, "reply_wait", "reply_lock", rid,
                              ph.t0) as wait:
            srv.reply_encode.acquire()
        try:
            with self._req_phase(srv, "reply_encode", rid,
                                 start=wait.t1) as enc:
                frame = wire.encode_response(disparity, meta, **ctx)
        finally:
            srv.reply_encode.release()
        srv.metrics.wire_bytes.labels(
            direction="out", format="binary").inc(len(frame))
        self._count_tiles(srv, "out", ph, wire.tile_census(frame))
        srv.metrics.requests.labels(endpoint=endpoint, outcome="ok").inc()
        tid, parent = self._trace if self._trace is not None else (rid, None)
        srv.tracer.record("request", t0, time.perf_counter(), tid,
                          parent_id=parent,
                          attrs={"endpoint": endpoint, "status": 200,
                                 "outcome": "ok"})
        with self._req_phase(srv, "reply_write", rid, start=enc.t1):
            self._send(200, frame, wire.WIRE_CONTENT_TYPE,
                       {"X-Request-Id": rid})
            del frame  # freed inside the phase, not in reply's tail

    # ------------------------------------------------------------- endpoints
    def do_GET(self):
        srv: "StereoServer" = self.server
        # blackhole_backend chaos: hold EVERY reply (probes included —
        # they time out against probe_timeout_s, which is the point)
        # while a fault window is active; a no-op otherwise.
        self._maybe_blackhole()
        url = urlparse(self.path)
        if url.path == "/healthz":
            ready = srv.is_ready
            if srv.fault_plan.healthz_lie():
                # flap_probe chaos: this reply LIES ready=false on a
                # perfectly healthy server — probe flapping with no
                # underlying fault (the router must ride it out
                # without dropping accepted work).
                ready = False
            if srv.fault_plan.evict_due():
                # evict_sessions chaos: piggybacked on the probe
                # cadence — the store empties within one probe
                # interval of the armed offset, every live stream's
                # next frame re-anchors cold.
                srv.evict_sessions()
            health = {
                "status": "ok",
                # live vs ready (k8s-style): live = the process answers;
                # ready = warmup finished and not draining, i.e. traffic
                # routed here will not pay a cold compile.  The cluster
                # router gates on ready, never on live.
                "live": True,
                "ready": ready,
                "draining": srv.draining,
                "drained": srv.drained,
                "queue_depth": srv.queue_depth,
                "compiled_buckets": sorted(srv.engine.compiled_keys),
                "max_batch_size": srv.config.max_batch_size,
                "iters": srv.config.iters,
            }
            if srv.config.tiers:
                health["tiers"] = {
                    "advertised": {t: srv.tiers[t]
                                   for t in sorted(srv.tiers)},
                    "refused": dict(srv.tier_reasons),
                }
            if srv.cascades or srv.cascade_reasons:
                health["cascade"] = {
                    "advertised": sorted(srv.cascades),
                    "refused": dict(srv.cascade_reasons),
                    "divergence": srv.config.cascade_divergence,
                }
            if srv.cluster is not None:
                health["cluster"] = srv.cluster.stats()
            if srv.scheduler is not None:
                health["sched"] = srv.scheduler.stats()
            if srv.stream is not None:
                health["stream"] = {
                    "ladder": list(srv.config.stream.ladder),
                    "sessions_active": len(srv.stream.store),
                    "session_limit": srv.config.stream.session_limit,
                    "session_bytes": int(srv.stream.store.total_bytes()),
                    "session_budget_mb":
                        srv.config.stream.session_budget_mb,
                }
                if srv.tier_publisher is not None:
                    health["stream"]["tier"] = srv.tier_publisher.state()
            if getattr(srv.engine, "spatial_shards", 1) > 1:
                # Capability negotiation (serve/spatial/): a client
                # reads this block to learn whether — and at which
                # padded buckets — oversized pairs are served.
                health["spatial"] = spatial_capability(srv.config,
                                                      srv.engine)
            self._json(200, health)
        elif url.path == "/metrics":
            self._send(200, srv.metrics.render().encode(),
                       "text/plain; version=0.0.4")
        elif url.path == "/debug/trace":
            try:
                body, extra = trace_response(srv.tracer, url.query)
            except ValueError as e:  # e.g. ?last=abc
                self._json(400, {"error": f"bad query: {e}"})
                return
            self._send(200, body, "application/json", extra)
        elif url.path == "/debug/threads":
            self._send(200, dump_threads().encode(), "text/plain")
        elif url.path.startswith("/debug/sessions/"):
            # Session-state export (migration, docs/serving.md): the
            # snapshot serializes on the session lock, so an in-flight
            # frame completes first and the state is always consistent.
            sid = unquote(url.path[len("/debug/sessions/"):])
            snapshot = srv.export_session(sid)
            if snapshot is None:
                self._json(404, {"error": "no exportable state for "
                                          f"session {sid!r}"})
            else:
                scfg = srv.config.stream
                self._json(200, snapshot_to_wire(
                    snapshot, compress=scfg.snapshot_compress,
                    compress_bound=scfg.snapshot_compress_bound))
        elif url.path == "/debug/vars":
            lat = srv.metrics.latency
            self._json(200, {
                "config": dataclasses.asdict(srv.config),
                "build": build_info(),
                # Live request-latency percentiles (utils/profiling
                # quantile) — operators see p50/p99 without a
                # Prometheus stack.  null until the first request.
                "latency": ({
                    "count": lat.count,
                    "p50_ms": round(lat.quantile(0.5) * 1e3, 3),
                    "p99_ms": round(lat.quantile(0.99) * 1e3, 3),
                } if lat.count else None),
                "engine": {
                    "compiled_buckets": sorted(srv.engine.compiled_keys),
                    # per warmed bucket program: the lookup kernel's
                    # blocks and which encoder stages run fused
                    "programs": srv.engine.compiled_programs,
                    "queue_depth": srv.queue_depth,
                    "stream_sessions": (len(srv.stream.store)
                                        if srv.stream is not None else None),
                },
                "sched": (srv.scheduler.stats()
                          if srv.scheduler is not None else None),
                "cluster": (srv.cluster.stats()
                            if srv.cluster is not None else None),
                "tiers": {"advertised": dict(srv.tiers),
                          "refused": dict(srv.tier_reasons)},
                "ready": srv.is_ready,
                "draining": srv.draining,
                "trace": {"capacity": srv.tracer.capacity,
                          "recorded": srv.tracer.recorded,
                          "dropped": srv.tracer.dropped},
                "profile_running": srv.profiler.running,
                "memory": device_memory(),
            })
        else:
            self._json(404, {"error": f"no such path {self.path!r}"})

    def _debug_profile(self, srv: "StereoServer") -> None:
        """POST /debug/profile: bounded on-demand jax.profiler window,
        mutually exclusive with any running capture (HTTP 409)."""
        try:
            length = int(self.headers.get("Content-Length", 0) or 0)
        except ValueError:
            length = -1
        if length < 0 or length > 1 << 16:  # tiny JSON only
            self.close_connection = True
            self._json(400, {"error": "bad Content-Length"})
            return
        raw = self.rfile.read(length) if length else b""
        try:
            payload = json.loads(raw) if raw else {}
            seconds = float(payload.get("seconds", 3.0))
        except Exception as e:
            self._json(400, {"error": f"bad request: {e}"})
            return
        try:
            info = srv.profiler.start(seconds)
        except ProfilerBusy as e:
            self._json(409, {"error": "profile already running",
                             "detail": str(e)})
            return
        except ValueError as e:
            self._json(400, {"error": str(e)})
            return
        self._json(200, info)

    def do_POST(self):
        srv: "StereoServer" = self.server
        # blackhole_backend chaos (see do_GET): requests are accepted
        # and parsed, replies held until the window closes — late, not
        # lost.  Arming POSTs land BEFORE their own window starts
        # (@t_ms offsets are measured from arming), so /debug/faults
        # itself is never blocked by the fault it arms.
        self._maybe_blackhole()
        path = urlparse(self.path).path
        if path == "/debug/profile":
            self._debug_profile(srv)
            return
        if path == "/debug/faults":
            # Runtime fault arming ({"faults": SPEC}) — the chaos
            # controller's seam (loadgen/chaos.py).
            raw = self._read_body(srv.config.max_body_mb)
            if raw is None:
                return
            try:
                spec = json.loads(raw or b"{}").get("faults", "")
                armed = srv.fault_plan.extend(str(spec or ""))
            except ValueError as e:
                self._json(400, {"error": f"bad fault spec: {e}"})
                return
            self._json(200, {"armed": [f.spec() for f in armed]})
            return
        if path == "/debug/drain":
            # Explicit drain (the router's scale-in/maintenance hook):
            # stop admitting /predict traffic, let everything already
            # queued or running finish, report drained on /healthz.
            # Drain any request body first (the router dialect sends
            # {"backend": ...}; unread bytes would desync keep-alive).
            if self._read_body(srv.config.max_body_mb) is None:
                return
            srv.start_drain()
            self._json(200, {"draining": True, "drained": srv.drained,
                             "queue_depth": srv.queue_depth,
                             "inflight": srv.inflight})
            return
        if path == "/debug/sessions":
            # Session-state import (migration): installs an exported
            # snapshot so the session's next in-order frame runs warm.
            # Cold fallbacks reply 200 with the outcome — losing warmth
            # is a performance event, never an error (PR 3 contract).
            raw = self._read_body(srv.config.max_body_mb)
            if raw is None:
                return
            if srv.stream is None:
                self._json(400, {"error": "streaming disabled on this "
                                          "server"})
                return
            try:
                obj = json.loads(raw)
                sid = str(obj.get("session_id", ""))
                snapshot = wire_to_snapshot(obj)
            except UnsupportedSnapshotCodec:
                # Mixed-fleet contract: a codec this build cannot
                # decode is the documented cold fallback, not an error
                # — the session re-anchors cold here, never garbage.
                self._json(200, {"session_id": sid,
                                 "outcome": "cold_schema"})
                return
            except Exception as e:
                self._json(400, {"error": f"bad snapshot: {e}"})
                return
            outcome = srv.import_session(snapshot)
            self._json(200, {"session_id": sid, "outcome": outcome})
            return
        # A router in front forwards its request id so the hop's spans
        # and the backend's spans share one trace (docs/observability.md).
        rid = (self.headers.get("X-Request-Id") or "")[:64] \
            or srv.tracer.new_trace_id()
        # Cross-hop trace continuation: a valid X-Trace-Context pins
        # this request's spans to the upstream trace (the parent is the
        # router's hop span); absent/malformed falls back to rid-as-
        # trace-id, sampled=0 suppresses every span.
        self._trace = self.trace_of(rid)
        t_req0 = time.perf_counter()
        endpoint = "predict"
        # Reset per request — the handler instance is reused across
        # keep-alive requests, so stale negotiation must never leak.
        self._wire_ctx = None
        # Refuse before buffering (shared body cap + chunked-encoding
        # policy; connection marked close): the reply rides through
        # _finish so the 411/413 is counted and traced like every other
        # /predict outcome.
        reject = self._reject_body(srv.config.max_body_mb)
        if reject is not None:
            code, payload = reject
            if code == 413:
                payload["limit_mb"] = srv.config.max_body_mb
            self._finish(code, payload, endpoint, rid, t_req0)
            return
        length = self._body_length
        binary_in = wire.is_wire_content_type(
            self.headers.get("Content-Type"))
        binary_out = wire.accepts_wire(self.headers.get("Accept"))
        srv.metrics.wire_bytes.labels(
            direction="in",
            format="binary" if binary_in else "json").inc(length)
        srv.metrics.wire_negotiations.labels(
            request="binary" if binary_in else "json",
            response="binary" if binary_out else "json").inc()
        # Bound CONCURRENT buffering, not just per-request size: each
        # in-flight JSON decode transiently holds body + base64 text +
        # decoded arrays (~3x the body); the binary path streams the
        # body chunk-at-a-time into plane staging (wire.FrameDecoder),
        # so it holds decoded arrays + one 64 KiB chunk — the slot then
        # bounds concurrent decoded pairs.  Without this, a handful of
        # parallel near-limit POSTs OOM the host before queue_limit
        # ever engages.
        # Body read + decode is the request's ``wire_decode`` phase, a
        # child of the ``admission`` span (whose id is minted here so
        # the child can name it before the parent is recorded).
        self._adm_span = srv.tracer.new_span_id()
        with self._req_phase(srv, "wire_decode", rid,
                             parent_id=self._adm_span) as ph:
            decoded = self._read_pair(srv, length, binary_in, binary_out,
                                      endpoint, rid, t_req0, ph)
        if decoded is None:  # already answered
            return
        (left, right, iters, session_id, seq_no, deadline_ms, priority,
         accuracy, spatial) = decoded
        del decoded
        try:
            self._predict_admitted(srv, endpoint, rid, t_req0, left, right,
                                   iters, session_id, seq_no, deadline_ms,
                                   priority, accuracy, spatial)
        finally:
            srv.end_predict()

    def _read_pair(self, srv: "StereoServer", length: int, binary_in: bool,
                   binary_out: bool, endpoint: str, rid: str,
                   t_req0: float, ph):
        """Read and decode one /predict body under a decode slot.
        Returns ``(left, right, iters, session_id, seq_no, deadline_ms,
        priority, accuracy, spatial)`` with the in-flight count taken
        (the caller owes ``end_predict``), or None when the request was
        refused and already answered."""
        # binary: decode_slot_wait -> body_read -> widen tile the
        # request's wire_decode
        with self._wait_phase(srv, "decode_slot_wait", "decode_slot", rid,
                              ph.t0) \
                if binary_in else contextlib.nullcontext() as slot:
            srv.decode_slots.acquire()
        try:
            if binary_in:
                # Decoded planes may legitimately exceed the body byte
                # count (tile compression, uint8->float32 promotion is
                # 4x) — cap the allocation a header can demand at 8x
                # the body cap instead of the raw cap itself.
                dec = wire.FrameDecoder(
                    expect=wire.FRAME_REQUEST,
                    max_payload_bytes=int(
                        srv.config.max_body_mb * 2 ** 20) * 8)
                try:
                    with self._req_phase(srv, "body_read", rid,
                                         start=slot.t1) as body:
                        complete = self._read_body_stream(length, dec.feed)
                except wire.WireError as e:
                    # Mid-body reject: the unread remainder can never
                    # be reframed — the connection must close.
                    self.close_connection = True
                    prefix = ("" if isinstance(e, wire.WireVersionError)
                              else "bad wire frame: ")
                    self._finish(400, {"error": f"{prefix}{e}"},
                                 endpoint, rid, t_req0)
                    return None
                raw = b""
            else:
                # Drain the body BEFORE any reply: under HTTP/1.1
                # keep-alive, unread body bytes would be parsed as the
                # next request line.
                parts = []
                complete = self._read_body_stream(length, parts.append)
                raw = b"".join(parts)
                del parts
            if not complete:
                self._finish(400, {"error": "body shorter than "
                                            "Content-Length"},
                             endpoint, rid, t_req0)
                return None
            if self.path != "/predict":
                self._finish(404, {"error": f"no such path {self.path!r}"},
                             "other", rid, t_req0)
                return None
            # Readiness gate + in-flight count, atomically: a warming
            # server must not accept traffic (the request would stall
            # behind the warmup compiles), a draining one must not
            # admit new work — and an ADMITTED request is counted in
            # flight from the same lock acquisition, so drain's "finish
            # everything admitted" contract covers requests still
            # decoding or validating (``drained`` must never read true
            # while a request sits between the gate and dispatch).
            if not srv.try_begin_predict():
                detail = ("draining" if srv.draining
                          else "not ready (warming up)")
                self._finish(503, {"error": "unavailable",
                                   "detail": detail},
                             endpoint, rid, t_req0, {"Retry-After": "1"})
                return None
            try:
                if binary_in:
                    self._count_tiles(srv, "in", ph, dec.census())
                    # from the body's end: the gate and census above,
                    # then the decoded planes as float32
                    with self._req_phase(srv, "widen", rid, start=body.t1):
                        req = dec.request()
                        # Mirror decode_array's contract: the engine
                        # always sees float32 (exact for uint8/int16
                        # payloads).
                        left = np.ascontiguousarray(req.left, np.float32)
                        right = np.ascontiguousarray(req.right,
                                                     np.float32)
                        payload = req.fields
                        # the decoded planes go here, not in the
                        # parent's tail
                        del dec, req
                else:
                    payload = json.loads(raw)
                    left = decode_array(payload["left"])
                    right = decode_array(payload["right"])
                iters = payload.get("iters")
                session_id = payload.get("session_id")
                seq_no = payload.get("seq_no")
                deadline_ms = payload.get("deadline_ms")
                # Deadline propagation (docs/fault_tolerance.md): a
                # router hop forwards the client's remaining budget in
                # X-Deadline-Ms, already decremented by its own elapsed
                # time.  Merge via min() — the tighter of body field
                # and header wins — but only where a body deadline
                # would be accepted anyway (scheduler present, cold
                # request): elsewhere the header is silently ignored,
                # a propagated hint must never 400 a request that
                # did not ask for a deadline contract.
                hdr = self.headers.get("X-Deadline-Ms")
                if (hdr is not None and srv.scheduler is not None
                        and session_id is None):
                    try:
                        hdr_ms = float(hdr)
                    except ValueError:
                        hdr_ms = None
                    if hdr_ms is not None:
                        deadline_ms = (hdr_ms if deadline_ms is None
                                       else min(float(deadline_ms),
                                                hdr_ms))
                priority = payload.get("priority")
                accuracy = payload.get("accuracy")
                spatial = payload.get("spatial")
                if binary_out:
                    self._wire_ctx = _response_prefs(
                        payload.get("response"))
            except Exception as e:
                srv.end_predict()
                self._finish(400, {"error": f"bad request: {e}"},
                             endpoint, rid, t_req0)
                return None
            del raw, payload
        finally:
            srv.decode_slots.release()
        return (left, right, iters, session_id, seq_no, deadline_ms,
                priority, accuracy, spatial)

    def _predict_admitted(self, srv: "StereoServer", endpoint, rid, t_req0,
                          left, right, iters, session_id, seq_no,
                          deadline_ms, priority, accuracy=None,
                          spatial=None) -> None:
        """Validation + dispatch of one admitted (gate-passed, decoded,
        in-flight-counted) /predict request."""
        # Downstream span recording (admission, batcher/scheduler
        # phases, stream warp) keys on the CONTINUED trace id — None
        # (sampled=0) makes every one a no-op without flag plumbing.
        tid = (self._trace or (rid, None))[0]
        mode = None
        cascade = None
        use_spatial = False
        try:
            # Channel count follows the model's input mode (sl/,
            # docs/structured_light.md): 3 for passive RGB, 12 for SL
            # pattern-conditioned stacks.  A mismatched request is a clean
            # 400 — there is no executable (nor cache key) for the other
            # modality on this engine.
            want_c = srv.engine.input_channels
            if left.ndim != 3 or left.shape[-1] != want_c \
                    or left.shape != right.shape:
                raise ValueError(
                    f"expected matching (H, W, {want_c}) pairs for "
                    f"input_mode={srv.engine.input_mode!r}, got "
                    f"{left.shape} / {right.shape}")
            # Spatial routing decides BEFORE the single-chip ceiling:
            # pairs above max_image_dim are exactly what the spatial
            # path exists for (serve/spatial/admission.py).  admit_
            # spatial rejects every v1 limitation (tiers, sessions,
            # scheduler fields, unwarmed buckets) as a clean 400, so
            # the remaining checks below are inert on this path.
            use_spatial = route_spatial(spatial, left.shape,
                                        srv.config, srv.engine)
            if use_spatial:
                endpoint = SPATIAL_ENDPOINT
                _, iters = admit_spatial(
                    srv.config, srv.engine, iters, accuracy, session_id,
                    deadline_ms, priority, left.shape)
            elif not srv.config.admits(*left.shape[:2]):
                raise ValueError(
                    f"image side {max(left.shape[:2])} exceeds "
                    f"max_image_dim {srv.config.max_image_dim} and "
                    f"{left.shape[0]}x{left.shape[1]} fits no shape "
                    f"listed in --buckets")
            if accuracy is not None:
                # Accuracy tiers (ops/quant.py, docs/serving.md): only
                # ADVERTISED tiers resolve — a tier the certification
                # manifest refused (or a server without tiers) answers
                # with the recorded reason, never a silently-degraded
                # result or an unwarmed compile.  Cascades resolve
                # first: explicit "cascade:<schedule>" requests, and
                # "certified" rides the cheapest certified cascade when
                # one is offered (its answer still leaves the fp32
                # executables — that is the cascade contract).
                accuracy = str(accuracy)
                if accuracy.startswith("cascade:"):
                    cascade = _resolve_cascade(
                        srv, accuracy[len("cascade:"):])
                elif accuracy == "certified" and srv.cascades:
                    from .cascade.schedule import cheapest

                    cascade = cheapest(srv.cascades.values())
                if cascade is None:
                    if accuracy not in srv.tiers:
                        reason = srv.tier_reasons.get(
                            accuracy, "tier not offered by this server "
                                      "(--tiers)")
                        raise ValueError(
                            f"accuracy tier {accuracy!r} not advertised: "
                            f"{reason}")
                    mode = srv.tiers[accuracy]
                    if mode == srv.engine.default_mode:
                        # The tier IS the default path's program (e.g.
                        # "certified" on an fp32 server): normalize to
                        # None so the batcher/scheduler group it WITH
                        # default traffic — same executable, shared
                        # batches, one running state per bucket.
                        mode = None
                elif iters is not None:
                    raise ValueError(
                        f"iters is fixed by the cascade schedule "
                        f"{cascade} (omit it)")
                elif session_id is not None:
                    raise ValueError(
                        "session frames cannot run as cascades (v1): "
                        "the warm-start state is single-tier")
            if srv.scheduler is None and (deadline_ms is not None
                                          or priority is not None):
                raise ValueError(
                    "deadline_ms/priority require the iteration-level "
                    "scheduler (start the server with --sched)")
            if session_id is not None and (deadline_ms is not None
                                           or priority is not None):
                raise ValueError(
                    "session frames are scheduled as high-priority short "
                    "jobs; deadline_ms/priority cannot be set per frame")
            if session_id is not None:
                # Streaming frame: validated here, then dispatched outside
                # this block (the session path bypasses the micro-batcher).
                endpoint = "stream"
                if srv.stream is None:
                    raise ValueError(
                        "streaming disabled on this server (start with a "
                        "stream config / without --no_stream)")
                if iters is not None:
                    raise ValueError(
                        "iters cannot be combined with session_id: the "
                        "adaptive controller owns per-frame iterations "
                        "(configure --stream_ladder)")
                session_id = str(session_id)
                if seq_no is not None:
                    seq_no = int(seq_no)
                if not srv.config.cold_buckets:
                    hw = srv.engine.bucket_of(left.shape)
                    if srv.scheduler is not None:
                        # Scheduled frames ride the phase executables:
                        # every ladder level is served by the same step
                        # executable, so warmth is per bucket, not level.
                        if not srv.engine.is_sched_warm(
                                hw, srv.config.sched.iters_per_step,
                                mode=mode):
                            raise ValueError(
                                f"shape {tuple(left.shape[:2])} -> bucket "
                                f"{hw} not sched-warmed; configure "
                                f"--buckets")
                    else:
                        missing = [lv for lv in srv.config.stream.ladder
                                   if not srv.engine.is_stream_warm(
                                       hw, lv, mode=mode)]
                        if missing:
                            raise ValueError(
                                f"shape {tuple(left.shape[:2])} -> bucket "
                                f"{hw} stream levels {missing} not warmed; "
                                f"configure --buckets and --stream_warmup")
            if iters is not None and not use_spatial:
                iters = int(iters)
                if srv.scheduler is not None:
                    # Iteration-level scheduling serves ANY target from
                    # the same step executable — only the cap and the
                    # boundary granularity constrain it (no per-iters
                    # compile to protect against).
                    sc = srv.config.sched
                    if not 1 <= iters <= sc.max_iters \
                            or iters % sc.iters_per_step:
                        raise ValueError(
                            f"iters {iters} not served; must be a "
                            f"multiple of {sc.iters_per_step} in "
                            f"[1, {sc.max_iters}]")
                else:
                    # Only the configured (warmed) iteration levels:
                    # arbitrary client values would each compile a fresh
                    # executable under the engine lock — a trivially
                    # triggered latency DoS.
                    allowed = {srv.config.iters, srv.config.degraded_iters}
                    if iters not in allowed:
                        raise ValueError(
                            f"iters {iters} not served; choose from "
                            f"{sorted(allowed)}")
            if session_id is None and not use_spatial \
                    and not srv.config.cold_buckets:
                # Production setting (plain requests; session frames and
                # spatial requests have their own executable checks
                # above): shapes outside the warmed buckets are rejected
                # up front — an on-demand compile would stall every
                # queued request behind it.
                hw = srv.engine.bucket_of(left.shape)
                if srv.scheduler is not None:
                    if cascade is not None:
                        if not srv.engine.is_cascade_warm(
                                hw, srv.config.sched.iters_per_step,
                                cheap_mode=cascade.cheap_mode,
                                cert_mode=cascade.cert_mode):
                            raise ValueError(
                                f"shape {tuple(left.shape[:2])} -> "
                                f"bucket {hw} not cascade-warmed; "
                                f"configure it in --buckets")
                    elif not srv.engine.is_sched_warm(
                            hw, srv.config.sched.iters_per_step,
                            mode=mode):
                        raise ValueError(
                            f"shape {tuple(left.shape[:2])} -> bucket "
                            f"{hw} not sched-warmed; configure it in "
                            f"--buckets")
                else:
                    want = iters if iters is not None else srv.config.iters
                    if not srv.engine.is_warm(hw, want, mode=mode):
                        raise ValueError(
                            f"shape {tuple(left.shape[:2])} -> bucket {hw} "
                            f"(iters {want}) not warmed; configure it in "
                            f"--buckets")
        except Exception as e:
            self._finish(400, {"error": f"bad request: {e}"},
                         endpoint, rid, t_req0)
            return
        # Decode + validation done: the admission span closes where the
        # request either enters the batcher queue or the session path.
        srv.tracer.record("admission", t_req0, time.perf_counter(), tid,
                          attrs={"endpoint": endpoint,
                                 "shape": list(left.shape)},
                          span_id=self._adm_span)
        if use_spatial:
            self._spatial_dispatch(srv, endpoint, rid, t_req0,
                                   left, right, iters)
            return
        if session_id is not None:
            # Session frames bypass the micro-batcher: ordering within a
            # session is the point (frame N warm-starts from N-1), so they
            # serialize on the session lock and then the engine lock.
            # Admission control still applies — queue_limit bounds the
            # frames waiting on those locks, so a slow batch or compile
            # sheds stream traffic with 503s (holding decoded arrays in
            # unboundedly many blocked handler threads would grow host
            # RSS exactly like the unbounded queue the plain path rejects).
            with srv.stream_inflight_lock:
                if srv.stream_inflight >= srv.config.queue_limit:
                    srv.metrics.shed.inc()
                    self._finish(503, {"error": "overloaded",
                                       "detail": f"stream frames in flight "
                                                 f">= queue_limit "
                                                 f"{srv.config.queue_limit}"},
                                 endpoint, rid, t_req0,
                                 {"Retry-After": "1"})
                    return
                srv.stream_inflight += 1
            try:
                res = srv.stream.step(session_id, seq_no, left, right,
                                      trace_id=tid, mode=mode)
            except Overloaded as e:
                # Sched mode: the frame is a scheduler job and admission
                # can shed it there too — same backpressure contract as
                # the plain path (503 + Retry-After, never a 500).
                self._finish(503, {"error": "overloaded",
                                   "detail": str(e)},
                             endpoint, rid, t_req0, {"Retry-After": "1"})
                return
            except RequestTimedOut as e:
                self._finish(504, {"error": "timeout", "detail": str(e)},
                             endpoint, rid, t_req0)
                return
            except (TimeoutError, ShuttingDown) as e:
                self._finish(503, {"error": "unavailable",
                                   "detail": str(e)},
                             endpoint, rid, t_req0)
                return
            except Exception as e:
                self._finish(500, {"error": f"inference failed: {e}"},
                             endpoint, rid, t_req0)
                return
            finally:
                with srv.stream_inflight_lock:
                    srv.stream_inflight -= 1
            meta = {"session_id": res.session_id, "seq_no": res.seq_no,
                    "frame_idx": res.frame_idx, "iters": res.iters,
                    "warm": res.warm,
                    "update_ema": round(res.update_ema, 4),
                    "latency_ms": round(res.latency_s * 1e3, 3)}
            if accuracy is not None:
                meta["accuracy"] = accuracy
            if res.replica is not None:
                meta["replica"] = res.replica
            # Counted at the 200, not at admission: a request shed or
            # 400'd downstream was not SERVED at this tier, and the
            # metric is the per-tier adoption signal.
            srv.metrics.tier_requests.labels(
                tier=accuracy or "default").inc()
            self._finish_ok(srv, res.disparity, meta, endpoint, rid,
                            t_req0)
            return
        # Size the HTTP-side wait for what can actually be ahead of this
        # request: one in-flight batch (60 s) — or a cold XLA compile,
        # which takes minutes; with the 60 s slack a cold-bucket request
        # would get a spurious 503 while the server finishes the compile
        # and discards the result.
        hw = srv.engine.bucket_of(left.shape)
        if srv.scheduler is not None:
            ips = srv.config.sched.iters_per_step
            if cascade is not None:
                warm = srv.engine.is_cascade_warm(
                    hw, ips, cheap_mode=cascade.cheap_mode,
                    cert_mode=cascade.cert_mode)
            else:
                warm = srv.engine.is_sched_warm(hw, ips, mode=mode)
        else:
            levels = ([iters] if iters is not None
                      else [srv.config.iters, srv.config.degraded_iters])
            warm = all(srv.engine.is_warm(hw, lv, mode=mode)
                       for lv in levels)
        slack = 60.0 if warm else 600.0
        try:
            if srv.scheduler is not None:
                kwargs = dict(iters=iters, priority=priority,
                              deadline_ms=deadline_ms, trace_id=tid,
                              mode=mode)
                if cascade is not None:
                    # Keyword only when set: in cluster mode the
                    # dispatcher fills the scheduler slot and predates
                    # the cascade contract (cascades are refused there,
                    # so this branch never fires against it).
                    kwargs["cascade"] = cascade
                fut = srv.scheduler.submit(left, right, **kwargs)
            else:
                fut = srv.batcher.submit(left, right, iters,
                                         trace_id=tid, mode=mode)
        except ValueError as e:  # bad priority/deadline/target (sched)
            self._finish(400, {"error": f"bad request: {e}"},
                         endpoint, rid, t_req0)
            return
        except Overloaded as e:
            self._finish(503, {"error": "overloaded", "detail": str(e)},
                         endpoint, rid, t_req0, {"Retry-After": "1"})
            return
        except ShuttingDown:
            self._finish(503, {"error": "shutting down"},
                         endpoint, rid, t_req0)
            return
        try:
            # The batcher/scheduler enforces request_timeout_ms while
            # queued; the slack covers whatever can run ahead (batch
            # or cold compile).
            res = fut.result(
                timeout=srv.config.request_timeout_ms / 1000.0 + slack)
        except RequestTimedOut as e:
            self._finish(504, {"error": "timeout", "detail": str(e)},
                         endpoint, rid, t_req0)
            return
        except (TimeoutError, ShuttingDown) as e:
            self._finish(503, {"error": "unavailable",
                               "detail": str(e)},
                         endpoint, rid, t_req0)
            return
        except Exception as e:
            self._finish(500, {"error": f"inference failed: {e}"},
                         endpoint, rid, t_req0)
            return
        if srv.scheduler is not None:
            meta = {"iters": res.iters,
                    "target_iters": res.target_iters,
                    "degraded": res.degraded, "priority": res.priority,
                    "batch_slots": res.batch_slots,
                    "latency_ms": round(res.latency_s * 1e3, 3)}
            if getattr(res, "cascade", None) is not None:
                meta["cascade"] = res.cascade
                meta["promoted_early"] = res.promoted_early
        else:
            meta = {"iters": res.iters, "degraded": res.degraded,
                    "batch_size": res.batch_size,
                    "latency_ms": round(res.latency_s * 1e3, 3)}
        if accuracy is not None:
            meta["accuracy"] = accuracy
        if res.replica is not None:
            meta["replica"] = res.replica
        # Counted at the 200 (see the session path): only requests
        # actually served at the tier feed the adoption signal.
        srv.metrics.tier_requests.labels(tier=accuracy or "default").inc()
        self._finish_ok(srv, res.disparity, meta, endpoint, rid, t_req0)

    def _spatial_dispatch(self, srv: "StereoServer", endpoint, rid, t_req0,
                          left, right, iters) -> None:
        """Dispatch one admitted spatial request: straight to
        ``engine.infer_spatial``, bypassing the batcher AND the
        iteration scheduler (v1) — the pair owns the whole (1, N) mesh
        for its dispatch, so there is nothing to batch with and no
        iteration boundary to join at.  Admission control still
        applies: handler threads blocked on the engine lock are bounded
        by queue_limit, the same backpressure contract as the session
        path (decoded 4K pairs held in unboundedly many blocked threads
        would grow host RSS exactly like an unbounded queue)."""
        tid = (self._trace or (rid, None))[0]
        with srv.spatial_inflight_lock:
            if srv.spatial_inflight >= srv.config.queue_limit:
                srv.metrics.shed.inc()
                srv.metrics.spatial_requests.labels(outcome="shed").inc()
                self._finish(503, {"error": "overloaded",
                                   "detail": f"spatial requests in flight "
                                             f">= queue_limit "
                                             f"{srv.config.queue_limit}"},
                             endpoint, rid, t_req0, {"Retry-After": "1"})
                return
            srv.spatial_inflight += 1
        t0 = time.perf_counter()
        try:
            disp, _low, compiled = srv.engine.infer_spatial(
                left, right, iters)
        except Exception as e:
            srv.metrics.spatial_requests.labels(outcome="error").inc()
            self._finish(500, {"error": f"inference failed: {e}"},
                         endpoint, rid, t_req0)
            return
        finally:
            with srv.spatial_inflight_lock:
                srv.spatial_inflight -= 1
        t1 = time.perf_counter()
        srv.tracer.record("spatial_dispatch", t0, t1, tid,
                          attrs={"shards": srv.engine.spatial_shards,
                                 "iters": iters, "compile": compiled})
        srv.metrics.spatial_requests.labels(outcome="ok").inc()
        if not compiled:
            # Compile-free dispatches only, like the stream/sched
            # latency histograms — a cold_buckets compile would put a
            # minutes-long sample in a seconds-scale histogram.
            srv.metrics.spatial_latency.observe(t1 - t0)
        meta = {"iters": iters, "spatial": srv.engine.spatial_shards,
                "warm": not compiled,
                "latency_ms": round((t1 - t0) * 1e3, 3)}
        # Spatial serves only the base precision (admission rejects
        # tiers), so the adoption signal lands on the default tier.
        srv.metrics.tier_requests.labels(tier="default").inc()
        self._finish_ok(srv, disp, meta, endpoint, rid, t_req0)


class StereoServer(ThreadingHTTPServer):
    """HTTP server owning the engine + batcher + metrics + tracer.

    ``config.port == 0`` binds an ephemeral port; read the real one from
    ``server.server_address[1]`` (tests do).
    """

    daemon_threads = True

    def __init__(self, config: ServeConfig, engine: BatchEngine,
                 batcher: Optional[DynamicBatcher], metrics: ServeMetrics,
                 stream=None, tracer: Optional[Tracer] = None,
                 scheduler: Optional[IterationScheduler] = None,
                 cluster=None, start_ready: bool = True,
                 tiers: Optional[Dict[str, str]] = None,
                 tier_reasons: Optional[Dict[str, str]] = None,
                 cascades: Optional[Dict[str, object]] = None,
                 cascade_reasons: Optional[Dict[str, str]] = None,
                 fault_plan: Optional[FaultPlan] = None):
        assert (batcher is None) != (scheduler is None), (
            "exactly one of batcher (monolithic dispatch) or scheduler "
            "(iteration-level continuous batching) must be set")
        self.config = config
        # Advertised accuracy tiers (tier -> precision mode) and the
        # refusal reasons for requested-but-uncertified ones
        # (eval/certify.resolve_tiers; build_server fills both).  Direct
        # construction defaults to NO tiers — any `accuracy` field is a
        # clean 400, and no tier executables are ever compiled.
        self.tiers = dict(tiers or {})
        self.tier_reasons = dict(tier_reasons or {})
        # Advertised speculative tier cascades (canonical schedule string
        # -> CascadeSchedule) and refusal reasons, the cascade twin of
        # the tier tables above (eval/certify.resolve_cascades;
        # docs/serving.md "Tier cascade").
        self.cascades = dict(cascades or {})
        self.cascade_reasons = dict(cascade_reasons or {})
        self._engine = engine
        self.batcher = batcher
        self.scheduler = scheduler
        self.metrics = metrics
        self.stream = stream  # stream.runner.StreamRunner or None
        # serve/cluster/.ClusterDispatcher or None.  In cluster mode the
        # dispatcher ALSO fills the batcher/scheduler slot above (it
        # implements their submit contracts), so the request paths are
        # identical; this reference is for cluster-specific surfaces
        # (healthz block, drain fan-out).
        self.cluster = cluster
        self.tracer = tracer or Tracer(capacity=config.trace_buffer)
        # Serving-plane fault plan (utils/faults.py): armed from
        # RAFTSTEREO_FAULTS at construction, extended at runtime over
        # POST /debug/faults — always a plan (usually empty), so the
        # handler hooks never branch on None.  build_server shares ONE
        # plan between the server and its engine(s) so one /debug/faults
        # POST arms every hook in the process.
        self.fault_plan = (fault_plan if fault_plan is not None
                           else FaultPlan.from_env()).arm()
        # Write-behind publisher to the durable session tier
        # (stream/tier.TierPublisher); build_server wires it when
        # ``config.stream.tier`` is set.  None = local-pin-only.
        self.tier_publisher = None
        self.profiler = OnDemandProfiler(log_dir="runs/serve/profile")
        # Ends build_server's subscription to the process's compile
        # events (``watch_xla_compiles``); None when built by hand.
        self.unwatch_compiles = None
        # Readiness (live vs ready on /healthz): set once warmup
        # finishes.  build_server passes start_ready=False and owns the
        # gate — it warms either before returning (blocking) or in a
        # background thread (warmup_async), during which the server is
        # live but refuses /predict with 503.  Direct construction
        # defaults to ready: whoever assembles the stack by hand has
        # already warmed (or chosen not to warm) the engine.
        self._ready = threading.Event()
        if start_ready:
            self._ready.set()
        self._flags_lock = threading.Lock()
        self._draining = False  # guarded_by: _flags_lock
        # /predict requests admitted and not yet answered (drain wants
        # "everything running finished", which queue depth alone misses).
        self._predict_inflight = 0  # guarded_by: _flags_lock
        # Admission control for the session path (which bypasses the
        # batcher queue): frames concurrently decoded-and-waiting on the
        # session/engine locks, shed with 503 beyond queue_limit.
        self.stream_inflight_lock = threading.Lock()
        self.stream_inflight = 0  # guarded_by: stream_inflight_lock
        # Same contract for the spatial path (which also bypasses the
        # batcher queue): requests concurrently holding decoded pairs
        # while waiting on the engine lock, shed beyond queue_limit.
        self.spatial_inflight_lock = threading.Lock()
        self.spatial_inflight = 0  # guarded_by: spatial_inflight_lock
        # Caps the number of request bodies being buffered/decoded at
        # once (each transiently costs ~3x its size); excess connections
        # queue on the semaphore instead of multiplying host RSS.
        self.decode_slots = threading.BoundedSemaphore(
            max(4, config.max_batch_size))
        # One binary reply is encoded at a time (the write is outside).
        # A batch's replies all become ready in the same millisecond,
        # while the worker stages the next batch; the encode is
        # memory-bound host work (the median `reply_encode` span on a
        # v5e host: 4.5 ms a 540x960 reply, 18-23 ms at 1080p, 109 ms
        # at 1988x2964; PERF.md §5) that gains nothing from running
        # eight at once, and eight at once cost the staging beside them
        # 8 ms a dispatch (PERF.md §6).  In turn they leave one encode
        # apart, and so do the requests that answer them: at 1080p the
        # median `reply_wait` is 53-73 ms.
        self.reply_encode = threading.Lock()
        super().__init__((config.host, config.port), _Handler)

    @property
    def port(self) -> int:
        return self.server_address[1]

    @property
    def queue_depth(self) -> int:
        """Requests waiting for dispatch, whichever front-end is active."""
        return (self.scheduler.queue_depth if self.scheduler is not None
                else self.batcher.queue_depth)

    @property
    def engine(self) -> BatchEngine:
        """Shape/warmth policy view for admission checks.  In cluster
        mode this resolves through the ReplicaSet ON EVERY ACCESS, not
        at construction: readiness is per-replica state, and replica 0
        may have failed warmup while others warmed — a snapshot taken
        before warmup would pin admission to its cold compile cache."""
        if self.cluster is not None:
            return self.cluster.rset.engine
        return self._engine

    # ------------------------------------------------- readiness + draining

    def mark_ready(self) -> None:
        """Warmup finished: the server may advertise ready and admit
        /predict traffic."""
        self._ready.set()

    @property
    def draining(self) -> bool:
        with self._flags_lock:
            return self._draining

    @property
    def is_ready(self) -> bool:
        """Routable: warmed AND not draining (what /healthz ``ready``
        reports and the cluster router gates on)."""
        return self._ready.is_set() and not self.draining

    def wait_ready(self, timeout: Optional[float] = None) -> bool:
        return self._ready.wait(timeout)

    def try_begin_predict(self) -> bool:
        """Atomic readiness gate + in-flight count: both under one lock
        so ``drained`` can never observe a request that passed the gate
        but is not yet counted (the drain-then-decommission flow polls
        ``drained`` and kills the process on true)."""
        with self._flags_lock:
            if not self._ready.is_set() or self._draining:
                return False
            self._predict_inflight += 1
            return True

    def end_predict(self) -> None:
        with self._flags_lock:
            self._predict_inflight -= 1

    @property
    def inflight(self) -> int:
        """Admitted /predict requests not yet answered.  Session frames
        are included: ``try_begin_predict`` wraps the WHOLE handler
        (cold and stream paths), so adding ``stream_inflight`` — the
        session path's separate admission-control counter — would
        double-count them."""
        with self._flags_lock:
            return self._predict_inflight

    def start_drain(self) -> None:
        """POST /debug/drain: stop admitting, finish everything already
        admitted (queued requests keep dispatching; running batches
        complete), then report ``drained`` on /healthz."""
        with self._flags_lock:
            self._draining = True
        if self.cluster is not None:
            self.cluster.drain()

    @property
    def drained(self) -> bool:
        """Drain complete: nothing queued, nothing running."""
        if not self.draining:
            return False
        if self.queue_depth or self.inflight:
            return False
        if self.scheduler is not None:
            active = getattr(self.scheduler, "active_slots", None)
            if callable(active) and active():
                return False
        return True

    # -------------------------------------------------- session migration

    def export_session(self, session_id: str) -> Optional[Dict]:
        """Host-side snapshot of one streaming session's warm-start
        state, or None when there is nothing warm to move.  In cluster
        mode ``self.stream`` IS the dispatcher, which resolves the
        owning replica; single-engine mode asks the StreamRunner
        directly.  Pure host numpy either way — zero device work, zero
        compiles (the retrace-guard contract for migration)."""
        if self.stream is None:
            return None
        return self.stream.export_session(session_id)

    def import_session(self, snapshot: Dict) -> str:
        """Install an exported snapshot; returns the handoff outcome
        (``warm`` / ``cold_schema`` / ``cold_lost`` — cold is a
        documented fallback, never an error)."""
        if self.stream is None:
            return "cold_lost"
        return self.stream.import_session(snapshot)

    def evict_sessions(self) -> int:
        """Drop every live streaming session (the ``evict_sessions``
        chaos hook, fired from /healthz so it lands within one probe
        interval of its armed offset).  ``self.stream`` is the
        StreamRunner or the cluster dispatcher — both implement
        ``evict_all``.  Returns sessions dropped; losing state is the
        documented cold fallback, never an error."""
        evictor = (getattr(self.stream, "evict_all", None)
                   if self.stream is not None else None)
        if evictor is None:
            return 0
        n = evictor()
        if n:
            logger.warning("fault injection: evicted %d live sessions", n)
        return n

    def close(self) -> None:
        """Stop accepting, drain the queue, release the socket."""
        self.shutdown()
        self.server_close()
        if self.tier_publisher is not None:
            self.tier_publisher.close()
        if self.batcher is not None:
            self.batcher.stop(drain=True)
        if self.scheduler is not None:
            self.scheduler.stop(drain=True)
        if self.unwatch_compiles is not None:
            self.unwatch_compiles()


def watch_xla_compiles(metrics: ServeMetrics, tracer: Tracer):
    """Count every program this process builds or loads from the
    persistent cache from now on (``serve_xla_compiles_total{kind=}``) and
    record each as a ``compile`` span under the trace ``xla`` — through
    the one ``jax.monitoring`` listener the retrace guard owns.  Returns
    the function that stops it."""
    from ..analysis.retrace_guard import subscribe

    def on_compile(kind: str, duration_s: float) -> None:
        metrics.xla_compiles.labels(kind=kind).inc()
        t1 = time.perf_counter()
        tracer.record("compile", t1 - duration_s, t1, "xla",
                      attrs={"kind": kind})

    return subscribe(on_compile)


def build_server(model, variables, config: ServeConfig,
                 metrics: Optional[ServeMetrics] = None,
                 tracer: Optional[Tracer] = None,
                 warmup_async: bool = False) -> StereoServer:
    """Wire engine(s) + dispatch + tracer + HTTP server; warm configured
    buckets.

    With ``config.cluster`` set, N engine replicas (one per device) are
    built behind a ClusterDispatcher instead of a single engine.

    ``warmup_async=False`` (default) warms before returning — the
    historical blocking behaviour, ready on return.  ``warmup_async=True``
    returns immediately with the server LIVE but NOT READY (/healthz
    ``ready: false``, /predict 503) and warms in a background thread —
    what a restarting production server wants: health-checkable at once,
    routable only when traffic will not pay a cold compile.

    The caller drives ``server.serve_forever()`` (blocking) or a thread,
    and ``server.close()`` on the way out.
    """
    metrics = metrics or ServeMetrics()
    if model is not None:
        from ..utils.platform import describe_network

        metrics.model_info.labels(
            **{k: str(v) for k, v in describe_network(model.config).items()},
            iters=str(config.iters)).set(1)
    tracer = tracer or Tracer(capacity=config.trace_buffer)
    # Before anything compiles: warm-up programs count too.
    unwatch_compiles = watch_xla_compiles(metrics, tracer)
    # ONE fault plan for the whole process (server + every engine): a
    # single POST /debug/faults arms every hook, and a count budget is
    # consumed once process-wide (utils/faults.py).
    fault_plan = FaultPlan.from_env().arm()
    if config.spatial_shards > 1 and config.cluster is not None:
        raise ValueError(
            "spatial sharding and cluster replicas are mutually exclusive "
            "(v1): both partition the device set — run the spatial server "
            "as its own process behind the router instead")
    # Accuracy tiers: validated against the certification manifest BEFORE
    # anything is advertised or warmed (eval/certify.py) — an uncertified
    # tier is refused with a recorded reason, and its executables are
    # never compiled.
    tiers: Dict[str, str] = {}
    tier_reasons: Dict[str, str] = {}
    warm_modes = None
    if config.tiers:
        from ..eval.certify import resolve_tiers

        tiers, tier_reasons = resolve_tiers(
            config, model.config if model is not None else None)
        if tiers:
            from ..ops.quant import default_mode

            # model=None mirrors BatchEngine's own fallback (engine
            # stubs never dispatch; their keys just stay well-formed).
            base = ("fp32" if model is None
                    else default_mode(model.config))
            warm_modes = [base] + sorted(set(tiers.values()) - {base})
    # Speculative tier cascades: every schedule must certify — resolved
    # against the same manifest, refused with a recorded reason
    # (eval/certify.resolve_cascades, docs/serving.md "Tier cascade").
    cascades: Dict[str, object] = {}
    cascade_reasons: Dict[str, str] = {}
    if config.cascades:
        if config.cluster is not None:
            # v1 limitation: the cluster dispatcher's submit contract
            # predates cascades; a cascade request in cluster mode is a
            # clean 400 with this reason, never a crash mid-dispatch.
            cascade_reasons = {s: "cascades are single-engine in v1 "
                                  "(not offered in cluster mode)"
                               for s in config.cascades}
        else:
            from ..eval.certify import resolve_cascades

            cascades, cascade_reasons = resolve_cascades(
                config, model.config if model is not None else None)
    cluster = None
    stream = None
    if config.cluster is not None:
        from .cluster import ClusterDispatcher, ReplicaSet

        rset = ReplicaSet(model, variables, config, metrics, tracer=tracer,
                          fault_plan=fault_plan)
        cluster = ClusterDispatcher(rset, config, metrics, tracer=tracer)
        engine = rset.engine
        # The dispatcher fills whichever dispatch slot the mode uses —
        # the HTTP layer's request paths are unchanged; per-replica
        # batchers/schedulers live inside the replicas.
        scheduler = cluster if config.sched is not None else None
        batcher = cluster if config.sched is None else None
        if config.stream is not None:
            stream = cluster  # sticky session routing via the dispatcher

        def warm():
            rset.warmup(modes=warm_modes)
    else:
        engine = BatchEngine(model, variables, config, metrics,
                             fault_plan=fault_plan, tracer=tracer)
        scheduler = None
        if config.sched is not None:
            # Iteration-level continuous batching: the scheduler IS the
            # dispatch path — the micro-batcher is not started, admission
            # control lives in scheduler.submit, and session frames ride
            # the same scheduler as high-priority short jobs.  Warmth is
            # the four phase executables per bucket, not per iteration
            # level.
            scheduler = IterationScheduler(engine, config, metrics,
                                           tracer=tracer).start()
        if config.stream is not None:
            from ..stream.runner import StreamRunner  # local: avoids an
            # import cycle (stream.runner's engine builder imports this
            # pkg)
            stream = StreamRunner(engine, config.stream, metrics,
                                  tracer=tracer, scheduler=scheduler)
        batcher = None
        if scheduler is None:
            batcher = DynamicBatcher(engine, config, metrics,
                                     tracer=tracer).start()

        def warm():
            if config.sched is not None:
                if config.warmup:
                    engine.warmup_sched(
                        iters_per_step=config.sched.iters_per_step,
                        modes=warm_modes)
                    if cascades:
                        # Both legs' sched phases, the four cascade
                        # executables AND the handoff transition pair —
                        # a cascade request never compiles under traffic
                        # (the retrace-budget-0 e2e holds this).
                        engine.warmup_cascade(
                            iters_per_step=config.sched.iters_per_step,
                            schedules=list(cascades.values()))
            else:
                if config.warmup:
                    engine.warmup(modes=warm_modes)
                if config.stream is not None and config.stream_warmup:
                    engine.warmup_stream(ladder=config.stream.ladder,
                                         modes=warm_modes)
            if engine.spatial_shards > 1 and config.warmup:
                # Base precision only — admission refuses tiers on the
                # spatial path, so tier executables would be dead weight
                # (and the sharded compile is the longest in the system).
                engine.warmup_spatial()

    metrics.spatial_shards.set(
        engine.spatial_shards
        if getattr(engine, "spatial_shards", 1) > 1 else 0)
    if model is not None:
        from ..utils.platform import describe_runtime

        # Which device, and what every backend-keyed kernel gate resolved
        # to in THIS process (chip_smoke.py holds a chip run to this line).
        logger.info("runtime: %s", json.dumps(describe_runtime(
            model.config, config.max_batch_size,
            engine.bucket_of((*config.buckets[0], engine.input_channels)))))
    server = StereoServer(config, engine, batcher, metrics, stream=stream,
                          tracer=tracer, scheduler=scheduler,
                          cluster=cluster, start_ready=False,
                          tiers=tiers, tier_reasons=tier_reasons,
                          cascades=cascades,
                          cascade_reasons=cascade_reasons,
                          fault_plan=fault_plan)
    server.unwatch_compiles = unwatch_compiles
    if config.stream is not None and config.stream.tier is not None:
        from ..stream.tier import TierClient, TierPublisher

        scfg = config.stream
        runners = ([r.stream for r in cluster.rset.replicas
                    if r.stream is not None]
                   if cluster is not None else [stream])

        def _live_sids() -> List[str]:
            sids: List[str] = []
            for rnr in runners:
                sids.extend(rnr.store.session_ids())
            return sids

        publisher = TierPublisher(
            TierClient(scfg.tier[0], scfg.tier[1],
                       timeout_s=scfg.tier_timeout_s),
            export_fn=server.export_session,
            to_wire=lambda snap: snapshot_to_wire(
                snap, compress=scfg.snapshot_compress,
                compress_bound=scfg.snapshot_compress_bound),
            metrics=metrics,
            queue_limit=scfg.tier_queue_limit,
            retries=scfg.tier_retries,
            backoff_ms=scfg.tier_backoff_ms,
            reprobe_s=scfg.tier_reprobe_s,
            resync_fn=_live_sids,
        ).start()
        server.tier_publisher = publisher
        # Hand the publisher to every runner: StreamRunner.step enqueues
        # the SID after each completed frame (write-behind — the frame's
        # request path never touches the tier).
        for rnr in runners:
            rnr.publisher = publisher

    def warm_then_ready():
        try:
            warm()
        except Exception:
            # Live but never ready: probes keep failing readiness, the
            # router keeps traffic away, and the operator sees why here.
            logger.exception("warmup failed; server stays NOT READY")
            return
        server.mark_ready()

    if warmup_async:
        threading.Thread(target=warm_then_ready, daemon=True,
                         name="serve-warmup").start()
    else:
        # Blocking path: a warmup failure must raise (a silent
        # never-ready server would hang the caller's first request).
        warm()
        server.mark_ready()
    logger.info("serving on %s:%d (buckets=%s, max_batch=%d, iters=%d/%d, "
                "stream=%s, sched=%s, replicas=%s, ready=%s)",
                config.host, server.port,
                sorted(engine.compiled_keys) or "lazy",
                config.max_batch_size, config.iters, config.degraded_iters,
                list(config.stream.ladder) if config.stream else "off",
                "on" if scheduler is not None else "off",
                len(cluster.rset) if cluster is not None else "1 (single)",
                server.is_ready)
    return server
