"""Client + load generator for the serving endpoint (stdlib http.client).

``ServeClient`` is a thin blocking client for one connection (keep-alive);
``run_load`` drives closed- or open-loop traffic against a server and
reports achieved throughput and latency percentiles:

* closed loop — ``concurrency`` workers each keep exactly one request in
  flight (classic saturation measurement: throughput at offered
  concurrency);
* open loop — requests fire on a fixed ``rate`` schedule regardless of
  completions (arrival-process realism: queueing delay and shedding show up
  instead of being absorbed by client backpressure).  The schedule is only
  honored while a worker is free: size ``concurrency`` >= rate x expected
  p99 latency, and check ``send_lag_p99_ms`` in the stats — when it grows,
  the workers fell behind and the run degraded toward closed loop.
"""

from __future__ import annotations

import http.client
import json
import math
import socket
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from .. import wire
from ..loadgen.records import Recorder, RequestRow, summarize
from ..utils.backoff import backoff_delay
from .server import decode_array, encode_array

__all__ = ["ServeClient", "ServeError", "run_load", "synthetic_pair_pool"]


def synthetic_pair_pool(height: int, width: int, n: int = 4, seed: int = 0):
    """``make_pair`` callable over a pool of ``n`` pre-generated random
    pairs — request cost stays in the server, not in host-side RNG
    (``cli.serve --loadgen``'s traffic)."""
    rng = np.random.default_rng(seed)
    pool = [(rng.integers(0, 255, (height, width, 3)).astype(np.float32),
             rng.integers(0, 255, (height, width, 3)).astype(np.float32))
            for _ in range(max(n, 1))]
    return lambda i: pool[i % len(pool)]


class ServeError(RuntimeError):
    """Non-200 reply; ``status``, the decoded error payload and (when the
    server sent one) the ``X-Request-Id`` attached — the id keys the failed
    request's spans in ``/debug/trace``."""

    def __init__(self, status: int, payload: Dict,
                 request_id: Optional[str] = None):
        msg = f"HTTP {status}: {payload.get('error', payload)}"
        if status == 413 and "limit_mb" in payload:
            # Actionable refusal, not a mystery drop: the cap auto-sizes
            # to --spatial_buckets (config.bucket_body_mb), so the fix
            # is a server configured for the resolution, not a retry.
            msg += (f" (server body cap {payload['limit_mb']} MB; an "
                    f"oversized pair needs --spatial_buckets covering it)")
        super().__init__(msg)
        self.status = status
        self.payload = payload
        self.request_id = request_id


class _RetrySafe(Exception):
    """Marks a connection failure that is provably safe to resend: the
    request never reached the server (send phase) or is idempotent
    (GET).  ``__cause__`` carries the underlying error.  The retry loop
    in ``ServeClient._request`` resends ONLY these — a response-phase
    POST failure may have executed server-side and propagates raw."""


class ServeClient:
    """Blocking client over one keep-alive connection (not thread-safe —
    load-gen workers each own one).

    ``retries`` adds bounded retry-with-backoff (exponential from
    ``retry_backoff_ms``, +-50% jitter to decorrelate client storms) on
    (a) send-side connection failures — a refused/reset connect never
    reached the server, so resending is always safe (a restarting or
    failing-over backend answers on a later attempt instead of the old
    immediate hard failure) — and (b) 5xx statuses listed in
    ``retry_statuses`` (default 502/503: shed and router-unavailable are
    transient by contract — both come with Retry-After).  Response
    timeouts are NEVER retried: the server may still be computing and a
    resend would double the work and the wait.  Default ``retries=0``
    preserves the historical fail-fast behaviour.

    ``wire_format`` picks the /predict dialect: ``"binary"`` (default —
    wire frames both ways, docs/wire_format.md) or ``"json"`` (the
    base64 dialect; the ``--json`` opt-out in cli.serve / cli.loadgen).
    ``response_encoding="int16"`` asks a binary server for the
    fixed-point disparity encoding; the exactness manifest arrives as
    ``meta["wire_manifest"]``.  ``bytes_sent``/``bytes_received`` count
    /predict body bytes both ways (the wire-bytes/pair signal the SLO
    harness reports).
    """

    def __init__(self, host: str, port: int, timeout: float = 120.0,
                 retries: int = 0, retry_backoff_ms: float = 100.0,
                 retry_statuses: Tuple[int, ...] = (502, 503),
                 wire_format: str = "binary",
                 response_encoding: str = "f32",
                 compress: bool = True,
                 compress_level: int = wire.LEVEL):
        self._conn = http.client.HTTPConnection(host, port, timeout=timeout)
        assert retries >= 0, retries
        assert wire_format in ("binary", "json"), wire_format
        assert response_encoding in ("f32", "int16"), response_encoding
        self.retries = retries
        self.retry_backoff_ms = retry_backoff_ms
        self.retry_statuses = tuple(retry_statuses)
        self.wire_format = wire_format
        self.response_encoding = response_encoding
        self.compress = compress
        # ``compress`` means "where it pays": the codec stores the tiles
        # its sample says will not shrink and deflates the rest at
        # ``wire.LEVEL``, the level the server's replies use too
        # (docs/wire_format.md "Compression").
        self.compress_level = compress_level
        self.bytes_sent = 0
        self.bytes_received = 0

    def close(self) -> None:
        self._conn.close()

    def _backoff(self, attempt: int) -> None:
        time.sleep(backoff_delay(self.retry_backoff_ms, attempt))

    def _request(self, method: str, path: str,
                 body: Optional[bytes] = None,
                 headers: Optional[Dict[str, str]] = None
                 ) -> Tuple[int, bytes, Dict[str, str]]:
        last_exc: Optional[Exception] = None
        for attempt in range(self.retries + 1):
            if attempt:
                self._backoff(attempt - 1)
            try:
                status, raw, headers_out = self._request_once(
                    method, path, body, headers)
            except socket.timeout:
                raise  # never resend: the server may still be computing
            except _RetrySafe as e:
                # Send-phase failure (or idempotent GET): provably safe
                # to resend — the only exceptions this loop may eat.  A
                # response-phase POST failure propagates raw below: the
                # server may have processed it, so resending would run
                # inference twice (and for a session frame, advance the
                # warm-start state — see serve/cluster/router.py, which
                # makes the same send/response distinction).
                last_exc = e.__cause__
                continue
            if status in self.retry_statuses and attempt < self.retries:
                continue
            return status, raw, headers_out
        raise last_exc

    def _request_once(self, method: str, path: str,
                      body: Optional[bytes] = None,
                      headers: Optional[Dict[str, str]] = None
                      ) -> Tuple[int, bytes, Dict[str, str]]:
        if headers is None:
            headers = ({"Content-Type": "application/json"} if body
                       else {})
        try:
            self._conn.request(method, path, body=body, headers=headers)
        except (http.client.HTTPException, ConnectionError, OSError):
            # Send-side failure (typically a stale keep-alive the server
            # closed while idle): the request never reached the server, so
            # one reconnect + resend is safe even for POST.
            self._conn.close()
            try:
                self._conn.request(method, path, body=body,
                                   headers=headers)
            except socket.timeout:
                self._conn.close()
                raise  # timeouts are never resent, even send-phase
            except (http.client.HTTPException, ConnectionError,
                    OSError) as e:
                # Still send-phase (typically connection refused): the
                # request never left, _request may back off and resend.
                self._conn.close()
                raise _RetrySafe() from e
        try:
            resp = self._conn.getresponse()
            return resp.status, resp.read(), dict(resp.headers)
        except socket.timeout:
            # Never resend on a response timeout — for /predict the server
            # may still be computing; a retry would run inference twice
            # and silently double the effective client timeout.
            self._conn.close()
            raise
        except (http.client.HTTPException, ConnectionError, OSError):
            self._conn.close()
            if method != "GET":
                raise  # non-idempotent: the server may have processed it
            # GET is idempotent: one inline resend regardless of the
            # retry budget (the historical stale-keep-alive recovery).
            try:
                self._conn.request(method, path, body=body,
                                   headers=headers)
                resp = self._conn.getresponse()
                return resp.status, resp.read(), dict(resp.headers)
            except socket.timeout:
                self._conn.close()
                raise  # timeouts are never resent (contract above)
            except (http.client.HTTPException, ConnectionError,
                    OSError) as e:
                self._conn.close()
                raise _RetrySafe() from e

    def predict(self, left: np.ndarray, right: np.ndarray,
                iters: Optional[int] = None,
                session_id: Optional[str] = None,
                seq_no: Optional[int] = None,
                deadline_ms: Optional[float] = None,
                priority: Optional[str] = None,
                accuracy: Optional[str] = None,
                spatial: Optional[bool] = None
                ) -> Tuple[np.ndarray, Dict]:
        """One stereo pair -> ((H, W) disparity, meta dict).

        ``session_id`` marks the pair as a frame of a video stream: the
        server warm-starts it from the session's previous frame
        (docs/streaming.md).  ``seq_no`` is the frame's position in the
        stream; omit it for an in-order client.  ``deadline_ms`` /
        ``priority`` (high/normal/low) are honored by servers running the
        iteration-level scheduler (``--sched``, docs/serving.md).
        ``accuracy`` picks an advertised accuracy tier
        (certified/fast/turbo, docs/serving.md "Accuracy tiers"); an
        unadvertised tier is a 400.  ``spatial=True`` demands the
        multi-chip spatially-sharded path (docs/serving.md "Spatial
        sharding"; the server advertises it under ``/healthz``
        ``spatial``), ``False`` forbids it, ``None`` lets the server
        auto-route pairs above its single-chip ceiling.  Raises
        ``ServeError`` on any non-200 status (503 = shed / 504 =
        timeout are expected under overload; callers count them).  A
        413 carries the server's body cap as ``limit_mb`` in the error
        payload — an oversized pair needs a server whose
        ``--spatial_buckets`` cover it (the cap auto-sizes to those
        buckets), not a retry.
        """
        fields: Dict = {}
        if iters is not None:
            fields["iters"] = int(iters)
        if accuracy is not None:
            fields["accuracy"] = str(accuracy)
        if spatial is not None:
            fields["spatial"] = bool(spatial)
        if deadline_ms is not None:
            fields["deadline_ms"] = float(deadline_ms)
        if priority is not None:
            fields["priority"] = str(priority)
        if session_id is not None:
            fields["session_id"] = str(session_id)
            if seq_no is not None:
                fields["seq_no"] = int(seq_no)
        use_binary = self.wire_format == "binary"
        if use_binary:
            if self.response_encoding != "f32" or not self.compress:
                fields["response"] = {"encoding": self.response_encoding,
                                      "compress": self.compress}
            try:
                body = wire.encode_request(
                    np.asarray(left, np.float32),
                    np.asarray(right, np.float32), fields,
                    compress=self.compress, level=self.compress_level)
            except wire.WireError:
                # A pair the frame format cannot carry (e.g. mismatched
                # shapes) must still reach the server so its validation
                # answers — the dialect choice must not change error
                # semantics. Fall back to JSON for this request.
                use_binary = False
                fields.pop("response", None)
            else:
                req_headers = {
                    "Content-Type": wire.WIRE_CONTENT_TYPE,
                    # Errors are always JSON (wire/negotiate.py):
                    # accept both.
                    "Accept": f"{wire.WIRE_CONTENT_TYPE}, "
                              "application/json",
                }
        if not use_binary:
            payload = dict(fields)
            payload["left"] = encode_array(np.asarray(left, np.float32))
            payload["right"] = encode_array(np.asarray(right, np.float32))
            body = json.dumps(payload).encode()
            req_headers = {"Content-Type": "application/json"}
        if deadline_ms is not None:
            # The body field reaches the backend's scheduler; the header
            # reaches the ROUTER, which decrements it by its own elapsed
            # time at each hop and answers 504 itself once the budget is
            # exhausted (docs/fault_tolerance.md "Deadline propagation").
            req_headers["X-Deadline-Ms"] = f"{max(float(deadline_ms), 0.0):.0f}"
        self.bytes_sent += len(body)
        status, resp, headers = self._request("POST", "/predict", body,
                                              headers=req_headers)
        self.bytes_received += len(resp)
        if status != 200:
            # Error replies are JSON in both dialects.
            data = json.loads(resp)
            raise ServeError(status, data,
                             request_id=headers.get("X-Request-Id"))
        if wire.is_wire_content_type(headers.get("Content-Type")):
            res = wire.decode_response(resp)
            disparity, meta = res.disparity, dict(res.meta)
            if res.manifest is not None:
                # Exactness certificate for the int16 encoding
                # (docs/wire_format.md "int16 manifest").
                meta.setdefault("wire_manifest", res.manifest)
        else:
            data = json.loads(resp)
            disparity, meta = decode_array(data["disparity"]), data["meta"]
        # The server already puts request_id in meta; the header is
        # authoritative (and present on error replies too).
        meta.setdefault("request_id", headers.get("X-Request-Id"))
        if "X-Backend" in headers:
            # Talking through the cluster router: which backend answered
            # (docs/serving.md "Cluster").
            meta.setdefault("backend", headers["X-Backend"])
        return disparity, meta

    def _get_json(self, path: str) -> Dict:
        status, body, _ = self._request("GET", path)
        if status != 200:
            raise ServeError(status, json.loads(body))
        return json.loads(body)

    def healthz(self) -> Dict:
        return self._get_json("/healthz")

    def metrics_text(self) -> str:
        status, body, _ = self._request("GET", "/metrics")
        if status != 200:
            raise ServeError(status, json.loads(body))
        return body.decode()

    # ---------------------------------------------------- debug endpoints

    def debug_trace(self, last: Optional[int] = None,
                    trace_id: Optional[str] = None) -> Dict:
        """Chrome trace-event JSON of the server's recent spans
        (docs/observability.md); save it and open at ui.perfetto.dev."""
        qs = []
        if last is not None:
            qs.append(f"last={int(last)}")
        if trace_id is not None:
            qs.append(f"trace_id={trace_id}")
        path = "/debug/trace" + ("?" + "&".join(qs) if qs else "")
        return self._get_json(path)

    def debug_vars(self) -> Dict:
        return self._get_json("/debug/vars")

    def debug_threads(self) -> str:
        status, body, _ = self._request("GET", "/debug/threads")
        if status != 200:
            raise ServeError(status, json.loads(body))
        return body.decode()

    def debug_profile(self, seconds: float) -> Dict:
        """Start an on-demand jax.profiler window on the server; raises
        ``ServeError`` (409) while a capture is already running."""
        status, body, _ = self._request(
            "POST", "/debug/profile",
            json.dumps({"seconds": seconds}).encode())
        data = json.loads(body)
        if status != 200:
            raise ServeError(status, data)
        return data


def run_load(host: str, port: int,
             make_pair: Callable[[int], Tuple[np.ndarray, np.ndarray]],
             requests: int = 64, concurrency: int = 4,
             mode: str = "closed", rate: Optional[float] = None,
             iters: Optional[int] = None,
             sequence_len: Optional[int] = None,
             timeout: float = 120.0, retries: int = 0,
             accuracy: Optional[str] = None,
             wire_format: str = "binary",
             response_encoding: str = "f32") -> Dict:
    """Drive ``requests`` pairs at the server; returns a stats dict.

    ``make_pair(i)`` supplies the i-th request's images (mix shapes to
    exercise several compile buckets).  ``mode='open'`` requires ``rate``
    (requests/sec): send times are fixed at ``i / rate`` from start,
    regardless of completions.

    ``retries`` enables the client's bounded retry-with-backoff (see
    ``ServeClient``) — load-gen against a router or a restarting server
    rides out refused connections and transient 502/503 instead of
    counting them as hard errors.

    ``sequence_len`` switches to SEQUENCE REPLAY (streaming traffic):
    request ``i`` is frame ``i % sequence_len`` of session
    ``loadgen-{i // sequence_len}``, sent with ``session_id``/``seq_no``
    so the server warm-starts it.  Workers claim whole sequences (a
    session's frames must arrive in order), and the stats grow
    ``warm_frames``/``cold_frames`` from the response meta — a quick check
    that warm starts actually engaged.

    ``wire_format`` selects the /predict dialect per ``ServeClient``
    (binary wire frames by default, ``"json"`` for the base64 dialect);
    the summary then carries ``wire_bytes_per_pair`` —
    request + response body bytes per served pair — so the two formats
    are directly comparable on the same traffic (docs/wire_format.md).

    Implementation rides the SLO harness's recorder
    (raftstereo_tpu/loadgen/records.py): one ``RequestRow`` per request,
    and the summary — including the historical key set — is
    ``records.summarize`` over the rows, so the same per-request data
    that certifies SLOs backs this quick path too.
    """
    assert mode in ("closed", "open"), mode
    if mode == "open" and not rate:
        raise ValueError("open-loop load needs a rate (requests/sec)")
    if sequence_len is not None:
        assert sequence_len >= 1, sequence_len
        if iters is not None:
            raise ValueError("explicit iters cannot drive sequence replay "
                             "(the server's controller owns per-frame "
                             "iterations)")
    recorder = Recorder()
    lock = threading.Lock()
    next_idx = [0]
    t_start = time.perf_counter()

    def claim() -> Optional[int]:
        """Next request index; sequence replay claims a whole sequence so
        one worker owns a session's frames in order."""
        stride = sequence_len or 1
        with lock:
            i = next_idx[0]
            if i >= requests:
                return None
            next_idx[0] += stride
            return i

    def run_one(client: ServeClient, i: int) -> None:
        lag_ms = 0.0
        sched_ms = math.nan
        if mode == "open":
            sched_ms = i / rate * 1e3
            delay = t_start + i / rate - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            else:
                lag_ms = -delay * 1e3
        left, right = make_pair(i)
        session = seq = None
        if sequence_len is not None:
            session = f"loadgen-{i // sequence_len}"
            seq = i % sequence_len
        fields = dict(index=i, t_sched_ms=sched_ms,
                      t_send_ms=(time.perf_counter() - t_start) * 1e3,
                      send_lag_ms=lag_ms, tier=accuracy or "default",
                      iters=iters, height=int(left.shape[0]),
                      width=int(left.shape[1]),
                      session=session or "", seq_no=seq,
                      wire=client.wire_format)
        sent0, recv0 = client.bytes_sent, client.bytes_received

        def used() -> Dict:
            return dict(bytes_sent=client.bytes_sent - sent0,
                        bytes_received=client.bytes_received - recv0)

        t0 = time.perf_counter()
        try:
            _, meta = client.predict(left, right, iters=iters,
                                     session_id=session, seq_no=seq,
                                     accuracy=accuracy)
        except ServeError as e:
            kind = {503: "shed", 504: "timeout"}.get(e.status, "error")
            recorder.add(RequestRow(
                outcome=kind, latency_ms=(time.perf_counter() - t0) * 1e3,
                status=e.status, request_id=e.request_id or "",
                **used(), **fields))
        except Exception:
            recorder.add(RequestRow(outcome="error", latency_ms=math.nan,
                                    **used(), **fields))
        else:
            recorder.add(RequestRow(
                outcome="ok",
                latency_ms=(time.perf_counter() - t0) * 1e3,
                status=200, iters_done=meta.get("iters"),
                warm=meta.get("warm"),
                degraded=bool(meta.get("degraded", False)),
                backend=meta.get("backend", ""),
                request_id=meta.get("request_id") or "",
                **used(), **fields))

    def worker():
        client = ServeClient(host, port, timeout=timeout, retries=retries,
                             wire_format=wire_format,
                             response_encoding=response_encoding)
        try:
            while True:
                start = claim()
                if start is None:
                    return
                stop = min(start + (sequence_len or 1), requests)
                for i in range(start, stop):
                    run_one(client, i)
        finally:
            client.close()

    threads = [threading.Thread(target=worker, daemon=True,
                                name=f"loadgen-{i}")
               for i in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    return summarize(recorder.rows(), mode=mode, requests=requests,
                     concurrency=concurrency, wall_s=wall, rate=rate,
                     sequence_len=sequence_len)
