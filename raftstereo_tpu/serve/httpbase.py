"""Shared stdlib-only HTTP handler plumbing for the serving front-ends.

Both the single-server front-end (``serve/server.py``) and the cluster
router (``serve/cluster/router.py``) speak the same small dialect:
JSON (or binary wire-frame, docs/wire_format.md) replies with explicit
Content-Length (keep-alive), and a bounded Content-Length check before
any body is buffered.  One base class keeps the two handlers
byte-identical on that dialect — a fix to the body-cap or header logic
lands in both.

The body cap is a POLICY ARGUMENT, not a constant: every call takes
``limit_mb`` from the caller's ``ServeConfig.max_body_mb``, which
auto-raises to fit the largest configured spatial bucket and the
largest plain bucket above ``max_image_dim``
(``config.bucket_body_mb`` — a 4K fp32 pair is 253.1 MiB of base64
JSON body, measured as ``len(json.dumps(payload))`` for a
3840x2160x3 pair, so the cap lands at ~316 MiB after the 25% decode
headroom; the binary wire format carries the same pair in under a
fifth of that).  Over-limit requests get an explicit 413 naming the
limit, never a silent drop: a client sending a bucket-scale pair to a
server not configured for it must learn which knob to turn.

Every refusal here carries an ``X-Request-Id`` header: pre-dispatch
errors (413/411/short reads) happen before the serving layer's tracer sees
the request, but the reply must still be joinable to client logs.

This module must stay importable without the engine/model stack: the
router is model-free (see serve/__init__.py's lazy exports).
"""

from __future__ import annotations

import json
import logging
import re
import uuid
from http.server import BaseHTTPRequestHandler
from typing import Callable, Dict, NamedTuple, Optional, Tuple

__all__ = ["JsonRequestHandler", "WIRE_CHUNK", "TRACE_HEADER",
           "TraceContext", "parse_trace_context", "format_trace_context"]

#: chunk size for the streaming body reader — also the upper bound on
#: what a streaming consumer (router forward, frame decoder) ever
#: buffers of the raw body at once.
WIRE_CHUNK = 64 * 1024

#: cross-hop trace context header (docs/observability.md).  Key-value
#: (not positional) because client-chosen ``X-Request-Id`` values — which
#: double as trace ids on the first hop — may themselves contain dashes
#: or dots, so no separator charset is safe for splitting.
TRACE_HEADER = "X-Trace-Context"

# trace id: whatever the X-Request-Id charset allows (it IS the trace id
# on un-headered requests); span id: the tracer's 16-hex form, but accept
# any short token — a foreign parent id is harmless, it just won't join.
_TRACE_TOKEN = re.compile(r"^[A-Za-z0-9._-]{1,64}$")
_SPAN_TOKEN = re.compile(r"^[A-Za-z0-9._-]{1,32}$")


class TraceContext(NamedTuple):
    """Parsed ``X-Trace-Context``: the identity a request carries across
    hops.  ``sampled=False`` means "count me, don't span me" — every hop
    suppresses span recording but still serves the request normally."""

    trace_id: str
    parent_id: Optional[str]
    sampled: bool


def parse_trace_context(value: Optional[str]) -> Optional[TraceContext]:
    """Parse a ``trace=<id>;parent=<spanid>;sampled=<0|1>`` header.

    Returns None for absent, malformed, or foreign-format values — the
    receiving hop then mints a fresh trace.  NEVER raises: a bad trace
    header must not be able to 500 a request (tests/test_obs.py)."""
    if not value or len(value) > 256:
        return None
    fields: Dict[str, str] = {}
    for part in value.split(";"):
        part = part.strip()
        if not part:
            continue
        key, sep, val = part.partition("=")
        if not sep:
            return None
        fields[key.strip().lower()] = val.strip()
    trace_id = fields.get("trace", "")
    if not _TRACE_TOKEN.match(trace_id):
        return None
    parent = fields.get("parent") or None
    if parent is not None and not _SPAN_TOKEN.match(parent):
        return None
    sampled = fields.get("sampled", "1")
    if sampled not in ("0", "1"):
        return None
    return TraceContext(trace_id, parent, sampled == "1")


def format_trace_context(trace_id: str, parent_id: Optional[str] = None,
                         sampled: bool = True) -> str:
    """Render the ``X-Trace-Context`` value for an outbound hop."""
    out = f"trace={trace_id}"
    if parent_id:
        out += f";parent={parent_id}"
    return out + f";sampled={'1' if sampled else '0'}"


class JsonRequestHandler(BaseHTTPRequestHandler):
    """JSON-over-HTTP/1.1 handler base: reply helpers + body cap.

    Subclasses set ``_log`` to their module logger (request chatter goes
    to ``logging``, never stderr) and their own ``server_version``."""

    protocol_version = "HTTP/1.1"  # keep-alive: load-gen reuses connections
    _log = logging.getLogger(__name__)

    WIRE_CHUNK = WIRE_CHUNK  # class alias for subclass convenience

    def log_message(self, fmt, *args):
        self._log.debug("%s %s", self.address_string(), fmt % args)

    def request_id(self) -> str:
        """Propagated or fresh request id for THIS request.

        Computed per call, never cached on ``self``: handler instances
        are REUSED across keep-alive requests, so cached per-request
        state would leak one request's id into the next."""
        return (self.headers.get("X-Request-Id") or "")[:64] \
            or uuid.uuid4().hex

    def trace_context(self) -> Optional[TraceContext]:
        """Parsed inbound ``X-Trace-Context``, or None (fresh trace).

        Computed per call, never cached on ``self`` — same keep-alive
        reuse hazard as ``request_id``."""
        return parse_trace_context(self.headers.get(TRACE_HEADER))

    def trace_of(self, rid: str) -> Tuple[Optional[str], Optional[str]]:
        """(trace_id, parent_span_id) this request's spans should carry.

        A valid inbound context is CONTINUED (its trace id + parent span
        id); ``sampled=0`` yields trace_id None, which ``Tracer.record``
        treats as "don't record" — the one central guard that makes the
        sampled flag hold end-to-end without per-callsite plumbing.  No
        (or malformed) context: the request id doubles as the trace id,
        exactly the pre-stitching behaviour."""
        ctx = self.trace_context()
        if ctx is None:
            return rid, None
        if not ctx.sampled:
            return None, None
        return ctx.trace_id, ctx.parent_id

    def _maybe_blackhole(self) -> float:
        """``blackhole_backend@t_ms`` chaos seam (utils/faults.py):
        while the owning server's fault plan has an active blackhole
        window, HOLD this request — the connection was accepted, the
        request is parsed, but nothing is answered until the window
        closes (then the request proceeds normally).  Probes time out
        against their short ``probe_timeout_s`` and the router's
        circuit breaker opens; nothing is lost, only late.  Returns
        the seconds held (0.0 in the common no-fault path — the
        getattr keeps the seam free for servers without a plan)."""
        plan = getattr(self.server, "fault_plan", None)
        if plan is None:
            return 0.0
        return plan.blackhole_hold()

    def _send(self, code: int, body: bytes, ctype: str,
              extra_headers: Optional[Dict[str, str]] = None) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _json(self, code: int, obj,
              extra_headers: Optional[Dict[str, str]] = None) -> None:
        self._send(code, json.dumps(obj).encode(), "application/json",
                   extra_headers)

    def _reject_body(self, limit_mb: float) -> Optional[Tuple[int, Dict]]:
        """Body-policy gate, applied BEFORE reading a single body byte.

        Returns ``(status, error_payload)`` when the request must be
        refused — the connection is then marked for close (an unread or
        unframed body can never be drained, so keep-alive would
        misparse it as the next request line) — or None to proceed, with
        the parsed length stashed in ``self._body_length``.

        Refusals:

        * ``Transfer-Encoding`` present -> 411: a chunked body has no
          Content-Length, would read as length 0 here, and its unread
          frames would desync the connection.
        * missing/unparseable/over-limit Content-Length -> 413 naming
          the limit.
        """
        te = (self.headers.get("Transfer-Encoding") or "").strip()
        if te:
            self.close_connection = True
            return 411, {"error": "Transfer-Encoding not supported; "
                                  "send a Content-Length body",
                         "transfer_encoding": te}
        try:
            length = int(self.headers.get("Content-Length", 0) or 0)
        except ValueError:
            length = -1
        if length < 0 or length > limit_mb * 2 ** 20:
            self.close_connection = True
            return 413, {"error": "body too large or bad Content-Length",
                         "limit_mb": limit_mb}
        self._body_length = length
        return None

    def _content_length(self, limit_mb: float) -> Optional[int]:
        """Parse + bound Content-Length WITHOUT reading the body.

        Returns the length, or None when the body policy refuses it
        (see ``_reject_body``); the caller sends its own error reply.
        ``self.body_reject`` then holds the (status, payload) to send."""
        self.body_reject = self._reject_body(limit_mb)
        if self.body_reject is not None:
            return None
        return self._body_length

    def _read_body_stream(self, length: int,
                          sink: Callable[[bytes], None]) -> bool:
        """Drain exactly ``length`` body bytes in bounded chunks into
        ``sink(chunk)`` — the streaming read path: the full body never
        exists in this layer, only one <= WIRE_CHUNK slice at a time.

        Returns False on a short read (client hung up or lied about
        Content-Length); the connection is marked close — the stream
        position is undefined, nothing further can be parsed."""
        remaining = length
        while remaining:
            chunk = self.rfile.read(min(self.WIRE_CHUNK, remaining))
            if not chunk:
                self.close_connection = True
                return False
            remaining -= len(chunk)
            sink(chunk)
        return True

    def _read_body(self, limit_mb: float) -> Optional[bytes]:
        """Bounded whole-body read; replies itself (with an
        ``X-Request-Id``) and returns None on a policy refusal or a
        short read."""
        reject = self._reject_body(limit_mb)
        if reject is not None:
            code, payload = reject
            self._json(code, payload,
                       {"X-Request-Id": self.request_id()})
            return None
        parts = []
        if not self._read_body_stream(self._body_length, parts.append):
            self._json(400, {"error": "body shorter than Content-Length"},
                       {"X-Request-Id": self.request_id()})
            return None
        return b"".join(parts)
