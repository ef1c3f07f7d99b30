"""raftstereo_tpu.wire — versioned binary frame format for the serving
data plane (docs/wire_format.md).

Dependency-free by design (stdlib ``struct``/``zlib``/``json`` + numpy):
this package is imported by the model-free cluster router and the
client, neither of which may pull in the engine stack.  Encode/decode is
pure host-side byte work — it creates no jax values and compiles no
executables, so adopting the format leaves the retrace budget at 0.

Two frame types over one fixed little-endian header:

* **request** — a stereo pair (two image planes) plus the JSON field
  dict the ``/predict`` dialect already speaks (iters, session_id, ...);
* **response** — one disparity plane, either raw float32 (bitwise equal
  to the JSON dialect's base64 payload) or int16 fixed-point carrying a
  per-response exactness manifest (scale, measured max quantization
  error) modeled on the accuracy-tier certification manifests.

Planes ship raw or lossless-tile-compressed (zlib over a byte-shuffle
filter); ``FrameDecoder`` decodes chunk-at-a-time into preallocated
plane staging so callers never hold body + decoded copies of a
bucket-scale pair at once.
"""

from .format import (
    FLAG_INT16,
    FLAG_SHUFFLE,
    FLAG_ZLIB,
    FRAME_REQUEST,
    FRAME_RESPONSE,
    HEADER_SIZE,
    LEVEL,
    MAGIC,
    STORE_SHARE,
    VERSION,
    FrameDecoder,
    WireError,
    WireRequest,
    WireResponse,
    WireVersionError,
    decode_request,
    decode_response,
    encode_request,
    encode_response,
    parse_header,
    tile_census,
)
from .negotiate import (
    JSON_CONTENT_TYPE,
    WIRE_CONTENT_TYPE,
    accepts_wire,
    is_wire_content_type,
)

__all__ = [
    "FLAG_INT16", "FLAG_SHUFFLE", "FLAG_ZLIB", "FRAME_REQUEST",
    "FRAME_RESPONSE", "HEADER_SIZE", "JSON_CONTENT_TYPE", "LEVEL", "MAGIC",
    "STORE_SHARE", "VERSION", "WIRE_CONTENT_TYPE", "FrameDecoder",
    "WireError", "WireRequest", "WireResponse", "WireVersionError",
    "accepts_wire", "decode_request", "decode_response", "encode_request",
    "encode_response", "is_wire_content_type", "parse_header",
    "tile_census",
]
