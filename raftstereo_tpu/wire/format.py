"""Binary frame codec: fixed header + JSON meta + image/disparity planes.

Byte layout (all integers little-endian; full table in
docs/wire_format.md):

    offset  size  field
    0       4     magic       b"RSWF"
    4       2     version     u16, this module speaks exactly 1
    6       1     frame_type  u8: 1 = request, 2 = response
    7       1     flags       u8 bitfield: 1 ZLIB, 2 SHUFFLE, 4 INT16
    8       1     dtype       u8 payload dtype code (see _DTYPES)
    9       1     channels    u8 channels per plane (disparity: 1)
    10      2     plane_count u16 (request: 2 — left, right; response: 1)
    12      4     height      u32
    16      4     width       u32
    20      4     meta_len    u32 bytes of UTF-8 JSON following the header
    24      8     payload_len u64 bytes of plane data following the meta
    32            meta, then planes

Plane payload, per plane in order:

* flags & ZLIB: ``u32 tile_count``, then per tile ``u32 raw_len``,
  ``u32 comp_len``, ``comp_len`` bytes of a complete zlib stream.
  Tiles partition the (possibly shuffled) plane bytes in order, at most
  ``TILE_BYTES`` raw bytes each — so a streaming decoder never stages
  more than one compressed tile.  (This encoder also ends a tile where
  a byte plane of a shuffled plane ends; a decoder need not know.)
* otherwise: the raw (possibly shuffled) plane bytes.

ZLIB means "deflated where it pays", decided tile by tile from the
bytes: the encoder deflates a ``PROBE_BYTES`` sample of the tile and,
unless the sample shrinks below ``STORE_SHARE`` of its size, writes the
tile as a *stored* zlib stream (``zlib.compress(tile, 0)``: a copy and
a checksum, ``comp_len`` = ``raw_len`` + ~100 B).  Sensor grain and
float mantissas do not deflate, and deflating them anyway was most of
a request's host time (docs/wire_format.md "Compression").  A stored
tile is still a complete zlib stream, so every version-1 decoder reads
it; a wrong guess costs bytes or milliseconds, never correctness.
Tiles that do deflate use ``LEVEL`` in both directions.

The SHUFFLE flag applies an HDF5-style byte-shuffle filter before
compression: plane bytes are regrouped so all 0th bytes of each element
come first, then all 1st bytes, etc.  Same-magnitude floats share
their exponent byte, so the filter turns a plane of which nothing
deflates into low-mantissa byte planes that are stored and an exponent
byte plane that deflates 30x and more.  Lossless: decode is a transpose.

Float32 images whose values are exactly uint8-representable (the
overwhelmingly common case — stereo cameras produce 8-bit intensities
later promoted to float) are demoted to uint8 planes on encode and
re-promoted on decode; ``astype`` in both directions is exact, so the
round-trip stays bitwise and the wire carries 4x fewer bytes before
compression even starts.
"""

from __future__ import annotations

import json
import math
import struct
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = [
    "FLAG_INT16", "FLAG_SHUFFLE", "FLAG_ZLIB", "FRAME_REQUEST",
    "FRAME_RESPONSE", "HEADER_SIZE", "MAGIC", "TILE_BYTES", "VERSION",
    "LEVEL", "STORE_SHARE", "FrameDecoder", "WireError", "WireRequest",
    "WireResponse", "WireVersionError", "decode_request",
    "decode_response", "encode_request", "encode_response",
    "parse_header", "tile_census",
]

MAGIC = b"RSWF"
VERSION = 1
# Versions this codec can decode (inclusive range, named in the 400 the
# server returns for anything outside it).
SUPPORTED_VERSIONS = (1, 1)

_HEADER = struct.Struct("<4sHBBBBHIIIQ")
HEADER_SIZE = _HEADER.size  # 32

FRAME_REQUEST = 1
FRAME_RESPONSE = 2

FLAG_ZLIB = 1     # planes are tile-compressed
FLAG_SHUFFLE = 2  # byte-shuffle filter applied before compression
FLAG_INT16 = 4    # response payload is int16 fixed-point (meta manifest)

TILE_BYTES = 1 << 20  # raw bytes per compression tile
# A tile is deflated only when a sample of it deflates below this share
# of the sample's size; otherwise it is stored (docs/wire_format.md
# "Compression" has the measurements the share was picked from).
STORE_SHARE = 0.75
# The sample: this many bytes in all, in _PROBE_SLICES evenly spaced
# slices, so a tile that changes character half-way is seen both sides.
PROBE_BYTES = 16 << 10
_PROBE_SLICES = 4
# Deflate level of every tile that is deflated, requests and replies
# alike: after the shuffle the ratio is in the bytes, not in the search.
LEVEL = 1

# u8 dtype code -> numpy dtype.  The code describes the PAYLOAD bytes;
# meta may direct a post-decode promotion (uint8 image -> float32).
_DTYPES: Dict[int, np.dtype] = {
    1: np.dtype("<f4"),
    2: np.dtype("<f2"),
    3: np.dtype("u1"),
    4: np.dtype("<i2"),
}
_DTYPE_CODES = {v: k for k, v in _DTYPES.items()}

_META_LIMIT = 16 << 20  # sanity cap on the JSON meta blob


class WireError(ValueError):
    """Malformed or unsupported frame (everything except version skew)."""


class WireVersionError(WireError):
    """Frame version outside SUPPORTED_VERSIONS — the server names the
    range in its 400 so old clients learn what to downgrade to."""


class WireRequest:
    """Decoded request frame: float32 (or as-sent dtype) image pair plus
    the /predict field dict (iters, session_id, seq_no, ...)."""

    def __init__(self, left: np.ndarray, right: np.ndarray,
                 fields: Dict):
        self.left = left
        self.right = right
        self.fields = fields


class WireResponse:
    """Decoded response frame: float32 disparity plus server meta; for
    int16 frames, ``manifest`` carries the exactness certificate."""

    def __init__(self, disparity: np.ndarray, meta: Dict,
                 manifest: Optional[Dict] = None):
        self.disparity = disparity
        self.meta = meta
        self.manifest = manifest


# --------------------------------------------------------------- filters

def _shuffle(a: np.ndarray) -> np.ndarray:
    """Byte planes of ``a``, one row per byte of an element."""
    return np.ascontiguousarray(
        a.reshape(-1).view(np.uint8).reshape(-1, a.dtype.itemsize).T)


def _unshuffle(raw: bytes, itemsize: int) -> bytes:
    if itemsize <= 1 or not raw:
        return raw
    a = np.frombuffer(raw, dtype=np.uint8).reshape(itemsize, -1)
    return np.ascontiguousarray(a.T).tobytes()


# --------------------------------------------------------------- encode

def _tile_stream(tile: np.ndarray, level: int) -> bytes:
    """The tile as a complete zlib stream: deflated when a sample of it
    deflates below ``STORE_SHARE`` of the sample's size, else stored."""
    sample = tile
    if len(tile) > PROBE_BYTES:
        step = len(tile) // _PROBE_SLICES
        each = PROBE_BYTES // _PROBE_SLICES
        sample = b"".join(tile[i * step:i * step + each]
                          for i in range(_PROBE_SLICES))
    comp = zlib.compress(sample, level)
    if len(comp) >= STORE_SHARE * len(sample):
        return zlib.compress(tile, 0)
    return comp if sample is tile else zlib.compress(tile, level)


def _encode_plane(plane: np.ndarray, flags: int, level: int) -> list:
    """The plane's payload as buffers for the frame's one ``join``.
    A shuffled plane's tiles end where a byte plane ends, so no tile
    mixes mantissa noise with exponent bytes."""
    rows = (_shuffle(plane) if flags & FLAG_SHUFFLE
            else plane.reshape(1, -1).view(np.uint8))
    if not flags & FLAG_ZLIB:
        return [rows.data]
    tiles = [row[off:off + TILE_BYTES] for row in rows
             for off in range(0, len(row), TILE_BYTES)]
    parts = [struct.pack("<I", len(tiles))]
    for tile in tiles:
        comp = _tile_stream(tile, level)
        parts.append(struct.pack("<II", len(tile), len(comp)))
        parts.append(comp)
    return parts


def _build_frame(frame_type: int, flags: int, dtype: np.dtype,
                 channels: int, planes: List[np.ndarray], meta: Dict,
                 level: int) -> bytes:
    h, w = planes[0].shape[:2]
    meta_raw = json.dumps(meta, separators=(",", ":")).encode()
    parts = [part for p in planes
             for part in _encode_plane(
                 np.ascontiguousarray(p, dtype=dtype), flags, level)]
    header = _HEADER.pack(MAGIC, VERSION, frame_type, flags,
                          _DTYPE_CODES[dtype], channels, len(planes),
                          h, w, len(meta_raw),
                          sum(memoryview(p).nbytes for p in parts))
    return b"".join([header, meta_raw, *parts])


def _as_uint8(a: np.ndarray) -> Optional[np.ndarray]:
    """The image as uint8 when it is exactly a promoted 8-bit capture
    (float32 holding whole numbers 0..255), else None.  One cast and
    one comparison: whatever the cast makes of a value outside that set
    is a uint8, and so not equal to it."""
    if a.dtype != np.float32 or a.size == 0:
        return None
    with np.errstate(invalid="ignore"):  # NaN, inf: refused below
        u = a.astype(np.uint8)
    return u if np.array_equal(u, a) else None


def encode_request(left: np.ndarray, right: np.ndarray,
                   fields: Optional[Dict] = None, *,
                   compress: bool = True, level: int = LEVEL,
                   shuffle: bool = True,
                   allow_uint8: bool = True) -> bytes:
    """Encode a stereo pair + /predict fields as one request frame.

    ``fields`` is the JSON dialect's top-level dict minus the images
    (iters, session_id, seq_no, deadline_ms, priority, accuracy,
    spatial, and the optional ``response`` preference dict).  Decode
    returns the images bitwise: float32 pairs that are exactly
    uint8-representable travel as uint8 and are re-promoted."""
    left = np.asarray(left)
    right = np.asarray(right)
    if left.ndim != 3 or right.ndim != 3 or left.shape != right.shape:
        raise WireError(f"expected matching (H, W, C) pairs, got "
                        f"{left.shape} / {right.shape}")
    meta: Dict = {"fields": dict(fields or {})}
    dtype = np.dtype(left.dtype)
    if right.dtype != left.dtype:
        raise WireError("left/right dtype mismatch: "
                        f"{left.dtype} / {right.dtype}")
    if allow_uint8:
        u_left = _as_uint8(left)
        u_right = _as_uint8(right) if u_left is not None else None
        if u_right is not None:
            dtype, left, right = np.dtype("u1"), u_left, u_right
            meta["promote"] = "float32"
    if dtype.newbyteorder("<") not in _DTYPE_CODES:
        raise WireError(f"unsupported image dtype {dtype}")
    dtype = dtype.newbyteorder("<")
    flags = 0
    if compress:
        flags |= FLAG_ZLIB
        if shuffle and dtype.itemsize > 1:
            flags |= FLAG_SHUFFLE
    h, w, c = left.shape
    _check_dims(h, w, c, 2)
    return _build_frame(FRAME_REQUEST, flags, dtype, c,
                        [left, right], meta, level)


def _int16_manifest(d: np.ndarray) -> Optional[Tuple[np.ndarray, Dict]]:
    """Power-of-two fixed-point quantization with a measured error cert.

    Returns None when int16 cannot represent the plane (non-finite
    values, or magnitudes that would need a sub-unit scale past the
    exponent clamp) — the caller falls back to bitwise float32."""
    if d.size == 0 or not np.isfinite(d).all():
        return None
    max_abs = float(np.max(np.abs(d)))
    if max_abs == 0.0:
        k = 0
    else:
        # Largest power-of-two gain that keeps max_abs inside int16.
        k = int(math.floor(math.log2(32766.0 / max_abs)))
        if not -120 <= k <= 120:
            return None
    gain = np.float64(2.0) ** k
    q = np.clip(np.rint(d.astype(np.float64) * gain),
                -32767, 32767).astype(np.int16)
    deq = (q.astype(np.float64) / gain).astype(np.float32)
    max_err = float(np.max(np.abs(deq.astype(np.float64)
                                  - d.astype(np.float64))))
    bound = float(2.0 ** -(k + 1))
    manifest = {
        "encoding": "int16_fixed",
        "scale_log2": -k,          # disparity = q * 2**scale_log2
        "scale": float(2.0 ** -k),
        "max_abs_err": max_err,    # measured on THIS response
        "err_bound": bound,        # half-ULP of the fixed-point grid
    }
    return q, manifest


def encode_response(disparity: np.ndarray, meta: Optional[Dict] = None, *,
                    encoding: str = "f32", compress: bool = True,
                    level: int = LEVEL, shuffle: bool = True) -> bytes:
    """Encode one disparity plane as a response frame.

    ``encoding='f32'`` is bitwise; ``encoding='int16'`` quantizes to a
    power-of-two fixed-point grid and attaches the exactness manifest
    (falling back to f32 when int16 cannot represent the plane)."""
    d = np.asarray(disparity)
    if d.ndim != 2:
        raise WireError(f"disparity must be (H, W), got {d.shape}")
    if encoding not in ("f32", "int16"):
        raise WireError(f"unknown response encoding {encoding!r}")
    meta_obj: Dict = {"meta": dict(meta or {})}
    flags = 0
    if d.dtype != np.float32:
        d = d.astype(np.float32)
    dtype = np.dtype("<f4")
    plane = d
    if encoding == "int16":
        packed = _int16_manifest(d)
        if packed is not None:
            plane, manifest = packed
            meta_obj["manifest"] = manifest
            dtype = np.dtype("<i2")
            flags |= FLAG_INT16
    if compress:
        flags |= FLAG_ZLIB
        if shuffle and dtype.itemsize > 1:
            flags |= FLAG_SHUFFLE
    h, w = d.shape
    _check_dims(h, w, 1, 1)
    return _build_frame(FRAME_RESPONSE, flags, dtype, 1, [plane],
                        meta_obj, level)


def _check_dims(h: int, w: int, c: int, planes: int) -> None:
    if not (1 <= h <= 0xFFFFFFFF and 1 <= w <= 0xFFFFFFFF
            and 1 <= c <= 255 and 1 <= planes <= 0xFFFF):
        raise WireError(f"dims out of range: h={h} w={w} c={c} "
                        f"planes={planes}")


# --------------------------------------------------------------- decode

def parse_header(buf: bytes, expect: Optional[int] = None,
                 max_payload_bytes: Optional[int] = None) -> Dict:
    """Parse + validate the fixed 32-byte header (no payload needed).

    Standalone so a proxy can peek a frame's dims/meta length and
    forward the rest chunk-wise without ever constructing a decoder —
    plane staging is never allocated here.  Raises ``WireVersionError``
    for version skew and ``WireError`` for everything else malformed;
    ``max_payload_bytes`` bounds what the header may claim (checked
    against both the on-wire payload and the decoded plane bytes)."""
    if len(buf) != HEADER_SIZE:
        raise WireError(f"header needs {HEADER_SIZE} bytes, got "
                        f"{len(buf)}")
    (magic, version, frame_type, flags, dtype_code, channels,
     plane_count, h, w, meta_len, payload_len) = _HEADER.unpack(buf)
    if magic != MAGIC:
        raise WireError(f"bad magic {magic!r} (not a wire frame)")
    lo, hi = SUPPORTED_VERSIONS
    if not lo <= version <= hi:
        raise WireVersionError(
            f"unsupported wire version {version}; this build speaks "
            f"versions {lo}..{hi}")
    if frame_type not in (FRAME_REQUEST, FRAME_RESPONSE):
        raise WireError(f"unknown frame type {frame_type}")
    if expect is not None and frame_type != expect:
        want = "request" if expect == FRAME_REQUEST else "response"
        raise WireError(f"expected a {want} frame, got type {frame_type}")
    if dtype_code not in _DTYPES:
        raise WireError(f"unknown dtype code {dtype_code}")
    if flags & ~(FLAG_ZLIB | FLAG_SHUFFLE | FLAG_INT16):
        raise WireError(f"unknown flag bits in {flags:#x}")
    if not (h and w and channels and plane_count):
        raise WireError("zero-sized frame dims")
    if meta_len > _META_LIMIT:
        raise WireError(f"meta blob {meta_len} bytes exceeds "
                        f"{_META_LIMIT}")
    dtype = _DTYPES[dtype_code]
    plane_bytes = h * w * channels * dtype.itemsize
    decoded = plane_count * plane_bytes
    if max_payload_bytes is not None and (payload_len > max_payload_bytes
                                          or decoded > max_payload_bytes):
        raise WireError(
            f"frame claims {max(payload_len, decoded)} payload bytes, "
            f"over the {max_payload_bytes}-byte cap")
    return {
        "version": version, "frame_type": frame_type, "flags": flags,
        "dtype": dtype, "channels": channels,
        "plane_count": plane_count, "height": h, "width": w,
        "meta_len": meta_len, "payload_len": payload_len,
        "plane_bytes": plane_bytes,
    }


class FrameDecoder:
    """Streaming frame decoder: ``feed(chunk)`` bytes in any sizes, read
    the result with ``request()`` / ``response()`` once ``done``.

    Decodes straight into preallocated per-plane staging: raw planes are
    copied chunk-by-chunk into their buffer; compressed planes stage at
    most one tile's compressed bytes (~1 MiB) and stream the inflate
    output into place.  Peak transient memory is therefore one decoded
    frame + one chunk, never body + decoded copies — the point of the
    streaming read path (serve/httpbase.py).

    ``max_payload_bytes`` bounds what a header may ask this decoder to
    allocate; a hostile header claiming absurd dims fails before any
    allocation.  All state is touched by exactly one reader thread (the
    HTTP handler feeding its own request); no locking."""

    _S_HEADER = 0
    _S_META = 1
    _S_TILE_COUNT = 2
    _S_TILE_HEADER = 3
    _S_TILE_BODY = 4
    _S_RAW_PLANE = 5
    _S_DONE = 6

    def __init__(self, expect: Optional[int] = None,
                 max_payload_bytes: Optional[int] = None):
        self._expect = expect
        self._max_payload = max_payload_bytes
        self._state = self._S_HEADER
        self._small = bytearray()
        self._need = HEADER_SIZE
        self.header: Optional[Dict] = None
        self.meta: Dict = {}
        self._dtype: Optional[np.dtype] = None
        self._plane_bytes = 0
        self._planes: List[bytearray] = []
        self._plane_idx = -1
        self._plane_view: Optional[memoryview] = None
        self._plane_pos = 0
        self._tiles_left = 0
        self._tile_raw = 0
        self._payload_seen = 0
        self._payload_len = 0
        self._tiles = [0, 0]  # deflated, stored

    # ------------------------------------------------------------- feed
    @property
    def done(self) -> bool:
        return self._state == self._S_DONE

    def feed(self, chunk: bytes) -> None:
        """Consume the next body bytes; raises WireError on malformed
        input (including trailing bytes past payload_len)."""
        mv = memoryview(chunk)
        while mv.nbytes:
            if self._state in (self._S_HEADER, self._S_META,
                               self._S_TILE_COUNT, self._S_TILE_HEADER,
                               self._S_TILE_BODY):
                take = min(mv.nbytes, self._need - len(self._small))
                self._small += mv[:take]
                mv = mv[take:]
                if len(self._small) == self._need:
                    buf = bytes(self._small)
                    self._small = bytearray()
                    self._advance(buf)
            elif self._state == self._S_RAW_PLANE:
                take = min(mv.nbytes, self._plane_bytes - self._plane_pos)
                self._plane_view[self._plane_pos:
                                 self._plane_pos + take] = mv[:take]
                self._plane_pos += take
                self._payload_seen += take
                mv = mv[take:]
                if self._plane_pos == self._plane_bytes:
                    self._finish_plane()
            else:  # _S_DONE
                raise WireError(
                    f"{mv.nbytes} trailing bytes past payload_len")

    # ---------------------------------------------------- state advance
    def _advance(self, buf: bytes) -> None:
        if self._state == self._S_HEADER:
            self._parse_header(buf)
        elif self._state == self._S_META:
            try:
                self.meta = json.loads(buf.decode("utf-8"))
            except Exception as e:
                raise WireError(f"bad frame meta: {e}")
            if not isinstance(self.meta, dict):
                raise WireError("frame meta must be a JSON object")
            self._begin_plane()
        elif self._state == self._S_TILE_COUNT:
            self._tiles_left = struct.unpack("<I", buf)[0]
            self._payload_seen += 4
            self._check_payload_budget()
            if self._tiles_left == 0:
                raise WireError("compressed plane with zero tiles")
            self._state = self._S_TILE_HEADER
            self._need = 8
        elif self._state == self._S_TILE_HEADER:
            self._tile_raw, comp_len = struct.unpack("<II", buf)
            self._payload_seen += 8
            if self._tile_raw > TILE_BYTES or comp_len > 2 * TILE_BYTES \
                    or self._tile_raw == 0 or comp_len == 0:
                raise WireError(
                    f"bad tile lengths raw={self._tile_raw} "
                    f"comp={comp_len}")
            if self._plane_pos + self._tile_raw > self._plane_bytes:
                raise WireError("tile overruns plane")
            self._check_payload_budget(comp_len)
            self._tiles[comp_len >= self._tile_raw] += 1
            self._state = self._S_TILE_BODY
            self._need = comp_len
        elif self._state == self._S_TILE_BODY:
            self._payload_seen += len(buf)
            try:
                raw = zlib.decompress(buf)
            except zlib.error as e:
                raise WireError(f"bad tile: {e}")
            if len(raw) != self._tile_raw:
                raise WireError(
                    f"tile decompressed to {len(raw)} bytes, header "
                    f"said {self._tile_raw}")
            self._plane_view[self._plane_pos:
                             self._plane_pos + len(raw)] = raw
            self._plane_pos += len(raw)
            self._tiles_left -= 1
            if self._tiles_left:
                self._state = self._S_TILE_HEADER
                self._need = 8
            else:
                if self._plane_pos != self._plane_bytes:
                    raise WireError(
                        f"plane {self._plane_idx}: tiles covered "
                        f"{self._plane_pos} of {self._plane_bytes} bytes")
                self._finish_plane()

    def _parse_header(self, buf: bytes) -> None:
        self.header = parse_header(buf, expect=self._expect,
                                   max_payload_bytes=self._max_payload)
        self._dtype = self.header["dtype"]
        self._plane_bytes = self.header["plane_bytes"]
        self._payload_len = self.header["payload_len"]
        meta_len = self.header["meta_len"]
        if meta_len:
            self._state = self._S_META
            self._need = meta_len
        else:
            self.meta = {}
            self._begin_plane()

    def _begin_plane(self) -> None:
        self._plane_idx += 1
        if self._plane_idx >= self.header["plane_count"]:
            if self._payload_seen != self._payload_len:
                raise WireError(
                    f"payload_len {self._payload_len} != "
                    f"{self._payload_seen} bytes consumed")
            self._state = self._S_DONE
            return
        self._planes.append(bytearray(self._plane_bytes))
        self._plane_view = memoryview(self._planes[-1])
        self._plane_pos = 0
        if self.header["flags"] & FLAG_ZLIB:
            self._state = self._S_TILE_COUNT
            self._need = 4
        else:
            self._check_payload_budget(self._plane_bytes)
            self._state = self._S_RAW_PLANE

    def _finish_plane(self) -> None:
        if self.header["flags"] & FLAG_SHUFFLE:
            raw = _unshuffle(bytes(self._planes[self._plane_idx]),
                             self._dtype.itemsize)
            self._planes[self._plane_idx] = bytearray(raw)
        self._plane_view = None
        self._begin_plane()

    def _check_payload_budget(self, upcoming: int = 0) -> None:
        if self._payload_seen + upcoming > self._payload_len:
            raise WireError(
                f"payload overruns declared payload_len "
                f"{self._payload_len}")

    # ----------------------------------------------------------- results
    def census(self) -> Dict[str, int]:
        """What ``tile_census`` says of the frame read so far."""
        return _census(self.header, self._tiles)

    def _array(self, idx: int, shape: Tuple[int, ...]) -> np.ndarray:
        # View over the staging bytearray — no extra copy; promotion /
        # dequantization below copies only where it must.
        return np.frombuffer(self._planes[idx],
                             dtype=self._dtype).reshape(shape)

    def request(self) -> WireRequest:
        if not self.done:
            raise WireError("frame incomplete")
        if self.header["frame_type"] != FRAME_REQUEST:
            raise WireError("not a request frame")
        hd = self.header
        if hd["plane_count"] != 2:
            raise WireError("request frames carry two image planes")
        shape = (hd["height"], hd["width"], hd["channels"])
        left = self._array(0, shape)
        right = self._array(1, shape)
        if self.meta.get("promote") == "float32":
            left = left.astype(np.float32)
            right = right.astype(np.float32)
        fields = self.meta.get("fields") or {}
        if not isinstance(fields, dict):
            raise WireError("meta.fields must be an object")
        return WireRequest(left, right, fields)

    def response(self) -> WireResponse:
        if not self.done:
            raise WireError("frame incomplete")
        if self.header["frame_type"] != FRAME_RESPONSE:
            raise WireError("not a response frame")
        hd = self.header
        shape = (hd["height"], hd["width"])
        if hd["channels"] != 1 or hd["plane_count"] != 1:
            raise WireError("response frames carry one disparity plane")
        plane = self._array(0, shape)
        manifest = None
        if hd["flags"] & FLAG_INT16:
            manifest = self.meta.get("manifest")
            if not isinstance(manifest, dict) \
                    or "scale_log2" not in manifest:
                raise WireError("int16 frame without a manifest")
            scale = np.float64(2.0) ** int(manifest["scale_log2"])
            plane = (plane.astype(np.float64) * scale).astype(np.float32)
        elif plane.dtype != np.float32:
            plane = plane.astype(np.float32)
        meta = self.meta.get("meta") or {}
        return WireResponse(plane, meta, manifest)


def _census(header: Dict, tiles: List[int]) -> Dict[str, int]:
    """``tiles`` counts by ``comp_len >= raw_len``: deflated, stored."""
    return {"tiles_stored": tiles[1], "tiles_deflated": tiles[0],
            "bytes_raw": header["plane_count"] * header["plane_bytes"],
            "bytes_wire": header["payload_len"]}


def tile_census(frame: bytes) -> Dict[str, int]:
    """How an encoded frame's planes travel: ``tiles_stored`` and
    ``tiles_deflated`` (a stored tile is one whose ``comp_len`` is not
    under its ``raw_len``; both 0 for raw planes), ``bytes_raw`` (the
    decoded planes) and ``bytes_wire`` (``payload_len``).  Hops from
    tile header to tile header; inflates nothing."""
    header = parse_header(frame[:HEADER_SIZE])
    tiles = [0, 0]
    if header["flags"] & FLAG_ZLIB:
        pos = HEADER_SIZE + header["meta_len"]
        for _ in range(header["plane_count"]):
            (count,) = struct.unpack_from("<I", frame, pos)
            pos += 4
            for _ in range(count):
                raw_len, comp_len = struct.unpack_from("<II", frame, pos)
                tiles[comp_len >= raw_len] += 1
                pos += 8 + comp_len
    return _census(header, tiles)


def _decode(buf: bytes, expect: int) -> FrameDecoder:
    dec = FrameDecoder(expect=expect)
    dec.feed(buf)
    if not dec.done:
        raise WireError(f"truncated frame: {len(buf)} bytes")
    return dec


def decode_request(buf: bytes) -> WireRequest:
    """One-shot inverse of ``encode_request``."""
    return _decode(buf, FRAME_REQUEST).request()


def decode_response(buf: bytes) -> WireResponse:
    """One-shot inverse of ``encode_response``."""
    return _decode(buf, FRAME_RESPONSE).response()
