"""Tier-1 tests for raftstereo_tpu.wire — the binary frame codec.

Pure numpy + stdlib: no jax, no server.  The seeded fuzz round-trip is
the contract test the serving stack leans on — random shapes, dtypes
and flag combinations must encode -> decode bitwise, fed whole or in
adversarially small chunks.
"""

import json
import struct
import zlib

import numpy as np
import pytest

from raftstereo_tpu import wire
from raftstereo_tpu.wire.format import (PROBE_BYTES, SUPPORTED_VERSIONS,
                                        TILE_BYTES, _HEADER)


def _feed_chunked(buf, rng, expect):
    """Decode via the streaming decoder with random chunk sizes."""
    dec = wire.FrameDecoder(expect=expect)
    pos = 0
    while pos < len(buf):
        step = int(rng.integers(1, 65537))
        dec.feed(buf[pos:pos + step])
        pos += step
    assert dec.done
    return dec


def _tiles(frame):
    """(raw_len, zlib stream) of every tile of a ZLIB frame, read the way
    docs/wire_format.md lays them out and with nothing of the codec's."""
    (_, version, _, flags, _, _, planes, _, _, meta_len,
     payload_len) = _HEADER.unpack(frame[:wire.HEADER_SIZE])
    assert version == 1 and flags & wire.FLAG_ZLIB
    pos = wire.HEADER_SIZE + meta_len
    assert len(frame) == pos + payload_len
    out = []
    for _ in range(planes):
        (count,) = struct.unpack_from("<I", frame, pos)
        pos += 4
        for _ in range(count):
            raw_len, comp_len = struct.unpack_from("<II", frame, pos)
            out.append((raw_len, frame[pos + 8:pos + 8 + comp_len]))
            pos += 8 + comp_len
    assert pos == len(frame)
    return out


def _parent_payload_bytes(planes, level=1):
    """Payload bytes of the encoder before the per-tile decision: every
    1-MiB tile of every plane deflated, whatever its bytes."""
    n = 0
    for p in planes:
        raw = p.tobytes()
        n += 4 + sum(8 + len(zlib.compress(raw[o:o + TILE_BYTES], level))
                     for o in range(0, len(raw), TILE_BYTES))
    return n


def _grain(h, w, seed):
    """Sensor grain: nothing of it deflates (an integer-valued float32
    image, so it travels as uint8)."""
    return np.random.default_rng(seed).integers(
        0, 256, (h, w, 3)).astype(np.float32)


def _camera(h, w, seed):
    """A compressible synthetic capture: smooth shading in three
    channels, quantised to 8 bits; deflates to about a third."""
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    xx = xx + 5 * seed
    base = 120 + 60 * np.sin(xx / 37) * np.cos(yy / 23) \
        + 20 * np.sin((xx + yy) / 11)
    img = np.stack([base, base * 0.9 + 10, base * 0.8 + 25], -1)
    return np.clip(img, 0, 255).astype(np.uint8).astype(np.float32)


def _smooth_exponent_field(h, w, seed):
    """float32 whose low mantissa bytes are noise and whose exponent
    byte hardly changes: a disparity field, as the shuffle sees it."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    return (-(24 + 6 * np.sin(xx / 200) * np.cos(yy / 150))
            + rng.standard_normal((h, w)).astype(np.float32) * 0.01
            ).astype(np.float32)


class TestHeader:
    def test_header_size_is_fixed(self):
        assert wire.HEADER_SIZE == 32

    def test_bad_magic_rejected(self):
        buf = bytearray(wire.encode_response(np.zeros((4, 5), np.float32)))
        buf[:4] = b"NOPE"
        with pytest.raises(wire.WireError, match="magic"):
            wire.decode_response(bytes(buf))

    def test_unknown_version_names_supported_range(self):
        buf = bytearray(wire.encode_response(np.zeros((4, 5), np.float32)))
        struct.pack_into("<H", buf, 4, 7)  # version field
        with pytest.raises(wire.WireVersionError) as ei:
            wire.decode_response(bytes(buf))
        lo, hi = SUPPORTED_VERSIONS
        assert f"{lo}..{hi}" in str(ei.value)
        assert "7" in str(ei.value)

    def test_truncated_frame_rejected(self):
        buf = wire.encode_request(np.ones((6, 7, 3), np.float32) * 0.5,
                                  np.ones((6, 7, 3), np.float32))
        with pytest.raises(wire.WireError, match="truncated"):
            wire.decode_request(buf[:-3])

    def test_trailing_garbage_rejected(self):
        buf = wire.encode_response(np.zeros((4, 5), np.float32))
        with pytest.raises(wire.WireError, match="trailing"):
            wire.decode_response(buf + b"x")

    def test_wrong_frame_type_rejected(self):
        req = wire.encode_request(np.ones((4, 4, 3), np.float32) * 0.25,
                                  np.ones((4, 4, 3), np.float32))
        with pytest.raises(wire.WireError, match="response"):
            wire.decode_response(req)

    def test_hostile_dims_fail_before_allocation(self):
        # A header claiming a ~70 TB plane must be refused by the size
        # guard, not by a MemoryError out of the staging allocation.
        hdr = _HEADER.pack(wire.MAGIC, wire.VERSION, wire.FRAME_REQUEST,
                           0, 1, 3, 2, 2 ** 32 - 1, 2 ** 12, 0, 2 ** 40)
        dec = wire.FrameDecoder(expect=wire.FRAME_REQUEST,
                                max_payload_bytes=256 << 20)
        with pytest.raises(wire.WireError, match="cap"):
            dec.feed(hdr)


class TestRoundTrip:
    def test_seeded_fuzz_bitwise(self):
        # The satellite fuzz test: random shapes/dtypes/flag combos,
        # encode -> decode bitwise, whole-buffer AND chunk-fed.
        rng = np.random.default_rng(20260806)
        dtypes = [np.float32, np.float16, np.uint8, np.int16]
        for trial in range(40):
            h = int(rng.integers(1, 50))
            w = int(rng.integers(1, 50))
            c = int(rng.choice([1, 3, 12]))
            dt = dtypes[trial % len(dtypes)]
            if np.issubdtype(dt, np.floating):
                left = rng.standard_normal((h, w, c)).astype(dt)
                right = rng.standard_normal((h, w, c)).astype(dt)
            else:
                info = np.iinfo(dt)
                left = rng.integers(info.min, info.max, (h, w, c)).astype(dt)
                right = rng.integers(info.min, info.max, (h, w, c)).astype(dt)
            compress = bool(trial % 2)
            shuffle = bool((trial // 2) % 2)
            fields = {"iters": 8, "session_id": f"s{trial}"}
            buf = wire.encode_request(left, right, fields,
                                      compress=compress, shuffle=shuffle,
                                      level=1, allow_uint8=bool(trial % 3))
            for req in (wire.decode_request(buf),
                        _feed_chunked(buf, rng,
                                      wire.FRAME_REQUEST).request()):
                assert req.left.tobytes() == left.tobytes()
                assert req.right.tobytes() == right.tobytes()
                assert req.left.dtype == left.dtype
                assert req.fields == fields

    def test_uint8_demotion_is_bitwise_for_promoted_captures(self):
        # float32 images holding exact 0..255 integers travel as uint8
        # and come back bitwise float32 — at ~4x fewer raw bytes.
        rng = np.random.default_rng(7)
        left = rng.integers(0, 256, (32, 48, 3)).astype(np.float32)
        right = rng.integers(0, 256, (32, 48, 3)).astype(np.float32)
        buf = wire.encode_request(left, right, compress=False)
        req = wire.decode_request(buf)
        assert req.left.dtype == np.float32
        assert req.left.tobytes() == left.tobytes()
        assert req.right.tobytes() == right.tobytes()
        raw = left.nbytes + right.nbytes
        assert len(buf) < raw / 3.9

    @pytest.mark.parametrize("odd", [np.nan, np.inf, -1.0, 256.0, 0.5,
                                     1e20])
    def test_one_value_outside_uint8_keeps_the_pair_float32(self, odd):
        # Demotion is all or nothing, and bitwise either way: a single
        # value that is not a whole number in 0..255 (in either image)
        # keeps both planes float32 on the wire.
        left, right = _grain(20, 30, 1), _grain(20, 30, 2)
        right[7, 11, 2] = odd
        buf = wire.encode_request(left, right)
        assert _HEADER.unpack(buf[:32])[4] == 1  # dtype code: <f4
        req = wire.decode_request(buf)
        assert req.left.tobytes() == left.tobytes()
        assert req.right.tobytes() == right.tobytes()

    def test_non_integer_floats_stay_float32(self):
        left = np.full((4, 4, 3), 0.5, np.float32)
        right = np.full((4, 4, 3), 1.5, np.float32)
        req = wire.decode_request(wire.encode_request(left, right))
        assert req.left.dtype == np.float32
        assert req.left.tobytes() == left.tobytes()

    def test_response_f32_bitwise(self):
        rng = np.random.default_rng(3)
        disp = (rng.standard_normal((33, 47)) * 60).astype(np.float32)
        meta = {"iters": 12, "warm": True}
        for compress in (False, True):
            buf = wire.encode_response(disp, meta, compress=compress)
            res = wire.decode_response(buf)
            assert res.disparity.tobytes() == disp.tobytes()
            assert res.meta == meta
            assert res.manifest is None

    def test_single_byte_chunk_feed_matches_one_shot(self):
        rng = np.random.default_rng(11)
        disp = rng.standard_normal((9, 13)).astype(np.float32)
        buf = wire.encode_response(disp, {"k": 1})
        dec = wire.FrameDecoder(expect=wire.FRAME_RESPONSE)
        for i in range(len(buf)):
            dec.feed(buf[i:i + 1])
        assert dec.done
        assert dec.response().disparity.tobytes() == disp.tobytes()

    def test_multi_tile_plane(self):
        # Plane bigger than one tile: tiles partition and reassemble.
        rng = np.random.default_rng(5)
        h = (3 * TILE_BYTES) // (512 * 4) + 1
        disp = rng.standard_normal((h, 512)).astype(np.float32)
        assert disp.nbytes > 2 * TILE_BYTES
        buf = wire.encode_response(disp, {}, level=1)
        res = _feed_chunked(buf, rng, wire.FRAME_RESPONSE).response()
        assert res.disparity.tobytes() == disp.tobytes()


class TestStoredTiles:
    """The encoder deflates only the tiles that shrink (docs/
    wire_format.md "Compression"); the rest are stored zlib streams."""

    @pytest.mark.parametrize("feed", ["one_shot", "byte_at_a_time"])
    def test_incompressible_plane_is_stored_and_bitwise(self, feed):
        # 80x100x3 uint8 = 24,000 bytes a plane: over PROBE_BYTES, so
        # the decision is made from the strided sample.
        left, right = _grain(80, 100, 1), _grain(80, 100, 2)
        assert left[..., 0].size * 3 > PROBE_BYTES
        buf = wire.encode_request(left, right, {"iters": 4})
        census = wire.tile_census(buf)
        assert census == {"tiles_stored": 2, "tiles_deflated": 0,
                          "bytes_raw": 2 * 24000,
                          "bytes_wire": len(buf) - wire.HEADER_SIZE
                          - _HEADER.unpack(buf[:32])[9]}
        # a stored tile costs its bytes and a few of framing, no more
        assert census["bytes_wire"] < census["bytes_raw"] + 64
        dec = wire.FrameDecoder(expect=wire.FRAME_REQUEST)
        if feed == "one_shot":
            dec.feed(buf)
        else:
            for i in range(len(buf)):
                dec.feed(buf[i:i + 1])
        assert dec.done and dec.census() == census
        req = dec.request()
        assert req.left.dtype == np.float32
        assert req.left.tobytes() == left.tobytes()
        assert req.right.tobytes() == right.tobytes()

    def test_stored_and_deflated_tiles_in_one_plane(self):
        # Shuffled float32 over 4 MiB: the low mantissa byte planes are
        # noise (stored), the exponent byte plane deflates.
        disp = _smooth_exponent_field(1100, 1024, 3)
        assert disp.nbytes > 4 * TILE_BYTES
        buf = wire.encode_response(disp, {"iters": 32})
        census = wire.tile_census(buf)
        assert census["tiles_stored"] >= 2
        assert census["tiles_deflated"] >= 1
        assert census["bytes_wire"] < 0.8 * census["bytes_raw"]
        rng = np.random.default_rng(5)
        chunked = _feed_chunked(buf, rng, wire.FRAME_RESPONSE)
        assert chunked.census() == census
        for res in (wire.decode_response(buf), chunked.response()):
            assert res.disparity.tobytes() == disp.tobytes()

    def test_every_tile_is_a_plain_zlib_stream_at_version_1(self):
        # The compatibility promise: a stored tile is an ordinary zlib
        # stream, so a version-1 decoder that has never heard of the
        # per-tile decision (here: bare zlib.decompress and a transpose)
        # reads the new encoder's frames.
        disp = _smooth_exponent_field(600, 1024, 4)
        buf = wire.encode_response(disp, {})
        assert wire.VERSION == 1 and SUPPORTED_VERSIONS == (1, 1)
        version, flags = struct.unpack_from("<H", buf, 4)[0], buf[7]
        assert version == 1
        assert flags == wire.FLAG_ZLIB | wire.FLAG_SHUFFLE
        tiles = _tiles(buf)
        codings = {len(comp) >= raw_len for raw_len, comp in tiles}
        assert codings == {True, False}, "want stored and deflated tiles"
        planes = b"".join(zlib.decompress(comp) for _, comp in tiles)
        assert [len(zlib.decompress(c)) for _, c in tiles] == \
            [n for n, _ in tiles]
        assert all(n <= TILE_BYTES and len(c) <= 2 * TILE_BYTES
                   for n, c in tiles)
        plain = np.frombuffer(planes, np.uint8).reshape(4, -1).T
        assert plain.tobytes() == disp.tobytes()

    def test_compressible_camera_pair_is_still_deflated(self):
        left, right = _camera(400, 1000, 6), _camera(400, 1000, 7)
        buf = wire.encode_request(left, right, {"iters": 4})
        census = wire.tile_census(buf)
        assert census["tiles_stored"] == 0 and census["tiles_deflated"] == 4
        assert census["bytes_wire"] < 0.45 * census["bytes_raw"]
        parent = _parent_payload_bytes(
            [left.astype(np.uint8), right.astype(np.uint8)])
        assert census["bytes_wire"] <= 1.02 * parent
        req = wire.decode_request(buf)
        assert req.left.tobytes() == left.tobytes()
        assert req.right.tobytes() == right.tobytes()

    def test_one_level_both_directions(self):
        # Request and reply deflate at the same level, the module's:
        # the zlib header of a deflated tile names its level class.
        assert wire.LEVEL == 1
        req = wire.encode_request(_camera(64, 96, 1), _camera(64, 96, 2))
        res = wire.encode_response(np.zeros((64, 96), np.float32))
        for frame in (req, res):
            for raw_len, comp in _tiles(frame):
                assert len(comp) < raw_len
                assert comp[:2] == zlib.compress(b"x" * 64, wire.LEVEL)[:2]
                assert comp[:2] != zlib.compress(b"x" * 64, 6)[:2]

    def test_compress_false_still_means_raw_planes(self):
        left, right = _grain(20, 30, 1), _grain(20, 30, 2)
        buf = wire.encode_request(left, right, compress=False)
        assert buf[7] & wire.FLAG_ZLIB == 0
        assert wire.tile_census(buf) == {
            "tiles_stored": 0, "tiles_deflated": 0,
            "bytes_raw": 2 * 1800, "bytes_wire": 2 * 1800}
        assert wire.decode_request(buf).left.tobytes() == left.tobytes()

    def test_int16_reply_takes_the_same_decision(self):
        disp = _smooth_exponent_field(700, 1024, 8)
        buf = wire.encode_response(disp, {}, encoding="int16")
        census = wire.tile_census(buf)
        assert census["tiles_stored"] + census["tiles_deflated"] == 2
        res = wire.decode_response(buf)
        assert np.max(np.abs(res.disparity - disp)) \
            <= res.manifest["err_bound"]

    def test_binary_carries_a_pair_in_a_quarter_of_the_json_bytes(self):
        """The floor docs/wire_format.md states: request + reply of one
        camera-style pair take at least 4x fewer bytes as wire frames
        than as the base64 JSON dialect (a small reply's exponent plane
        has to stay deflated for it: 3.7x with that plane stored)."""
        from raftstereo_tpu.serve import encode_array

        left, right = _grain(64, 96, 1), _grain(64, 96, 2)
        disp = _smooth_exponent_field(64, 96, 3)
        meta = {"iters": 4, "request_id": "r"}
        binary = len(wire.encode_request(left, right, {})) \
            + len(wire.encode_response(disp, meta))
        as_json = len(json.dumps({"left": encode_array(left),
                                  "right": encode_array(right)})) \
            + len(json.dumps({"disparity": encode_array(disp),
                              "meta": meta}))
        assert as_json >= 4.0 * binary, (as_json, binary)


class TestInt16Manifest:
    def test_manifest_bounds_hold(self):
        rng = np.random.default_rng(17)
        disp = (rng.random((64, 96)) * 190).astype(np.float32)
        buf = wire.encode_response(disp, {}, encoding="int16")
        res = wire.decode_response(buf)
        m = res.manifest
        assert m is not None and m["encoding"] == "int16_fixed"
        # scale is an exact power of two
        assert m["scale"] == 2.0 ** m["scale_log2"]
        measured = float(np.max(np.abs(
            res.disparity.astype(np.float64) - disp.astype(np.float64))))
        # the manifest's measured error is exact, and within the
        # half-step bound of the fixed-point grid
        assert measured == pytest.approx(m["max_abs_err"], abs=0.0)
        assert m["max_abs_err"] <= m["err_bound"]
        assert m["err_bound"] <= 2.0 ** -7  # 190 max -> k >= 7

    def test_zero_disparity_is_exact(self):
        disp = np.zeros((8, 8), np.float32)
        res = wire.decode_response(
            wire.encode_response(disp, {}, encoding="int16"))
        assert res.manifest["max_abs_err"] == 0.0
        assert res.disparity.tobytes() == disp.tobytes()

    def test_nonfinite_falls_back_to_f32(self):
        disp = np.full((6, 6), np.nan, np.float32)
        buf = wire.encode_response(disp, {}, encoding="int16")
        res = wire.decode_response(buf)
        assert res.manifest is None  # fell back: bitwise f32
        assert np.isnan(res.disparity).all()
        assert res.disparity.tobytes() == disp.tobytes()

    def test_int16_smaller_than_f32(self):
        rng = np.random.default_rng(23)
        disp = (rng.random((128, 128)) * 100).astype(np.float32)
        f32 = wire.encode_response(disp, {}, encoding="f32")
        i16 = wire.encode_response(disp, {}, encoding="int16")
        assert len(i16) < len(f32)


class TestNegotiation:
    def test_content_type_matching(self):
        assert wire.is_wire_content_type(wire.WIRE_CONTENT_TYPE)
        assert wire.is_wire_content_type(
            "application/x-raftstereo-frame; charset=binary")
        assert wire.is_wire_content_type(" Application/X-RaftStereo-Frame ")
        assert not wire.is_wire_content_type("application/json")
        assert not wire.is_wire_content_type(None)
        assert not wire.is_wire_content_type("")

    def test_accept_requires_explicit_listing(self):
        assert wire.accepts_wire(wire.WIRE_CONTENT_TYPE)
        assert wire.accepts_wire(
            "application/json, application/x-raftstereo-frame;q=0.9")
        # wildcards and q=0 never select binary
        assert not wire.accepts_wire("*/*")
        assert not wire.accepts_wire("application/*")
        assert not wire.accepts_wire(None)
        assert not wire.accepts_wire(
            "application/x-raftstereo-frame;q=0")
        assert not wire.accepts_wire("application/json")


class TestMalformedPayload:
    def test_payload_len_mismatch_rejected(self):
        disp = np.ones((4, 4), np.float32)
        buf = bytearray(wire.encode_response(disp, {}, compress=False))
        struct.pack_into("<Q", buf, 24, 9999)  # payload_len field
        with pytest.raises(wire.WireError):
            wire.decode_response(bytes(buf))

    def test_corrupt_tile_rejected(self):
        disp = np.ones((64, 64), np.float32)
        buf = bytearray(wire.encode_response(disp, {}))
        buf[-20] ^= 0xFF  # flip a byte inside the zlib stream
        with pytest.raises(wire.WireError):
            wire.decode_response(bytes(buf))

    def test_bad_meta_rejected(self):
        disp = np.ones((4, 4), np.float32)
        buf = bytearray(wire.encode_response(disp, {"a": 1},
                                             compress=False))
        meta_len = struct.unpack_from("<I", buf, 20)[0]
        buf[32:32 + meta_len] = b"{" * meta_len  # still meta_len bytes
        with pytest.raises(wire.WireError, match="meta"):
            wire.decode_response(bytes(buf))

    @staticmethod
    def _fuzz_frame(coding):
        """A request frame whose tiles are all of one coding: grain is
        stored, floats of a few repeated values are deflated."""
        if coding == "stored":
            left, right = _grain(12, 18, 1), _grain(12, 18, 2)
        else:
            rng = np.random.default_rng(20260806)
            left = np.rint(rng.standard_normal((12, 18, 3))
                           ).astype(np.float32) + 0.5
            right = left[::-1].copy()
        buf = wire.encode_request(left, right, {"iters": 4},
                                  compress=True)
        census = wire.tile_census(buf)
        mine, other = (("tiles_stored", "tiles_deflated")
                       if coding == "stored"
                       else ("tiles_deflated", "tiles_stored"))
        assert census[mine] >= 2 and census[other] == 0, census
        return buf

    @pytest.mark.parametrize("coding", ["stored", "deflate"])
    def test_fuzz_truncations_never_complete_or_hang(self, coding):
        # Chaos-plane contract: a frame cut at ANY byte boundary either
        # raises WireError (oversized claims, header damage) or leaves
        # the streaming decoder waiting for more bytes — it must never
        # report done on a prefix, which is what keeps a half-relayed
        # body from being handed to the engine as a frame.
        buf = self._fuzz_frame(coding)
        for cut in range(0, len(buf), 7):
            dec = wire.FrameDecoder(expect=wire.FRAME_REQUEST)
            try:
                dec.feed(buf[:cut])
            except wire.WireError:
                continue
            assert not dec.done, f"prefix of {cut} bytes decoded"
            with pytest.raises(wire.WireError, match="truncated"):
                wire.decode_request(buf[:cut])

    @pytest.mark.parametrize("coding", ["stored", "deflate"])
    def test_fuzz_bitflips_raise_wire_error_or_decode(self, coding):
        # Seeded single-bit corruption anywhere in the frame (the
        # router's corrupt_frame chaos hook does exactly this between
        # hops): the decoder must either raise WireError — the clean
        # 400 the serving stack relies on — or return a materializable
        # request.  Any other exception type would surface as a 500.
        # A stored tile keeps its Adler-32, so it is held to the same.
        rng = np.random.default_rng(20260806)
        buf = self._fuzz_frame(coding)
        rejected = 0
        for _ in range(120):
            i = int(rng.integers(0, len(buf)))
            mutated = bytearray(buf)
            mutated[i] ^= 1 << int(rng.integers(0, 8))
            try:
                req = wire.decode_request(bytes(mutated))
            except wire.WireError:
                rejected += 1
                continue
            req.left.tobytes()
            req.right.tobytes()
        # compressed payloads are checksummed: the vast majority of
        # flips must be caught, not silently decoded
        assert rejected > 60

    def test_meta_survives_json_round_trip(self):
        # frames embed meta as compact JSON — any JSON-legal fields ride
        fields = {"iters": None, "spatial": {"mode": "auto"},
                  "deadline_ms": 33.5, "accuracy": "certified"}
        buf = wire.encode_request(np.ones((2, 2, 3), np.float32) * 0.5,
                                  np.zeros((2, 2, 3), np.float32), fields)
        assert wire.decode_request(buf).fields == json.loads(
            json.dumps(fields))
