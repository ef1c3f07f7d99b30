"""Cut a profiler trace down to something a repository can hold.

    python benchmark/tests/make_small_trace.py <in.xplane.pb> <out.xplane.pb.gz> [events]

Keeps the first device plane, its ``XLA Ops`` line with the first ``events``
operations, and the metadata those refer to; everything else (host threads,
other lines) goes.  Works on the protobuf wire format directly (XSpace.planes=1;
XPlane.name=2, lines=3, event_metadata=4; XLine.name=2, events=4;
XEvent.metadata_id=1), since no xplane bindings are installed.
``data/serve_default_b8.xplane.pb.gz`` was made by it from a traced run of
``default_serve_saturated`` (PR 25).
"""

import gzip
import sys


def varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if not b & 0x80:
            return x, i
        shift += 7


def enc_varint(x):
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def fields(buf):
    """[(field number, wire type, raw value bytes incl. nothing of the key)]"""
    i, out = 0, []
    while i < len(buf):
        key, i = varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            j = i
            _, i = varint(buf, i)
            out.append((num, wt, buf[j:i]))
        elif wt == 2:
            n, i = varint(buf, i)
            out.append((num, wt, buf[i:i + n]))
            i += n
        elif wt == 1:
            out.append((num, wt, buf[i:i + 8]))
            i += 8
        elif wt == 5:
            out.append((num, wt, buf[i:i + 4]))
            i += 4
        else:
            raise ValueError(f"wire type {wt}")
    return out


def emit(num, wt, val):
    key = enc_varint(num << 3 | wt)
    return key + (enc_varint(len(val)) + val if wt == 2 else val)


def main(src, dst, n_events=7000):
    space = open(src, "rb").read()
    for num, wt, plane in fields(space):
        if num != 1:
            continue
        pf = fields(plane)
        name = next(v for n, _, v in pf if n == 2).decode()
        if not name.startswith("/device:TPU:"):
            continue
        used, lines = set(), []
        for n, w, v in pf:
            if n != 3:
                continue
            lf = fields(v)
            if next(x for k, _, x in lf if k == 2) != b"XLA Ops":
                continue
            kept, count = [], 0
            for k, lw, x in lf:
                if k == 4:
                    if count >= n_events:
                        continue
                    count += 1
                    used.add(varint(next(
                        y for f, _, y in fields(x) if f == 1), 0)[0])
                kept.append(emit(k, lw, x))
            lines.append(emit(3, 2, b"".join(kept)))
        out = []
        for n, w, v in pf:
            if n == 3:
                continue
            if n == 4:      # map entry: key=1 (metadata id), value=2
                key = varint(next(y for f, _, y in fields(v) if f == 1), 0)[0]
                if key not in used:
                    continue
            out.append(emit(n, w, v))
        small = emit(1, 2, b"".join(out + lines))
        with gzip.open(dst, "wb") as f:
            f.write(small)
        print(f"{name}: {len(used)} operation names, {len(small)} bytes")
        return 0
    print("no device plane")
    return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2],
                  *(int(a) for a in sys.argv[3:4])))
