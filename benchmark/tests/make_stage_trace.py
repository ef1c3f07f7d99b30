"""Cut a profiler trace down to what the stage and host-phase readers read.

    python benchmark/tests/make_stage_trace.py <in.xplane.pb[.gz]> <out.xplane.pb.gz> <from_s> <to_s>

Beside ``make_small_trace.py`` (which keeps the first operations of the device
plane and nothing of the host).  This one keeps, between the two trace times:

* the first device plane's ``XLA Ops`` line, every operation's metadata cut to
  its name, display name and ``tf_op`` statistic (the ``op_name`` path the
  program's ``jax.named_scope`` stages are read from);
* of the host plane, on whichever thread they lie, the events the program
  emits itself — ``Tracer.phase`` / ``timed_phase`` annotations by name, with
  their statistics — and both ``obs.clock`` events wherever they lie.  The
  runtime's own host events (millions of ``Transpose``) go.

``data/serve_default_b8_stages.xplane.pb.gz`` was made by it from a traced run
of ``default_serve_saturated`` (PR 26): two whole dispatches under a capture.
"""

import gzip
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from make_small_trace import emit, enc_varint, fields, varint  # noqa: E402

from benchmark.xplane_wire import read_bytes  # noqa: E402

PROGRAM_EVENTS = {"queue_empty", "batch_form", "pad_bucket", "launch",
                  "device_wait", "host_fetch", "reply_handoff", "wire_decode",
                  "reply", "data_wait", "step", "checkpoint"}
CLOCK = "obs.clock"
KEEP_STATS = {"tf_op"}


def _map_entry(v):
    key, val = 0, b""
    for f, _, y in fields(v):
        if f == 1:
            key = varint(y, 0)[0]
        elif f == 2:
            val = y
    return key, val


def _plane_parts(plane):
    name, lines, metas, stat_names, rest = "", [], {}, {}, []
    for n, w, v in fields(plane):
        if n == 2:
            name = v.decode()
        elif n == 3:
            lines.append(v)
        elif n == 4:
            k, val = _map_entry(v)
            metas[k] = val
        elif n == 5:
            k, val = _map_entry(v)
            stat_names[k] = next((y.decode() for f, _, y in fields(val)
                                  if f == 2), "")
        else:
            rest.append(emit(n, w, v))
    return name, lines, metas, stat_names, rest


def _cut_line(line, keep_event):
    """The line with only the events ``keep_event(metadata id, start_s,
    end_s)`` keeps; returns (bytes, kept metadata ids) or (None, ...)."""
    lf = fields(line)
    ts_ns = next((varint(x, 0)[0] for k, _, x in lf if k == 3), 0)
    kept, used = [], set()
    for k, w, x in lf:
        if k == 4:
            mid = off = dur = 0
            for f, _, y in fields(x):
                if f == 1:
                    mid = varint(y, 0)[0]
                elif f == 2:
                    off = varint(y, 0)[0]
                elif f == 3:
                    dur = varint(y, 0)[0]
            t0 = ts_ns * 1e-9 + off * 1e-12
            if not keep_event(mid, t0, t0 + dur * 1e-12):
                continue
            used.add(mid)
        kept.append(emit(k, w, x))
    return (emit(3, 2, b"".join(kept)) if used else None), used


def _slim_meta(val, keep_stat_ids):
    out = []
    for f, w, y in fields(val):
        if f == 5:
            sid = next((varint(z, 0)[0] for g, _, z in fields(y) if g == 1), 0)
            if sid not in keep_stat_ids:
                continue
        out.append(emit(f, w, y))
    return b"".join(out)


def _emit_map(field, key, val):
    return emit(field, 2, emit(1, 0, enc_varint(key)) + emit(2, 2, val))


def main(src, dst, t_from, t_to):
    t_from, t_to = float(t_from), float(t_to)
    space = bytes(read_bytes(src))
    out_planes, device_done = [], False
    for num, _, plane in fields(space):
        if num != 1:
            continue
        name, lines, metas, stat_names, rest = _plane_parts(plane)
        meta_name = {k: next((y.decode("utf-8", "replace") for f, _, y in
                              fields(v) if f == 2), "")
                     for k, v in metas.items()}
        if name.startswith("/device:TPU:") and not device_done:
            device_done = True
            keep_ids = {k for k, n in stat_names.items() if n in KEEP_STATS}

            def keep_event(mid, t0, t1):
                return t_from <= t0 and t1 <= t_to
            only = [ln for ln in lines if next(
                x for k, _, x in fields(ln) if k == 2) == b"XLA Ops"]
        elif name == "/host:CPU":
            keep_ids = set(stat_names)

            def keep_event(mid, t0, t1, meta_name=meta_name):
                n = meta_name.get(mid)
                return n == CLOCK or (n in PROGRAM_EVENTS
                                      and t_from <= t0 and t1 <= t_to)
            only = lines
        else:
            continue
        new_lines, used = [], set()
        for ln in only:
            cut, ids = _cut_line(ln, keep_event)
            if cut is not None:
                new_lines.append(cut)
                used |= ids
        body = [emit(2, 2, name.encode())]
        body += [_emit_map(4, k, _slim_meta(metas[k], keep_ids))
                 for k in sorted(used)]
        body += [_emit_map(5, k, emit(1, 0, enc_varint(k))
                           + emit(2, 2, n.encode()))
                 for k, n in sorted(stat_names.items())
                 if k in keep_ids or name == "/host:CPU"]
        out_planes.append(emit(1, 2, b"".join(body + new_lines)))
        print(f"{name}: {len(used)} event names kept")
    small = b"".join(out_planes)
    with gzip.open(dst, "wb") as f:
        f.write(small)
    print(f"{len(small)} bytes, {os.path.getsize(dst)} gzipped")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:5]))
