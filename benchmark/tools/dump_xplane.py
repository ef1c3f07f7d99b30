"""What a raw ``.xplane.pb`` holds: its planes, their lines with the number
of events, and for every line the names of the statistics found on events and
on the events' metadata, each with one example value.  By hand, after a traced
run kept with ``BENCH_KEEP_RUN_DIR=1``; how an operation is tied to its
``jax.named_scope`` stage (PERF.md section 3) was read from this.

    python benchmark/tools/dump_xplane.py <file.xplane.pb[.gz]> [events per line]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import xplane_wire  # noqa: E402


def _short(v, n=110):
    s = repr(v)
    return s if len(s) <= n else s[:n] + "..."


def main(path, show=2):
    for plane in xplane_wire.planes(path):
        print(f"PLANE {plane.name!r}: {len(plane.lines)} lines, "
              f"{len(plane._meta_raw)} event metadata, "
              f"{len(plane.stat_names)} stat names")
        for i, (name, ts_ns, _) in enumerate(plane.lines):
            n, t_lo, t_hi = 0, None, None
            on_event, on_meta, first, names = {}, {}, [], {}
            for e in plane.events(line_index=i, with_stats=True):
                n += 1
                end = e["start_s"] + e["dur_s"]
                t_lo = e["start_s"] if t_lo is None else min(t_lo, e["start_s"])
                t_hi = end if t_hi is None else max(t_hi, end)
                for k, v in e["stats"].items():
                    on_event.setdefault(k, v)
                for k, v in e["meta"]["stats"].items():
                    on_meta.setdefault(k, v)
                names[e["meta"]["name"][:60]] = names.get(
                    e["meta"]["name"][:60], 0) + 1
                if len(first) < show:
                    first.append(e)
            if not n:
                continue
            print(f"  LINE {name!r}: {n} events, {t_lo:.6f} .. {t_hi:.6f} s "
                  f"(timestamp_ns {ts_ns})")
            print("    stats on events:   "
                  + ", ".join(f"{k}={_short(v, 40)}" for k, v in on_event.items()))
            print("    stats on metadata: "
                  + ", ".join(f"{k}={_short(v, 60)}" for k, v in on_meta.items()))
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            print("    most frequent names: "
                  + ", ".join(f"{k!r} x{c}" for k, c in top))
            for e in first:
                print(f"    e.g. {_short(e['meta']['name'])} display="
                      f"{e['meta']['display']!r} start={e['start_s']:.6f} "
                      f"dur={e['dur_s']:.9f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], *(int(a) for a in sys.argv[2:3])))
