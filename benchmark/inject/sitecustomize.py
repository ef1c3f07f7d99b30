"""Loaded by the interpreter of the benchmark's children (it sits first on
their PYTHONPATH).  The program exposes no device-memory reading, and only
the process that holds the chip can take one, so on SIGUSR1 that process
writes ``device.memory_stats()`` of its fullest chip to the path the runner
named.  Nothing else of the program is touched, and without the variable
nothing is installed."""

import os
import signal

_PATH = os.environ.get("BENCH_MEMSTATS_PATH")


def _write_memstats(signum, frame):
    import json

    out = {"error": None}
    try:
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        # this runtime keeps compiled programs' temporaries in a pool of
        # its own ("reserved"), outside the allocator's "in use"
        out["peak_bytes_in_use"] = max(
            int(s.get("peak_bytes_in_use", 0)) for s in stats)
        out["peak_bytes_reserved"] = max(
            int(s.get("peak_bytes_reserved", 0)) for s in stats)
        out["peak_bytes"] = max(
            int(s.get("peak_bytes_in_use", 0))
            + int(s.get("peak_bytes_reserved", 0)) for s in stats)
        out["bytes_in_use"] = max(int(s.get("bytes_in_use", 0)) for s in stats)
        out["bytes_limit"] = max(int(s.get("bytes_limit", 0)) for s in stats)
        out["devices"] = len(stats)
        out["all"] = {k: int(v) for k, v in stats[0].items()
                      if isinstance(v, (int, float))}
    except Exception as e:  # the runner reports a run without a reading
        out["error"] = repr(e)
    tmp = _PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, _PATH)


if _PATH:
    signal.signal(signal.SIGUSR1, _write_memstats)
