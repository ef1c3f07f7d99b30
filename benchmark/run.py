#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up, warms, measures for ``--seconds``, checks what the timed path
produced against the plain reference, prints one JSON object as the last line
of standard output and exits 0.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics (with a device trace taken inside
the window).  This process never initialises a JAX backend: the program's own
entry point runs as one child that holds the chip.  Without a TPU, with fewer
chips than the cell asks for, or without the program beside it, it prints no
result and exits non-zero.  ``--rehearse`` drives every driver, reader and the
last line's shape at toy size wherever JAX lands, prints its numbers under a
``rehearsal`` key and never under ``metrics``, and exits non-zero.

Everything that belongs to one cell, configuration or metric is a file that
is found by the name ``BENCHMARK.json`` gives (see ``benchmark/README.md``).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import signal
import sys
import time
from types import SimpleNamespace
from typing import Dict, Optional

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import child  # noqa: E402
from benchmark.child import BenchFailure, say  # noqa: E402

HERE = os.path.join(ROOT, "benchmark")


def load_json(*parts: str) -> Dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def rehearsal_cell(cell: Dict) -> Dict:
    """Toy sizes for --rehearse, from the cell's own ``rehearse`` block."""
    out = dict(cell)
    out.update(cell.get("rehearse", {}))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    # ended from outside: unwind through the finally blocks, which stop the
    # chip-holding child and the clients, instead of leaving them behind
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    try:
        if not os.path.isdir(os.path.join(ROOT, "raftstereo_tpu")):
            raise BenchFailure("the program (raftstereo_tpu/) is not beside "
                               "benchmark/: nothing to measure")
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        entry = {w["name"]: w for w in manifest["workloads"]}.get(
            args.workload)
        if entry is None:
            raise BenchFailure(f"no cell {args.workload!r} in BENCHMARK.json")
        cell = load_json("workloads", f"{args.workload}.json")
        if args.rehearse:
            cell = rehearsal_cell(cell)
        config = load_json("configs", f"{entry['config']}.json")
        if args.rehearse:
            config = dict(config, iters=2)
        seconds = float(args.seconds if args.seconds is not None
                        else manifest["run_seconds"])

        child.REHEARSE = args.rehearse
        dev = child.probe_device()
        say(f"[probe] {json.dumps(dev)}")
        if not args.rehearse and (dev["platform"] != "tpu"
                                  or dev["count"] < entry["chips"]):
            raise BenchFailure(
                f"the cell needs {entry['chips']} TPU chip(s); JAX finds "
                f"{dev['count']} x {dev['platform']}: no CPU fallback")

        run_dir = os.path.join(ROOT, ".bench_runs", args.workload)
        shutil.rmtree(run_dir, ignore_errors=True)
        os.makedirs(run_dir)
        ctx = SimpleNamespace(
            cell=cell, config=config, entry=entry, seed=args.seed,
            seconds=seconds, trace=bool(args.trace), rehearse=args.rehearse,
            chips=entry["chips"], run_dir=run_dir, t_start=T_START,
            device=dev, manifest=manifest)
        driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
        result = driver.run(ctx)

        # ---- metrics: the cell's end-to-end ones, or its per-layer ones
        if ctx.trace:
            from benchmark import trace_reduce
            planes, free_from = trace_reduce.load_trace(result["xplane"])
            if not planes and not ctx.rehearse:
                raise BenchFailure("the device trace shows no operation on "
                                   "a device")
            result["planes"] = planes
            tr = result["trace"] = trace_reduce.reduce_events(
                planes, host_spans_on_trace_clock(result, planes),
                free_from=free_from)
            say(f"[trace] host tracer ended at {free_from}; busy / window "
                f"after it {tr['busy_s']:.3f} / {tr['window_s']:.3f} s"
                if tr["tracer_free"] else
                f"[trace] under a second recorded past the host tracer's "
                f"end ({free_from}): busy, window and the idle share are "
                "the whole trace's, slowed by the tracer")
            if tr.get("traced"):
                say(f"[trace] whole trace, host tracer on for most of it: "
                    f"busy / window {tr['traced']['busy_s']:.3f} / "
                    f"{tr['traced']['window_s']:.3f} s")
        values = {}
        for kind in ("end_to_end", "per_layer"):
            values[kind] = read_metrics(ctx, result, manifest[kind])
        shown = values["per_layer" if ctx.trace else "end_to_end"]
        if ctx.trace:
            say("[trace] end-to-end readings of this traced run (compare "
                "with untraced runs for the tracing overhead): "
                + json.dumps({k: v["value"] for k, v in
                              values["end_to_end"].items()}))

        # ---- correct
        checks = dict(result.get("checks", {}))
        checks.update(driver.check(ctx, result))
        correct = all(_passes(c) for c in checks.values())
        mem = result.get("memstats") or {}
        device = {"platform": result["runtime"]["platform"],
                  "kind": result["runtime"]["device_kind"],
                  "count": result["runtime"]["device_count"],
                  "memory_peak_bytes": mem.get("peak_bytes"),
                  "memory_in_use_peak_bytes": mem.get("peak_bytes_in_use"),
                  "memory_reserved_peak_bytes": mem.get("peak_bytes_reserved")}
        line = {"correct": correct, "attempted": result["attempted"],
                "failed": result["failed"]}
        if ctx.trace:
            tr = result["trace"]
            device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
            line["breakdown"] = {"device_ops": tr["device_ops"],
                                 "idle_gaps": tr["idle_gaps"]}
        line["metrics" if not ctx.rehearse else "rehearsal"] = shown
        line["device"] = device
        line["checks"] = checks
        for name, c in checks.items():
            say(f"[check] {name}: {json.dumps(c)} -> "
                f"{'ok' if _passes(c) else 'NOT ok'}")
        if ctx.rehearse:
            print(json.dumps(line), flush=True)
            say("rehearsal: no measurement was made; exiting non-zero")
            return 3
        if device["memory_peak_bytes"] is None:
            raise BenchFailure(f"no device memory reading: {mem}")
        print(json.dumps(line), flush=True)
        return 0
    except BenchFailure as e:
        say(f"benchmark FAILED after {time.time() - T_START:.0f}s: {e}")
        return 1
    finally:
        if not os.environ.get("BENCH_KEEP_RUN_DIR"):
            shutil.rmtree(os.path.join(ROOT, ".bench_runs", args.workload),
                          ignore_errors=True)


def _passes(c: Dict) -> bool:
    if "limit" in c:
        return c["value"] is not None and c["value"] <= c["limit"]
    return c["value"] is not None and c["value"] >= c["at_least"]


def read_metrics(ctx, result: Dict, declared) -> Dict[str, Dict]:
    """Every declared metric that lists this cell and whose reader finds
    something to read; a reader that finds nothing is left out."""
    out = {}
    for m in declared:
        if "workloads" in m and ctx.entry["name"] not in m["workloads"]:
            continue
        spec = load_json("metrics", f"{m['name']}.json")
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        try:
            value = reader.read(ctx, result, spec.get("params", {}))
        except KeyError as e:
            if not ctx.rehearse:        # e.g. a device that has no peaks
                raise BenchFailure(f"metric {m['name']}: {e}")
            say(f"[rehearse] a measured run would fail here: {e}")
            continue
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def host_spans_on_trace_clock(result: Dict, planes) -> Optional[list]:
    """The program's spans as {name, t0, t1} on the device trace's clock
    (see ``trace_reduce.clock_offset``); None where the clocks cannot be
    laid onto each other, and the gaps then stay unnamed."""
    from benchmark import trace_reduce

    spans = result.get("spans", [])
    synced = [s for s in spans if s["name"] == "device_compute"]
    sync = sorted({round((s["ts"] + s["dur"]) * 1e-6, 4) for s in synced})
    durs = sorted(s["dur"] * 1e-6 for s in synced)
    off = trace_reduce.clock_offset(
        planes, sync, result.get("trace_started_at"),
        min_burst_s=0.5 * durs[len(durs) // 2] if durs else 0.0)
    say(f"[trace] clock offset trace -> host: {off}")
    if off is None:
        return None
    return [{"name": s["name"], "t0": s["ts"] * 1e-6 - off,
             "t1": (s["ts"] + s["dur"]) * 1e-6 - off} for s in spans]


if __name__ == "__main__":
    sys.exit(main())
