"""Per-op device-time breakdown of the flagship forward.

Runs a short ``jax.profiler`` trace around compiled forward executions and
aggregates device-stream op durations from the generated Perfetto JSON, so
optimisation work targets measured time, not guesses (VERDICT r2 items 1-2).

Usage:
    python scripts/profile_flagship.py [--iters 32] [--batch 1] [--top 40]
                                       [--realtime] [--stage fixed|loop|all]
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def build_forward(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raftstereo_tpu.config import RAFTStereoConfig
    from raftstereo_tpu.models.raft_stereo import RAFTStereo
    from raftstereo_tpu.ops.image import InputPadder

    model_kw = {}
    if args.realtime:
        model_kw = dict(shared_backbone=True, n_downsample=3, n_gru_layers=2,
                        hidden_dims=(128, 128), slow_fast_gru=True)
    cfg = RAFTStereoConfig(corr_implementation=args.corr,
                           compute_dtype="bfloat16", **model_kw)
    model = RAFTStereo(cfg)
    variables = model.init(jax.random.key(0), (64, 96))
    rng = np.random.default_rng(0)
    img = rng.integers(0, 255, (args.batch, args.height, args.width, 3))
    img1 = jnp.asarray(img.astype(np.float32))
    img2 = jnp.asarray(img.astype(np.float32))
    padder = InputPadder(img1.shape, divis_by=32)
    img1, img2 = padder.pad(img1, img2)
    fwd = jax.jit(lambda v, a, b: model.forward(v, a, b, iters=args.iters,
                                                test_mode=True))
    return fwd, variables, img1, img2


def collect_trace(fn, reps, log_dir):
    import jax

    fn()  # compile + warm
    fn()
    with jax.profiler.trace(log_dir):
        for _ in range(reps):
            fn()


def load_device_events(log_dir):
    """Parse the Perfetto trace: return [(name, dur_us)] for device-lane ops."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if not paths:
        raise SystemExit(f"no trace found under {log_dir}")
    path = max(paths, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    # Identify device process ids: process_name metadata containing TPU/device.
    device_pids = set()
    tid_names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            name = e.get("args", {}).get("name", "")
            if re.search(r"(TPU|/device:|XLA)", name, re.I):
                device_pids.add(e["pid"])
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tid_names[(e["pid"], e["tid"])] = e.get("args", {}).get("name", "")
    out = []
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in device_pids:
            continue
        lane = tid_names.get((e["pid"], e["tid"]), "")
        if re.search(r"step|scope", lane, re.I):
            continue  # step/annotation lanes duplicate op time
        out.append((e.get("name", "?"), float(e.get("dur", 0.0)),
                    e.get("args", {}) or {}))
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--height", type=int, default=540)
    p.add_argument("--width", type=int, default=960)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--iters", type=int, default=32)
    p.add_argument("--corr", default="pallas_alt")
    p.add_argument("--reps", type=int, default=2)
    p.add_argument("--top", type=int, default=30)
    p.add_argument("--realtime", action="store_true")
    p.add_argument("--log_dir", default="/tmp/raft_profile")
    p.add_argument("--reuse", action="store_true",
                   help="re-analyze the existing trace without running")
    args = p.parse_args()

    if not args.reuse:
        fwd, variables, img1, img2 = build_forward(args)

        def run():
            lo, up = fwd(variables, img1, img2)
            float(up.sum())

        os.makedirs(args.log_dir, exist_ok=True)
        collect_trace(run, args.reps, args.log_dir)

    events = load_device_events(args.log_dir)
    # Parent spans (the whole jit program, the scan while loop) contain the
    # op events — keep them out of sums, but report the loop total.
    per_op = {}
    loop_ms = prog_ms = 0.0
    for name, dur, a in events:
        if name.startswith("jit_"):
            prog_ms += dur
            continue
        if name.startswith("while"):
            loop_ms += dur
            continue
        rec = per_op.setdefault(name, {"dur": 0.0, "n": 0, "args": a})
        rec["dur"] += dur
        rec["n"] += 1
    r = args.reps
    total = sum(v["dur"] for v in per_op.values()) / r

    def fmt(name, rec):
        a = rec["args"]
        dur_us = rec["dur"] / r / max(rec["n"] // r, 1)  # per single run
        n = rec["n"] // r
        flops = float(a.get("model_flops", 0) or 0)
        bts = float(a.get("raw_bytes_accessed", 0) or 0)
        tfs = flops / (dur_us * 1e-6) / 1e12 if dur_us else 0.0
        gbs = bts / (dur_us * 1e-6) / 1e9 if dur_us else 0.0
        cat = a.get("hlo_category", "?")
        src = (a.get("source") or "").split("/")[-1]
        ln = a.get("long_name", "")
        m = re.search(r"= (\S+?)\{", ln)
        shape = m.group(1) if m else ""
        return (f"  {rec['dur']/r/1000:7.3f} ms x{n:<3d} {dur_us:7.1f}us "
                f"{tfs:6.1f}TF/s {gbs:5.0f}GB/s {cat[:18]:18s} "
                f"{shape[:28]:28s} {src[:30]}")

    hdr = ("   total       n   per-op     TF/s      GB/s  category"
           "           out-shape                    source")
    print(f"\n== device op time per execution: {total/1000:.2f} ms; "
          f"scan loop span: {loop_ms/r/1000:.2f} ms; "
          f"program span: {prog_ms/r/1000:.2f} ms ==")
    per_iter = {k: v for k, v in per_op.items() if v["n"] >= r * args.iters}
    fixed = {k: v for k, v in per_op.items() if v["n"] < r * args.iters}
    lsum = sum(v["dur"] for v in per_iter.values()) / r
    fsum = sum(v["dur"] for v in fixed.values()) / r
    print(f"\n-- LOOP ops (x{args.iters}): {lsum/1000:.2f} ms total, "
          f"{lsum/1000/args.iters:.4f} ms/iter --")
    print(hdr)
    for name, rec in sorted(per_iter.items(), key=lambda kv: -kv[1]["dur"])[
            : args.top]:
        print(fmt(name, rec))
    print(f"\n-- FIXED-stage ops: {fsum/1000:.2f} ms total --")
    print(hdr)
    for name, rec in sorted(fixed.items(), key=lambda kv: -kv[1]["dur"])[
            : args.top]:
        print(fmt(name, rec))

    # Category rollup over everything (parents excluded).
    cats = collections.Counter()
    for name, rec in per_op.items():
        cats[rec["args"].get("hlo_category", "?")] += rec["dur"]
    print("\n-- by hlo_category --")
    for cat, dur in cats.most_common():
        print(f"  {cat:28s} {dur/r/1000:8.3f} ms ({100*dur/r/total:5.1f}%)")


if __name__ == "__main__":
    main()
