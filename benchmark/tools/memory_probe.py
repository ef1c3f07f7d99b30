#!/usr/bin/env python3
"""What ``memory_peak_bytes`` is made of, shown for one cell (by hand, on the
chip; no run of the benchmark calls this):

    python benchmark/tools/memory_probe.py --workload default_serve_saturated

Builds the program's engine as ``cli.serve`` builds it from the cell's flags,
compiles the bucket program the window drives (``BatchEngine._fn``: the same
jitted function, so the same executable and the same cache entry), prints its
``memory_analysis()`` (arguments, outputs, temporaries, code), runs one full
batch through ``infer_batch`` and prints ``device.memory_stats()`` before and
after.  The runner reads ``peak_bytes_in_use + peak_bytes_reserved`` of the
serving child as ``memory_peak_bytes``; this shows whether ``reserved`` is the
program's temporaries or an unfilled pool.  Holds the chip itself: run nothing
beside it.  The last line is one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def stats(dev):
    s = dev.memory_stats() or {}
    return {k: int(s[k]) for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved", "bytes_limit", "bytes_reservable_limit",
        "largest_alloc_size") if k in s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--tiny", action="store_true",
                    help="the cell's rehearsal sizes, to try the script")
    args = ap.parse_args(argv)
    here = os.path.join(ROOT, "benchmark")
    with open(os.path.join(here, "workloads", f"{args.workload}.json")) as f:
        cell = json.load(f)
    if args.tiny:
        cell.update(cell.get("rehearse", {}))
    with open(os.path.join(here, "configs", f"{cell['config']}.json")) as f:
        config = json.load(f)
    iters = 2 if args.tiny else int(config["iters"])
    h, w = cell["image_hw"]

    import jax
    import numpy as np

    from raftstereo_tpu.cli.serve import build_parser
    from raftstereo_tpu.config import (model_config_from_args,
                                       serve_config_from_args)
    from raftstereo_tpu.models import RAFTStereo
    from raftstereo_tpu.serve.engine import BatchEngine
    from raftstereo_tpu.utils.platform import setup_compile_cache

    setup_compile_cache()
    a = build_parser().parse_args(
        ["--buckets", f"{h}x{w}", "--serve_iters", str(iters),
         "--degraded_iters", str(iters), "--no_stream",
         *config["flags"], *cell.get("flags", [])])
    dev = jax.local_devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "cell": args.workload, "at_start": stats(dev)}
    model = RAFTStereo(model_config_from_args(a))
    variables = model.init(jax.random.key(0))
    engine = BatchEngine(model, variables, serve_config_from_args(a))
    out["weights_loaded"] = stats(dev)

    batch = int(cell["max_batch_size"])
    pair = (np.zeros((h, w, 3), np.float32), np.ones((h, w, 3), np.float32))
    _, hw, i1, i2, _ = engine._pad_pairs([pair] * batch)
    mode = engine._mode(None)
    compiled = engine._fn(iters, mode).lower(engine.variables, i1,
                                             i2).compile()
    ma = compiled.memory_analysis()
    out["bucket"] = [batch, hw[0], hw[1], iters, mode]
    out["memory_analysis"] = {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(ma, k)}
    del i1, i2
    out["compiled"] = stats(dev)
    disp = engine.infer_batch([pair] * batch, iters)
    assert len(disp) == batch and disp[0].shape == (h, w)
    out["after_one_batch"] = stats(dev)
    disp = engine.infer_batch([pair] * batch, iters)
    out["after_two_batches"] = stats(dev)
    for k in ("at_start", "weights_loaded", "memory_analysis", "compiled",
              "after_one_batch", "after_two_batches"):
        print(f"[memory_probe] {k}: {json.dumps(out[k])}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
