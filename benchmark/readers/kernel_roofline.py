"""A kernel's share of its roofline: the least time the chip could take for
the calls the trace shows (operations and bytes of one call from
benchmark/work.py at the shapes the program runs it at — the padded batch
and bucket — against benchmark/peaks.json) over the kernel's device time in
the trace.  params: kernel (a ``work.kernel_<name>`` function), patterns
(regular expressions on the trace's operation names, as one hand-read trace
shows them).  Nothing to read (no trace, no matching operation) gives
nothing."""

from benchmark import trace_reduce, work
from benchmark.child import say
from benchmark.reference.raft_stereo import bucket_pad


def read(ctx, run, params):
    if not run.get("planes"):
        return None
    kt = trace_reduce.kernel_time(run["planes"], params["patterns"])
    if not kt["calls"] or kt["seconds"] <= 0:
        return None
    h, w = ctx.cell["image_hw"]
    if ctx.cell["driver"].startswith("serve"):
        t, b, l, r = bucket_pad((h, w), ctx.cell.get("divis_by", 32),
                                ctx.cell.get("bucket_multiple", 64))
        h, w = h + t + b, w + l + r
    batch = int(ctx.cell.get("max_batch_size", ctx.cell.get("batch_size", 1)))
    one = getattr(work, "kernel_" + params["kernel"])(
        dict(ctx.config["model"], compute_dtype=ctx.config["compute_dtype"]),
        (h, w), batch)
    least, bound = work.least_seconds(
        one, work.peaks(run["runtime"]["device_kind"]))
    say(f"[roofline] {params['kernel']}: {kt['calls']:.0f} calls, "
        f"{kt['seconds'] / kt['calls'] * 1e3:.3f} ms a call on the device, "
        f"least {least * 1e3:.3f} ms ({bound} bind) at batch {batch}, "
        f"{h}x{w}")
    return 100.0 * least * kt["calls"] / kt["seconds"]
