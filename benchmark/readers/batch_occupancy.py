"""Real rows over ``max_batch_size``, averaged over the window's dispatches
(the ``dispatch`` spans' ``batch_size``; the engine pads every batch to the
full size, so the rest is wasted device work)."""

from benchmark.readers import spans_in_window


def read(ctx, run, params):
    sizes = [s["args"]["batch_size"]
             for s in spans_in_window(run, "dispatch", per_dispatch=True)
             if s["args"].get("batch_size")]
    if not sizes:
        return None
    return 100.0 * sum(sizes) / (len(sizes) * int(ctx.cell["max_batch_size"]))
