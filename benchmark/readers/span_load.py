"""Share of the timed window that the named spans fill, in %: the summed
length of the spans called any of ``spans`` that start inside the window,
over the window's seconds.  params: spans (names), per ("dispatch": one per
batch, as ``span_median``).  For spans that one thread, or one lock, holds in
turn (the launcher's ``pad_bucket`` and ``launch``; ``reply_encode``, under
the server's reply lock) it is how full that serial resource was: near 100 %
it paces everything behind it.  A ring without the spans gives nothing."""

from benchmark.readers import spans_in_window


def read(ctx, run, params):
    per_dispatch = params.get("per") == "dispatch"
    spans = [s for name in params["spans"]
             for s in spans_in_window(run, name, per_dispatch)]
    if not spans:
        return None
    return 100.0 * sum(s["dur"] for s in spans) * 1e-6 / (
        run["t_end"] - run["t0"])
