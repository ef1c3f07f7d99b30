"""One file per reader: ``read(ctx, run, params) -> number or None``."""


def spans_in_window(run, name, per_dispatch=False):
    """The program's spans called ``name`` that started inside the window;
    with ``per_dispatch`` one per batch (the batcher records a batch's
    dispatch and device_compute once for every request in it, with the same
    start)."""
    spans = [s for s in run.get("spans", []) if s["name"] == name
             and run["t0"] * 1e6 <= s["ts"] <= run["t_end"] * 1e6]
    if per_dispatch:
        spans = list({round(s["ts"]): s for s in spans}.values())
    return spans
