"""The whole step's share of the chip's bf16 peak: analytic operations of
the real (un-padded) pairs (benchmark/work.py) times the pairs finished a
second in this run's window, over chips times peak (benchmark/peaks.json)."""

from benchmark import work


def read(ctx, run, params):
    rate = run.get("pairs_per_s")
    if not rate:
        return None
    flops = work.pair_flops(ctx.config["model"], tuple(ctx.cell["image_hw"]),
                            int(ctx.config["iters"]))
    pk = work.peaks(run["runtime"]["device_kind"])
    return 100.0 * flops * rate / (ctx.chips * pk["bf16_flops_per_s"])
