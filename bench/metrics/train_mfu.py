"""Model FLOP utilization of the contributor's steps: the model FLOPs of
the steps completed in the traced window (``counts.train_flops_per_step``)
over the window times the chip's peak bf16 rate."""
from bench.counts import train_flops_per_step


def read(ctx):
    c = ctx.counters
    if not ctx.peaks or not c.get("steps") or c.get("window_s", 0.0) <= 0:
        return None
    flops = c["steps"] * train_flops_per_step(ctx.cfg, c["batch"], c["seq"],
                                              c["num_classes"])
    return 100.0 * flops / c["window_s"] / ctx.peaks["bf16_flops_per_s"]
