"""The whole round's share of the chips' peak: the least time the cell's
chips together need for the round's fuse (the larger of its bytes at their
summed peak bandwidth and its FLOPs at their summed peak rate) over the
round's time in the traced window."""
from bench.counts import fuse_required_s


def read(ctx):
    if not ctx.peaks:
        return None
    c = ctx.counters
    n, secs = c.get("rounds", 0), c.get("window_s", 0.0)
    if not n or secs <= 0:
        return None
    need = n * fuse_required_s(c["k"], c["n"], ctx.peaks) / c.get("chips", 1)
    return 100.0 * need / secs
