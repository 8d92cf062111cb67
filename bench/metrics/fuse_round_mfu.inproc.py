"""The whole round's share of the chip's peak: the least time a chip needs
for the round's fuse (the larger of its bytes at peak bandwidth and its
FLOPs at peak rate) over the round's time in the traced window."""
from bench.counts import fuse_required_s


def read(ctx):
    if not ctx.peaks:
        return None
    n, secs = ctx.counters.get("rounds", 0), ctx.counters.get("window_s", 0.0)
    if not n or secs <= 0:
        return None
    need = n * fuse_required_s(ctx.counters["k"], ctx.counters["n"], ctx.peaks)
    return 100.0 * need / secs
