"""Share of the HBM roofline the ``cold_fuse`` kernel reaches: the bytes a
fuse of K rows of N must move, (K + 2) * N * 2, per call, over the device
time of the ``_cold_fuse_impl`` events times the chip's peak bandwidth."""
from bench.counts import fuse_bytes
from bench.tracing import kernel_time


def read(ctx):
    if not ctx.trace or not ctx.peaks:
        return None
    secs, calls = kernel_time(ctx.trace, "_cold_fuse_impl")
    if not calls or secs <= 0:
        return None
    need = calls * fuse_bytes(ctx.counters["k"], ctx.counters["n"])
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
