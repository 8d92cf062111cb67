"""Share of the HBM roofline the ``cold_fuse`` kernel reaches: the bytes a
fuse of K rows of N must move, (K + 2) * N * 2, per call, over the device
time of the ``_cold_fuse_impl`` events times the chip's peak bandwidth.

On several chips each chip runs the kernel on its own slice of the rows,
so every event (one a chip a call) must move at least the bytes of
ceil(N / chips) elements (padding is no work), and the events' time is
summed over the chips."""
from bench.counts import fuse_bytes
from bench.tracing import kernel_time


def read(ctx):
    if not ctx.trace or not ctx.peaks:
        return None
    secs, calls = kernel_time(ctx.trace, "_cold_fuse_impl")
    if not calls or secs <= 0:
        return None
    c = ctx.counters
    need = calls * fuse_bytes(c["k"], -(-c["n"] // c.get("chips", 1)))
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
