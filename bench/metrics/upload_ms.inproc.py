"""Host time per round of the K ``Repository.upload`` calls, from the
benchmark's span around them."""
import statistics


def read(ctx):
    d = ctx.spans.durations("upload")
    return 1e3 * statistics.fmean(d) if d else None
