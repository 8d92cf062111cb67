"""Mean host time of one ``ContributorClient.submit`` in the window (the
client's sketch and npz write), from the benchmark's span around it."""
import statistics


def read(ctx):
    d = ctx.spans.durations("submit")
    return 1e3 * statistics.fmean(d) if d else None
