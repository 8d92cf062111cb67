"""Host time per round spent in ``ColdService.run_once`` (admission, spill
reads, staging, the fuse, the publish), from the benchmark's spans around
each cycle, summed over the window's cycles and divided by its rounds."""


def read(ctx):
    d = ctx.spans.durations("run_once")
    n = ctx.counters.get("rounds", 0)
    return 1e3 * sum(d) / n if d and n else None
