"""Mean host time of the program's ``repo.finalize`` span, one a round:
the screen's wait on the fuse's all-reduce (``repo.screen_sync``), the
screen, and the publish of the fused base."""
import statistics


def read(ctx):
    d = ctx.program.durations("repo.finalize")
    return 1e3 * statistics.fmean(d) if d else None
