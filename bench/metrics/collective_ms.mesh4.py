"""Device time a round in collective operations (all-reduce, all-gather,
all-to-all, collective-permute, reduce-scatter, with their start and done
halves), averaged over the chips: the fuse's one all-reduce and whatever
the restaging of each upload into the sharded layout moves between
chips."""

KINDS = ("all-reduce", "all-gather", "all-to-all", "collective-permute",
         "reduce-scatter")


def read(ctx):
    t = ctx.trace
    rounds = ctx.counters.get("rounds", 0)
    if not t or not t["devices"] or not rounds:
        return None
    secs = sum(v for name, v in t["op_s"].items()
               if any(k in name for k in KINDS))
    if secs <= 0:
        return None
    return 1e3 * secs / t["devices"] / rounds
