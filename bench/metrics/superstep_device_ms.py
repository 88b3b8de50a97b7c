"""Device time of one launch of the jitted protocol super-step program (the
shard_map step of core/distributed), from the trace's program events,
averaged over launches and chips."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.program_n["superstep"] <= 0:
        return None
    return 1e3 * tr.program_s["superstep"] / tr.program_n["superstep"]
