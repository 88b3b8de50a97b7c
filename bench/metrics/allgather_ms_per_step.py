"""Device time of the sites-to-coordinator all-gather per super-step: the
trace's all-gather op time per chip over the super-step launches.  Only a
step whose sites span chips has one to read."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.kernel_n["all_gather"] <= 0 or tr.program_n["superstep"] <= 0:
        return None
    return 1e3 * tr.kernel_s["all_gather"] / tr.devices / tr.program_n["superstep"]
