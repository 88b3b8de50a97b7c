"""Host wall time per published snapshot, from the program's counters:
repro_publish_seconds_total / repro_publish_total over the window."""


def read(ctx):
    n = ctx.counter("repro_publish_total")
    if n <= 0:
        return None
    return 1e3 * ctx.counter("repro_publish_seconds_total") / n
