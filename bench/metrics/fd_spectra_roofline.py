"""Roofline share of the publish-time spectrum kernels (the batched Gram and
projection in fd_spectra): the least time the chip needs for the Gram and
projection of every snapshot published in the window, over the kernels'
device time in the trace."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.kernel_n["fd_spectra"] <= 0:
        return None
    work = ctx.roofline.fd_spectra(ctx.counter("repro_publish_total"),
                                   ctx.sketch_rows, int(ctx.config["stream"]["d"]))
    return ctx.roofline.share_pct(work, tr.kernel_s["fd_spectra"], ctx.device_kind)
