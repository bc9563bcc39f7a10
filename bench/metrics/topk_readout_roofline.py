"""Share of the roofline in the device time of the fused top-k readout
(the Pallas kernel of ``topk_readout_pallas``): the least time reading
every valid score of the window's dispatches takes at the chip's peaks,
over the kernel's traced time."""

from bench import work

KERNEL = "topk_readout_pallas"


def read(ctx):
    seconds = ctx.trace.kernel_s(KERNEL)
    if seconds <= 0:
        return None
    return work.percent_of_roofline(
        ctx.cell.dispatch_work()["topk_readout"], seconds, ctx.peaks
    )
