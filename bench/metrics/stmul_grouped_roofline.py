"""Share of the roofline in the device time of the grouped spectral MAC
(the Pallas kernel of ``spectral_mac_grouped_pallas``): the least time
the useful MAC work of the window's dispatches needs at the chip's
peaks, over the kernel's traced time."""

from bench import work

KERNEL = "spectral_mac_grouped_pallas"


def read(ctx):
    seconds = ctx.trace.kernel_s(KERNEL)
    if seconds <= 0:
        return None
    return work.percent_of_roofline(
        ctx.cell.dispatch_work()["stmul_grouped"], seconds, ctx.peaks
    )
