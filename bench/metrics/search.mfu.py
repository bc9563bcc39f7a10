"""The whole served search step's share of the chip's peak: the direct
3-D correlation a request asks for (valid positions × kernels × taps ×
2), times the requests answered in the traced window, over the window
and the peak."""

from bench import work


def read(ctx):
    cell = ctx.cell
    if ctx.peaks is None:
        return None
    t0, t1 = cell.window
    n = cell.completed_in(t0, t1)
    if n == 0 or t1 <= t0:
        return None
    g = cell.geometry
    per = work.direct_correlation_flops(
        g.frame_hw, g.frames, g.kernel, cell.cfg["kernels_per_tenant"],
        g.channels,
    )
    return 100.0 * n * per / (t1 - t0) / ctx.peaks.flops_per_s
