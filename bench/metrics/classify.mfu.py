"""The whole classifier step's share of the chip's peak: the conv layer
as a direct correlation plus the head's two dense layers, times the
clips classified in the traced window, over the window and the peak."""

from bench import work


def read(ctx):
    cell = ctx.cell
    if ctx.peaks is None:
        return None
    t0, t1 = cell.window
    n = cell.completed_in(t0, t1) * cell.mix["clips_per_request"]
    if n == 0 or t1 <= t0:
        return None
    per = work.classifier_flops(cell.cfg)
    return 100.0 * n * per / (t1 - t0) / ctx.peaks.flops_per_s
