"""Share of the kernel rows computed during the run's window that a
request asked for: per pooled row that carried a request, the kernels of
its requests' tenants over the ``n_out`` rows the row computed, from the
engine's ``pool_stats()`` counters before and after the window.  A
program without those counters gives nothing."""


def read(ctx):
    before, after = getattr(ctx.cell, "pool_window", (None, None))
    if not before or not after or "kernel_rows_dispatched" not in after:
        return None
    asked = after["kernel_rows_requested"] - before["kernel_rows_requested"]
    done = after["kernel_rows_dispatched"] - before["kernel_rows_dispatched"]
    if done <= 0:
        return None
    return 100.0 * asked / done
