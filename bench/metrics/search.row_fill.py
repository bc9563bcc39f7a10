"""Share of the pooled rows dispatched during the run's window that
carry a request: rows that carried one over those plus the zero rows
that padded a pool group's batch to its row bucket, from the engine's
``pool_stats()`` counters before and after the window."""


def read(ctx):
    before, after = getattr(ctx.cell, "pool_window", (None, None))
    if not before or not after or "rows_padded" not in after:
        return None
    rows = after["rows_dispatched"] - before["rows_dispatched"]
    padded = after["rows_padded"] - before["rows_padded"]
    if rows + padded <= 0:
        return None
    return 100.0 * rows / (rows + padded)
