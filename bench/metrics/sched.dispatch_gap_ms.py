"""Mean time in the batcher thread from the end of one ``search_batch``
call to the start of the next: batch forming and the scheduler's own
bookkeeping while a queue waits."""


def read(ctx):
    gaps = ctx.cell.dispatch_gaps_s()
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
