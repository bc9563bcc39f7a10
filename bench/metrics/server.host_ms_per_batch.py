"""Mean host time per ``search_batch`` call: the call's span (a
``TraceAnnotation`` of the harness) less the device-busy time inside
it — grouping, grating fetches, dispatch, the result copy and slicing."""


def read(ctx):
    spans = ctx.trace.spans("bench.search_batch")
    if not spans:
        return None
    total = sum(e - s for s, e in spans) * 1e-9
    return 1e3 * (total - ctx.trace.overlap_busy_s(spans)) / len(spans)
