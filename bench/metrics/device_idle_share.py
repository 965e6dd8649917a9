"""Share of the profiled span in which no operation ran on the device, in
percent: 1 − (union of operation intervals) / (span on the host clock)."""


def read(ctx):
    span = ctx.window.trace_span
    if ctx.trace is None or span is None or not ctx.trace["busy_s"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / (span[1] - span[0]))
