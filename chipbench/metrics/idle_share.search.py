"""Share of the traced search window in which no operation ran on the
device: 1 - busy / window, from the profiler trace."""


def read(ctx):
    if ctx.trace.window_s <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
