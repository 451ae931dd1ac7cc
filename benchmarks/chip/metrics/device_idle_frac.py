"""device_idle_frac: 1 - (union of the device's op intervals / the traced
stretch), mean over the cell's chips.  Profiler trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 1.0 - ctx.trace.mean_busy_s / ctx.trace.window_s
