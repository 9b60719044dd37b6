"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device's op intervals over the window, taken from
the profiler trace (``bench/trace.py``), the mean over the cell's chips."""


def read(ctx):
    return 100.0 * ctx.trace.mean_idle_share
