"""The whole query's share of the chips' peak while they ran it: the
model's operations per scored row (``bench/models/<kind>.py``,
``flops_per_row``) times the rows the traced window's queries scored
(those that pass the query's relational WHERE), over the seconds in which
an operation ran on the device (``bench/trace.py``, summed over the cell's
chips) times the peak of ``bench/peaks.json``.  A tree ensemble is counted
in its dense GEMM form on its own unpadded, unpruned sizes, whatever
strategy serves it; a strategy that skips nodes could therefore read
above what it computes."""


def read(ctx):
    busy = ctx.trace.mean_busy_s * ctx.chips
    if not ctx.work.scored_rows or busy <= 0:
        return None
    return (100.0 * ctx.flops_per_row * ctx.work.scored_rows
            / (busy * ctx.peaks["flops_per_s"]))
