"""The query's least HBM traffic (``bench/counts.py``: each input column
read once, the output written once) times the traced window's queries,
over the seconds in which an operation ran on the device
(``bench/trace.py``, summed over the cell's chips) times the HBM bandwidth
of ``bench/peaks.json``."""


def read(ctx):
    busy = ctx.trace.mean_busy_s * ctx.chips
    if not ctx.work.queries or busy <= 0:
        return None
    return (100.0 * ctx.bytes_per_query * ctx.work.queries
            / (busy * ctx.peaks["hbm_bytes_per_s"]))
