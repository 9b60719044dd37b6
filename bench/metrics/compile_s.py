"""Seconds of XLA backend compiles during set-up, persistent-cache reads
included, from JAX's monitoring events (``bench/compile_meter.py``)."""


def read(ctx):
    return ctx.compile_s
