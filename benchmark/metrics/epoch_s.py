"""Seconds per block in the `process_epoch` span."""
from benchmark.trace import span_ns


def read(ctx):
    ns = span_ns(ctx.reduced.spans, {"process_epoch"})
    return ns / 1e9 / ctx.blocks if ns else None
