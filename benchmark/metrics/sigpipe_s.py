"""Host seconds per block inside the signature pipeline's spans: set
collection and the batch flush (which may run on the flush thread)."""
from benchmark.trace import span_ns


def read(ctx):
    ns = span_ns(ctx.reduced.spans, {"sigpipe.collect", "sigpipe.flush"})
    return ns / 1e9 / ctx.blocks if ns else None
