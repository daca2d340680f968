"""The per-layer readers' view of the program's own spans
(`consensus_specs_tpu/utils/spans.py`): the trace reduction keeps them
beside the harness's spans, under their bare names.  A program without
them, an older commit, leaves the metric out instead of reading 0."""
from benchmark.trace import span_ns


def seconds_per_block(ctx, names, thread=None):
    """Seconds per block covered by the spans named in `names` (only
    those on `thread`, if given), or None when the block's trace holds
    none of them."""
    spans = [sp for sp in ctx.reduced.spans
             if sp[0] in names and (thread is None or sp[1] == thread)]
    if not spans:
        return None
    return span_ns(spans, names) / 1e9 / ctx.blocks
