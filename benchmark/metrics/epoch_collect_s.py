"""Host seconds per block in the fused epoch's prefix, up to its sweep
dispatch (state columns, participation, justification, the sweep's
inputs): the program's `epoch.collect` span."""
from benchmark.program_spans import seconds_per_block


def read(ctx):
    return seconds_per_block(ctx, {"epoch.collect"})
