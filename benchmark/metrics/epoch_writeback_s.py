"""Host seconds per block in the fused epoch's writeback after its
sweep (balances, inactivity scores, registry, effective balances): the
program's `epoch.writeback` span."""
from benchmark.program_spans import seconds_per_block


def read(ctx):
    return seconds_per_block(ctx, {"epoch.writeback"})
