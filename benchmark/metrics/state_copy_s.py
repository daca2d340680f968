"""Host seconds per block copying SSZ containers inside the window: the
program's `ssz.copy` spans, there the replay's copy of the pre-state."""
from benchmark.program_spans import seconds_per_block


def read(ctx):
    return seconds_per_block(ctx, {"ssz.copy"})
