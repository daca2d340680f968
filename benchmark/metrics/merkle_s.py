"""Host seconds per block in incremental merkle re-roots, on any
thread (plan, sweep dispatch, commit, guard): the program's
`merkle.reroot` spans."""
from benchmark.program_spans import seconds_per_block


def read(ctx):
    return seconds_per_block(ctx, {"merkle.reroot"})
