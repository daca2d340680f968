"""Host seconds per block the block thread waits for its signature
flush's verdicts: the program's `sigpipe.join` spans on the window's
thread.  The part of the flush that lies on the block's critical path."""
from benchmark.program_spans import seconds_per_block


def read(ctx):
    return seconds_per_block(ctx, {"sigpipe.join"},
                             thread=ctx.reduced.thread)
