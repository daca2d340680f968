"""Host seconds per block inside the device calls of the signature
flush, on any thread: the program's `dispatch:<site>` spans of the
signature sites, each around one device call with its transfers and
readback.  Host wall time over the calls, not device time."""
from benchmark.program_spans import seconds_per_block

SITES = ("sigpipe.hash_to_g2_batch", "ops.g1_aggregate", "ops.msm",
         "ops.pairing_fold", "ops.pairing_product", "bls.pairing_check")


def read(ctx):
    return seconds_per_block(ctx, {"dispatch:" + site for site in SITES})
