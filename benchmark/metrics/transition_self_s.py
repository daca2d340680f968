"""Host seconds per block in `state_transition` that the spans of the
layers below it do not cover (the epoch sweep, the merkle root, the
signature pipeline's collection and flush, on any thread): the spec's
own Python, and its waits on nothing else."""
from benchmark.trace import self_ns

BELOW = {"process_epoch", "hash_tree_root", "sigpipe.collect",
         "sigpipe.flush"}


def read(ctx):
    ns = self_ns(ctx.reduced.spans, "state_transition", BELOW)
    return ns / 1e9 / ctx.blocks if ns else None
