"""Profiler spans at the program's layer boundaries.

`span(name)` is a `jax.profiler.TraceAnnotation`: while a profiler
session runs, each span lands in the session's `.xplane.pb` as a host
event on the thread that opened it, on the same clock as the device
trace, so a trace viewer (or `benchmark/trace.py`) can say what the
host was doing while the device sat idle.  With no session running,
entering and leaving a span costs one `TraceMe` check, under a
microsecond on a CPU host.  There is no switch: an operator turns
spans on by taking a profile (docs/sigpipe.md, "Profiling a node").

Spans record nothing else.  No wall-clock reading reaches the `METRICS`
counters, whose snapshots must replay bit-identically per (scenario,
seed) in the scenario tier.

Every span name is declared here: the names in `SPANS`, plus
`dispatch:<site>` (`DISPATCH`) for a site registered in
`resilience/sites.py`, which `resilience.supervisor.dispatch` opens
around every device call.  Each annotation is labelled `LABEL_PREFIX +
name`: the trace reduction of the repo's benchmark (`benchmark/
trace.py`) keeps the host events that carry that prefix and reads them
under their bare names, beside the spans its harness installs
(`benchmark/spans.json`) and its `window`.  A name here equal to one
of those would be summed twice by the harness's readers, which
`tests/benchmark/test_bench_program_spans.py` rules out.

Like `utils/nodectx.py` and `resilience/sites.py`, this module imports
only the standard library.  `TraceAnnotation` is resolved on the first
span opened after JAX is imported: before that no profiler session
can exist, so a span is a shared no-op context and an oracle-only
process never imports JAX through it.
"""
from __future__ import annotations

import sys
from contextlib import nullcontext

LABEL_PREFIX = "bench:"
DISPATCH = "dispatch:"

# what each one bounds: the table in docs/sigpipe.md, "Profiling a node"
SPANS = frozenset({
    "sigpipe.join", "sigpipe.leg_join", "sigpipe.prepare",
    "epoch.collect", "epoch.writeback", "epoch.tail",
    "merkle.reroot", "ssz.copy",
    # the steps of process_block (specs/base.py BLOCK_STEPS)
    "block.header", "block.randao", "block.eth1_data",
    "block.operations", "block.sync_aggregate", "block.withdrawals",
    "block.execution_payload", "block.execution_payload_header",
})

_NULL = nullcontext()
_annotation = None


def _resolve():
    global _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    return TraceAnnotation


def span(name: str):
    """A context manager that marks `name` in a running profile."""
    annotation = _annotation
    if annotation is None:
        if "jax" not in sys.modules:
            return _NULL
        annotation = _resolve()
    return annotation(LABEL_PREFIX + name)


def declared(name: str) -> bool:
    """Whether `name` is a span this module declares."""
    if name.startswith(DISPATCH):
        from ..resilience import sites
        site = name[len(DISPATCH):]
        return sites.is_registered(site) and \
            sites.site(site).kind == sites.DISPATCH
    return name in SPANS
